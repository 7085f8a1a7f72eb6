"""Serving benchmark: steady-state throughput, request latency and TTFT
percentiles, with and without injected soft faults, for three decode engines:

  * ``stepwise``         — PR-1 per-token decode (one dispatch + host sync per
    token);
  * ``window8_blocking`` — zero-sync decode windows (``Replica(window=8,
    overlap=False)``): K greedy steps fused on device, deferred fault
    detection, double-buffered commit — but admission/LFLR still a blocking
    full-prompt prefill between windows;
  * ``window8_overlap``  — stall-free serving (``overlap=True``): chunked
    prefill fused into the decode windows, admission and LFLR recovery as
    background lanes, zero host stalls.

Requests carry a non-trivial prompt (``PROMPT_LEN``) and outnumber the slots
3×, so admission churn is continuous — the traffic pattern where blocking
prefill stalls dominate. Rows (name, derived, us):
  * serve_{engine}_{steady|faulted}_tokens_per_s / _latency_p* / _ttft_p*;
  * serve_window_speedup   — windowed (blocking) vs stepwise, steady;
  * serve_overlap_speedup  — overlapped vs blocking windows, faulted (the
    stall-free acceptance number: ISSUE 3 targets ≥ 1.5×);
  * serve_paged_*          — paged-KV capacity cell (ISSUE 4): on a
    mixed-length workload (prompt lens 16–1024, full-attention arch) the
    paged pool serves ≥ 2× the concurrent slots of the contiguous layout at
    an equal HBM budget, token-bit-exact, zero dropped requests;
  * serve_window8_spec_* / serve_spec_speedup — speculative decode windows
    (ISSUE 5): draft-and-verify inside the fused window on the qwen3-1.7b
    smoke config, vs the overlap engine on the same config
    (``window8_overlap_qwen3`` cells) — targets ≥ 1.4× steady tok/s at equal
    (bit-exact) output tokens;
  * serve_tracer_overhead — fault-causality tracing cell (DESIGN §3.5): an
    enabled ``repro.obs.Tracer`` on the overlap engine must cost ≤ 2% steady
    tok/s vs the no-op default (asserted; ``record["tracer"]``);
  * serve_elastic_* — elastic serve-group cells (ISSUE 8, DESIGN §3.7):
    survivor tok/s *during* a non-blocking replica join must stay ≥ 0.9× the
    survivors' steady rate (asserted — the join is a background lane, not a
    stall), plus the fleet tok/s with the fsync'd write-ahead ledger on
    (``record["elastic"]``, all guarded by ``bench_gate.py``);
  * serve_window8_tp2_* — tensor-parallel replica cells (ISSUE 9, DESIGN
    §3.8): the ``tp=2`` engine (storage sharded over the "model" mesh axis,
    per-shard error words OR-folded at retirement) on the qwen3 smoke config,
    steady + faulted, skipped when fewer than 2 devices are visible (CI
    forces them with ``XLA_FLAGS=--xla_force_host_platform_device_count=2``).

Every ``Replica``/``ServeGroup`` here is built through one validated
:class:`repro.serve.EngineConfig` — the single construction path the bench
shares with the tests and the fuzzer.

``python -m benchmarks.run --json`` appends the record to the run history in
``BENCH_serving.json`` (perf trajectory across PRs); ``python -m
benchmarks.serving --smoke`` is the CI decode-hotpath gate, ``--smoke
--overlap`` the CI overlap gate (overlapped ≥ blocking on faulted traffic),
``--smoke --paged`` the CI paged gate (bit-exact + 2× slot capacity),
``--smoke --spec`` the CI speculative gate (bit-exact steady+faulted +
non-zero draft acceptance), ``--smoke --trace`` the CI trace gate (traced
faulted traffic is token-bit-exact vs untraced, the dumped trace round-trips
through ``scripts/trace_tool.py --check``), ``--smoke --elastic`` the CI
elastic gate (kill a rank, crash the whole fleet mid-flight, restart from
the write-ahead ledger alone, regrow via the non-blocking join — zero
drops, bit-exact streams, merged two-incarnation trace validates),
``--smoke --tp`` the CI tensor-parallel gate (tp=2 token-bit-exact vs the
single-device engine steady AND under a one-shard injection, shard loss
inside a group shrinks with zero drops, dumped trace validates) and
``--smoke --multihost`` the CI multi-host gate (3 real worker *processes*
under the heartbeat supervisor; one is SIGKILL'd mid-decode — detected,
evicted within 2× the suspect timeout, outstanding requests re-routed from
the WAL with zero drops and bit-exact streams vs an in-process reference;
one is SIGSTOP'd for less than the suspect timeout — suspected but never
evicted; the merged trace passes ``trace_tool.py --check``).

All file artifacts the smokes write (traces, WALs) land under the
gitignored ``artifacts/`` directory (override with ``REPRO_ARTIFACTS``).
"""
from __future__ import annotations

import json
import os
import shutil
import time

import jax

from repro.configs import smoke_config
from repro.serve import EngineConfig, Replica, Request

#: Every smoke/bench file artifact (traces, WALs) lands under this gitignored
#: directory — CI uploads it wholesale, the repo root stays clean.
ARTIFACTS_DIR = os.environ.get("REPRO_ARTIFACTS", "artifacts")


def _artifact(name: str) -> str:
    os.makedirs(ARTIFACTS_DIR, exist_ok=True)
    return os.path.join(ARTIFACTS_DIR, name)


N_REQUESTS = 12
PROMPT_LEN = 16     # long prompts: admission/recovery prefill is real work
MAX_NEW = 32        # long generations: steady-state decode still dominates
NUM_SLOTS = 4
MAX_LEN = 64
WINDOW = 8
FAULT_EVERY = 2     # 1 injected fault per FAULT_EVERY completed requests
N_TRIALS = 5        # best-of-N per cell: shields the tracked trajectory
                    # (BENCH_serving.json) from OS scheduling noise. Trials
                    # are interleaved round-robin across cells (not run
                    # consecutively per cell) so a multi-minute slow window
                    # on a shared box cannot swallow any one cell's whole
                    # best-of and hand the bench-regression gate a bad draw
N_TRIALS_FAULTED = 7  # faulted cells swing ~2× on top of that (fault
                      # timing decides how much recovery work a run pays)
                      # — they get a deeper best-of. Best-of-N measures
                      # near-peak capability (the luckiest fault draw), so
                      # the faulted-vs-steady gap it reports is a lower
                      # bound on typical recovery cost; that bias is the
                      # price of a statistic stable enough to gate on, and
                      # the trial counts ride in record["config"] so runs
                      # stay comparable-by-construction

ENGINES = (
    ("stepwise", dict(window=0)),
    (f"window{WINDOW}_blocking", dict(window=WINDOW, overlap=False)),
    (f"window{WINDOW}_overlap", dict(window=WINDOW, overlap=True)),
)

# --- speculative decode cells (full-attention arch: verify needs positional,
# idempotent cache writes) — window8_spec vs the overlap engine on the SAME
# qwen3 smoke config, steady + faulted, interleaved best-of like every cell.
# ISSUE-5 acceptance: spec steady tok/s >= 1.4x overlap at equal output tokens.
#
# The smoke reduction keeps only 2 layers, which makes a "shallow-exit"
# drafter structurally impossible (1 of 2 layers is 60% of the model once the
# exit is counted); the spec cells therefore deepen the qwen3 smoke config to
# 8 layers so draft_layers=1 is a 1/8-depth drafter — the same depth fraction
# a 4-layer drafter has on the real 28-layer qwen3-1.7b. Both engines run the
# identical deepened config, and the workload leans on steady decode
# (max_new >> prompt_len) because that is the regime the cell measures.
SPEC_ARCH = "qwen3-1.7b"
SPEC_NUM_LAYERS = 8
SPEC_DRAFT_LEN = 5
SPEC_DRAFT_LAYERS = 1
SPEC_N_REQUESTS = 8
SPEC_MAX_NEW = 64
SPEC_MAX_LEN = 96
SPEC_RUN_KW = dict(arch=SPEC_ARCH, num_layers=SPEC_NUM_LAYERS,
                   n_requests=SPEC_N_REQUESTS, max_new=SPEC_MAX_NEW,
                   max_len=SPEC_MAX_LEN)
SPEC_ENGINES = (
    (f"window{WINDOW}_overlap_qwen3", dict(window=WINDOW, overlap=True)),
    (f"window{WINDOW}_spec", dict(window=WINDOW, overlap=True,
                                  speculate=True, draft_len=SPEC_DRAFT_LEN,
                                  draft_layers=SPEC_DRAFT_LAYERS)),
)

# --- elastic serve-group cells (ISSUE 8): survivor throughput while a spare
# joins as a background lane, and the fsync'd write-ahead-ledger cost ---
ELASTIC_RANKS = 2
ELASTIC_MAX_RANKS = 3
ELASTIC_N_REQUESTS = 96       # deep backlog: the serve must outlast the
                              # spare's warm-up + the stretched transfer so
                              # the whole join window falls in the busy
                              # phase, preceded by an equally busy baseline
                              # window
ELASTIC_PROMPT_LEN = 8
ELASTIC_MAX_NEW = 48
ELASTIC_JOIN_ROUND = 2
ELASTIC_TRANSFER_CHUNKS = 75  # stretch the join-time state transfer to
                              # ~150 ms so the join window spans many decode
                              # rounds — window retires land in bursts, and a
                              # measurement window narrower than a burst
                              # period reads pure scheduling noise
N_TRIALS_ELASTIC = 3          # group runs are whole-fleet thread harnesses —
                              # fewer, heavier trials than the replica cells

# --- paged-KV capacity cell (full-attention arch: every KV byte is pageable) --
PAGED_ARCH = "qwen3-1.7b"
PAGED_PAGE = 64
PAGED_MAX_LEN = 1088          # 17 pages: covers a 1024-token prompt + decode
PAGED_CONTIG_SLOTS = 2        # contiguous baseline → the HBM budget
PAGED_SLOTS = 4               # paged engine: 2× the slots, same pool bytes
PAGED_MIXED_PROMPTS = (16, 1024, 32, 48, 64, 128, 16, 256, 32, 512, 24, 96)
PAGED_MAX_NEW = 16

# --- tensor-parallel cells (ISSUE 9): the tp=2 engine on the qwen3 smoke
# config (the arch the TP test suite shards), steady + faulted. Fewer than
# TP visible devices is an error, never a skip; on the CPU, force host
# devices (XLA_FLAGS=--xla_force_host_platform_device_count=TP).
TP = 2
TP_ARCH = "qwen3-1.7b"
TP_ENGINE = (f"window{WINDOW}_tp{TP}",
             dict(window=WINDOW, overlap=True, tp=TP))
TP_RUN_KW = dict(arch=TP_ARCH)


def _serve_once(engine_kw: dict, fault_every: int = 0,
                n_requests: int = N_REQUESTS, max_new: int = MAX_NEW,
                num_slots: int = NUM_SLOTS, max_len: int = MAX_LEN,
                prompt_len: int = PROMPT_LEN,
                arch: str = "recurrentgemma-2b", num_layers: int = 0,
                tracer=None):
    cfg = smoke_config(arch)
    if num_layers:
        cfg = cfg.replace(num_layers=num_layers)
    # generous retry budget: the bench measures recovery *throughput*, and a
    # round-robin injection stream must not exhaust one request's retries
    rep = Replica(cfg, config=EngineConfig(num_slots=num_slots,
                                           max_len=max_len,
                                           max_request_retries=6,
                                           **engine_kw),
                  tracer=tracer)
    # every compile (decode path + LFLR prefill buckets) outside the timed
    # region, and fresh metrics so warm-up never pollutes the percentiles
    rep.warmup(max_new=max_new)
    for i in range(n_requests):
        rej = rep.submit(Request(
            id=i, prompt=tuple(3 + i + j for j in range(prompt_len)),
            max_new_tokens=max_new))
        assert rej is None, rej
    t0 = time.monotonic()
    done = 0
    injected = 0
    while not rep.idle():
        out = rep.step()
        done += len(out)
        if fault_every and done // fault_every > injected:
            # rotate the poisoned slot so injections spread across requests —
            # but only slots whose state a window will actually consume: a
            # lane that has not started its first chunk gets a fresh-cache
            # reset at dispatch, which would silently wipe the injection and
            # bias the overlap-vs-blocking faulted comparison
            eligible = [i for i in rep.sched.active_slots()
                        if not (rep.sched.slots[i].pending is not None
                                and rep.sched.slots[i].prefill_pos == 0)]
            if eligible and rep.inject_state_fault(
                    eligible[injected % len(eligible)]) is not None:
                injected += 1
    wall = time.monotonic() - t0
    summary = rep.metrics.summary()
    assert summary["statuses"].get("ok") == n_requests, summary["statuses"]
    summary["timed_tokens"] = summary["decode_tokens"]
    summary["wall_s"] = wall
    summary["tokens_per_s_timed"] = (summary["timed_tokens"] / wall
                                     if wall > 0 else 0.0)
    summary["faults_injected"] = injected
    return summary


def _serve_mixed(prompts, *, paged: bool, num_slots: int, max_len: int,
                 page_budget=None, max_new: int = PAGED_MAX_NEW):
    """Serve a mixed-length workload on the full-attention arch; returns the
    metrics summary. ``paged=False`` is the contiguous HBM-budget baseline;
    ``paged=True`` shares the same pool bytes across more slots. (Faulted
    paged traffic is gated by ``--smoke --paged`` and tests — this cell
    measures capacity.)"""
    cfg = smoke_config(PAGED_ARCH)
    rep = Replica(cfg, config=EngineConfig(
        num_slots=num_slots, max_len=max_len, window=WINDOW, overlap=True,
        max_request_retries=6, paged=paged, page_size=PAGED_PAGE,
        page_budget=page_budget))
    rep.warmup(max_new=max_new)
    for i, plen in enumerate(prompts):
        rej = rep.submit(Request(
            id=i, prompt=tuple(3 + (i + j) % 200 for j in range(plen)),
            max_new_tokens=max_new))
        assert rej is None, rej
    t0 = time.monotonic()
    n_ok = 0
    while not rep.idle():
        n_ok += sum(r.status == "ok" for r in rep.step())
    wall = time.monotonic() - t0
    s = rep.metrics.summary()
    assert n_ok == len(prompts), s["statuses"]
    s["wall_s"] = wall
    s["tokens_per_s_timed"] = s["decode_tokens"] / wall if wall > 0 else 0.0
    if paged:
        rep.alloc.check()
        s["hbm_cache_bytes"] = rep.layout.pool_bytes()
    else:
        # contiguous: every slot owns a full-capacity block
        from repro.launch.paging import PagedLayout
        from repro.models import build_model
        layout = PagedLayout(build_model(cfg).init_cache(1, max_len), max_len,
                             page_size=PAGED_PAGE, num_pages=1)
        s["hbm_cache_bytes"] = (num_slots
                               * layout.contiguous_paged_bytes_per_slot())
    return s


def bench_paged_capacity():
    """ISSUE-4 acceptance cell: mixed prompt lengths 16–1024 on a pure
    full-attention arch. The contiguous layout fits ``PAGED_CONTIG_SLOTS``
    slots in the HBM budget; the paged pool serves ``PAGED_SLOTS`` (2×)
    concurrent slots on the *same* bytes, zero dropped requests."""
    budget_pages = PAGED_CONTIG_SLOTS * (PAGED_MAX_LEN // PAGED_PAGE)
    contig = _serve_mixed(PAGED_MIXED_PROMPTS, paged=False,
                          num_slots=PAGED_CONTIG_SLOTS,
                          max_len=PAGED_MAX_LEN)
    paged = _serve_mixed(PAGED_MIXED_PROMPTS, paged=True,
                         num_slots=PAGED_SLOTS, max_len=PAGED_MAX_LEN,
                         page_budget=budget_pages)
    assert paged["hbm_cache_bytes"] <= contig["hbm_cache_bytes"], (
        "paged pool exceeds the contiguous HBM budget")
    ratio = paged["peak_active_slots"] / max(contig["peak_active_slots"], 1)
    assert ratio >= 2.0, (
        f"paged engine sustained only {paged['peak_active_slots']} concurrent "
        f"slots vs {contig['peak_active_slots']} contiguous — "
        "the capacity win has regressed")
    record = {
        "arch": f"{PAGED_ARCH}(smoke)",
        "page_size": PAGED_PAGE,
        "max_len": PAGED_MAX_LEN,
        "pool_pages": budget_pages,
        "hbm_budget_bytes": contig["hbm_cache_bytes"],
        "prompt_lens": list(PAGED_MIXED_PROMPTS),
        "slot_capacity_ratio": ratio,
        "contiguous": {
            "num_slots": PAGED_CONTIG_SLOTS,
            "tokens_per_s": contig["tokens_per_s_timed"],
            "peak_active_slots": contig["peak_active_slots"],
            "latency_p99_s": contig["latency_p99_s"],
        },
        "paged": {
            "num_slots": PAGED_SLOTS,
            "tokens_per_s": paged["tokens_per_s_timed"],
            "peak_active_slots": paged["peak_active_slots"],
            "latency_p99_s": paged["latency_p99_s"],
            "page_evictions": paged["page_evictions"],
            "peak_pages_in_use": paged["peak_pages_in_use"],
        },
    }
    rows = [
        ("serve_paged_capacity_ratio",
         f"{ratio:.1f}x_slots_at_equal_hbm", 0.0),
        ("serve_paged_mixed_tokens_per_s",
         f"{paged['tokens_per_s_timed']:.0f}tok/s_"
         f"{paged['peak_active_slots']}slots", 0.0),
        ("serve_contig_mixed_tokens_per_s",
         f"{contig['tokens_per_s_timed']:.0f}tok/s_"
         f"{contig['peak_active_slots']}slots", 0.0),
    ]
    return rows, record


def bench_tracer_overhead():
    """Tracer acceptance cell: an enabled :class:`repro.obs.Tracer` must cost
    ≤ 2% steady tok/s on the overlap engine vs the no-op default. Interleaved
    best-of-N like every other cell — per-trial noise on a shared box dwarfs
    the effect being measured, so the gate compares near-peak capability of
    the two configurations."""
    from repro.obs import Tracer

    engine_kw = dict(window=WINDOW, overlap=True)
    best: dict[str, float] = {}
    events = 0
    for _ in range(N_TRIALS):
        s = _serve_once(engine_kw)
        best["noop"] = max(best.get("noop", 0.0), s["tokens_per_s_timed"])
        tr = Tracer()
        s = _serve_once(engine_kw, tracer=tr)
        if s["tokens_per_s_timed"] > best.get("enabled", 0.0):
            best["enabled"] = s["tokens_per_s_timed"]
            events = tr.num_events
    overhead = (1.0 - best["enabled"] / best["noop"]
                if best["noop"] > 0 else 0.0)
    assert best["enabled"] >= 0.98 * best["noop"], (
        f"enabled tracer costs {overhead * 100:.1f}% tok/s "
        f"({best['enabled']:.0f} vs {best['noop']:.0f} no-op) — "
        "the hot-path span emission has regressed past the 2% budget")
    record = {
        "noop": {"tokens_per_s": best["noop"]},
        "enabled": {"tokens_per_s": best["enabled"], "events": events},
        "overhead_frac": overhead,
    }
    rows = [("serve_tracer_overhead",
             f"{overhead * 100:+.1f}%_tok/s_{events}events", 0.0)]
    return rows, record


def _elastic_requests():
    return [Request(id=i,
                    prompt=tuple(5 + i + j for j in range(ELASTIC_PROMPT_LEN)),
                    max_new_tokens=ELASTIC_MAX_NEW)
            for i in range(ELASTIC_N_REQUESTS)]


def _overlap_tokens(decode, lo: float, hi: float) -> float:
    """Committed tokens attributed to ``[lo, hi]`` (trace µs), each decode
    span's tokens spread uniformly over its duration — overlap-weighted
    attribution, so the bursty retire *points* don't alias the estimate."""
    tok = 0.0
    for e in decode:
        k = (e.get("args") or {}).get("committed", 0)
        if not k:
            continue
        d = e.get("dur", 0.0)
        if d <= 0:
            tok += k if lo <= e["ts"] <= hi else 0
            continue
        ov = min(e["ts"] + d, hi) - max(e["ts"], lo)
        if ov > 0:
            tok += k * ov / d
    return tok


def _survivor_rates(trace: dict, *, joined: int, survivors) -> tuple:
    """(tok/s during the join window, tok/s over the equal-length window just
    *before* it) for the pre-join members. The ``replica_join`` span is the
    summons-to-first-exchange window; comparing against the adjacent earlier
    window keeps both measurements in the same traffic phase (deep backlog)
    with the same member count, so the ratio isolates what the join itself
    cost the survivors — the admission ramp, the drain tail, and the
    post-join CPU contention from the third replica never enter either
    side."""
    survivors = set(survivors)
    evs = trace["traceEvents"]
    joins = [e for e in evs
             if e.get("name") == "replica_join" and e.get("pid") == joined]
    assert joins, "the summoned replica never joined"
    j = joins[0]
    t0, t1 = j["ts"], j["ts"] + j.get("dur", 0.0)
    assert t1 > t0, "empty join window"
    decode = [e for e in evs
              if e.get("name") == "decode" and e.get("pid") in survivors]
    assert decode, "survivors committed no decode windows"
    span_s = (t1 - t0) / 1e6
    during = _overlap_tokens(decode, t0, t1) / span_s
    steady = _overlap_tokens(decode, t0 - (t1 - t0), t0) / span_s
    return during, steady


def bench_elastic():
    """ISSUE-8 acceptance cells. (1) *Non-blocking join*: a 2-rank group
    serves a continuous backlog while a spare is summoned at round
    ``ELASTIC_JOIN_ROUND``; the survivors' tok/s during the join window
    (warm-up + chunked state transfer + epoch agreement) must stay ≥ 0.9×
    their steady rate — the join is a background lane, never a stall.
    (2) *Durable ledger*: the same workload with every submit/route/retire
    fsync'd to the write-ahead log — the durability cost rides the tracked
    history so a WAL hot-path regression trips the bench gate.

    The ratio is taken best-of-N and quantizes on window-retire bursts, so
    readings above 1 are normal; only a collapse toward 0 across every trial
    (a join that blocks the survivors) can fail the assertion. The gated
    history cells are the steady/durable tok/s — the ratio's burst noise
    stays out of the regression tripwire."""
    import tempfile

    from repro.serve import ServeGroup

    group = ServeGroup(smoke_config("recurrentgemma-2b"), ELASTIC_RANKS,
                       config=EngineConfig(num_slots=NUM_SLOTS,
                                           max_len=MAX_LEN, window=WINDOW,
                                           overlap=True,
                                           max_request_retries=6, trace=True),
                       max_ranks=ELASTIC_MAX_RANKS,
                       transfer_chunks=ELASTIC_TRANSFER_CHUNKS)
    best = {"ratio": 0.0, "during": 0.0, "steady": 0.0, "durable": 0.0}
    wal_stats: dict = {}
    for _ in range(N_TRIALS_ELASTIC):
        res = group.serve(_elastic_requests(), joins=[ELASTIC_JOIN_ROUND])
        assert len(res.responses) == ELASTIC_N_REQUESTS
        assert all(r.ok for r in res.responses.values())
        assert ELASTIC_RANKS in res.joined, "scheduled join never landed"
        during, steady = _survivor_rates(
            res.trace(), joined=ELASTIC_RANKS, survivors=range(ELASTIC_RANKS))
        ratio = during / steady if steady > 0 else 0.0
        if ratio > best["ratio"]:
            best.update(ratio=ratio, during=during, steady=steady)
        tmp = tempfile.mkdtemp(prefix="bench-elastic-")
        path = os.path.join(tmp, "ledger.wal")
        try:
            t0 = time.monotonic()
            dur = group.serve(_elastic_requests(), ledger_path=path)
            wall = time.monotonic() - t0
            assert len(dur.responses) == ELASTIC_N_REQUESTS
            assert all(r.ok for r in dur.responses.values())
            tps = dur.summary()["decode_tokens"] / wall if wall > 0 else 0.0
            if tps > best["durable"]:
                best["durable"] = tps
                wal_stats = {"records": sum(1 for _ in open(path)),
                             "bytes": os.path.getsize(path)}
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    assert best["ratio"] >= 0.9, (
        f"survivor throughput dropped to {best['ratio']:.2f}x steady during "
        f"the replica join ({best['during']:.0f} vs {best['steady']:.0f} "
        "tok/s) — the non-blocking join has regressed into a stall")
    record = {
        "config": {"ranks": ELASTIC_RANKS, "max_ranks": ELASTIC_MAX_RANKS,
                   "n_requests": ELASTIC_N_REQUESTS,
                   "prompt_len": ELASTIC_PROMPT_LEN,
                   "max_new": ELASTIC_MAX_NEW,
                   "join_round": ELASTIC_JOIN_ROUND,
                   "transfer_chunks": ELASTIC_TRANSFER_CHUNKS,
                   "n_trials": N_TRIALS_ELASTIC},
        "steady": {"tokens_per_s": best["steady"]},
        "during_join": {"tokens_per_s": best["during"]},
        "durable": {"tokens_per_s": best["durable"], **wal_stats},
        "join_ratio": best["ratio"],
    }
    rows = [
        ("serve_elastic_join_ratio",
         f"{best['ratio']:.2f}x_survivor_tok/s_during_join", 0.0),
        ("serve_elastic_steady_tokens_per_s",
         f"{best['steady']:.0f}tok/s_{ELASTIC_RANKS}ranks", 0.0),
        ("serve_elastic_join_tokens_per_s",
         f"{best['during']:.0f}tok/s_during_join", 0.0),
        ("serve_elastic_durable_tokens_per_s",
         f"{best['durable']:.0f}tok/s_"
         f"{wal_stats.get('records', 0)}wal_records", 0.0),
    ]
    return rows, record


def bench_all():
    """Run all engine × traffic cells; returns (csv_rows, json_record)."""
    rows = []
    record = {
        "benchmark": "serving",
        "config": {"arch": "recurrentgemma-2b(smoke)",
                   "n_requests": N_REQUESTS, "prompt_len": PROMPT_LEN,
                   "max_new": MAX_NEW, "num_slots": NUM_SLOTS,
                   "max_len": MAX_LEN, "window": WINDOW,
                   "fault_every": FAULT_EVERY,
                   "n_trials": N_TRIALS,
                   "n_trials_faulted": N_TRIALS_FAULTED,
                   "spec_arch": f"{SPEC_ARCH}(smoke,{SPEC_NUM_LAYERS}L)",
                   "spec_draft_len": SPEC_DRAFT_LEN,
                   "spec_draft_layers": SPEC_DRAFT_LAYERS,
                   "spec_n_requests": SPEC_N_REQUESTS,
                   "spec_max_new": SPEC_MAX_NEW,
                   "spec_max_len": SPEC_MAX_LEN,
                   "tp": TP, "tp_arch": f"{TP_ARCH}(smoke)"},
        "engines": {},
    }
    cells = [(engine, engine_kw, label, fault_every, {})
             for engine, engine_kw in ENGINES
             for label, fault_every in (("steady", 0),
                                        ("faulted", FAULT_EVERY))]
    cells += [(engine, engine_kw, label, fault_every, SPEC_RUN_KW)
              for engine, engine_kw in SPEC_ENGINES
              for label, fault_every in (("steady", 0),
                                         ("faulted", FAULT_EVERY))]
    if len(jax.devices()) < TP:
        raise RuntimeError(
            f"the tp={TP} cells need {TP} devices, found "
            f"{len(jax.devices())} (on the CPU, set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={TP})")
    cells += [(TP_ENGINE[0], TP_ENGINE[1], label, fault_every, TP_RUN_KW)
              for label, fault_every in (("steady", 0),
                                         ("faulted", FAULT_EVERY))]
    best: dict[str, dict] = {}
    for trial in range(max(N_TRIALS, N_TRIALS_FAULTED)):
        for engine, engine_kw, label, fault_every, run_kw in cells:
            if trial >= (N_TRIALS_FAULTED if fault_every else N_TRIALS):
                continue
            s = _serve_once(engine_kw, fault_every=fault_every, **run_kw)
            key = f"{engine}/{label}"
            if (key not in best or s["tokens_per_s_timed"]
                    > best[key]["tokens_per_s_timed"]):
                best[key] = s
    for engine, engine_kw, label, fault_every, run_kw in cells:
        record["engines"].setdefault(engine, {})
        s = best[f"{engine}/{label}"]
        tps = s["tokens_per_s_timed"]
        us_per_tok = (s["wall_s"] * 1e6 / max(s["timed_tokens"], 1))
        note = (f"{s['faults_injected']}_faults_recovered" if fault_every
                else f"{N_REQUESTS}req_x_{MAX_NEW}tok")
        rows.append((f"serve_{engine}_{label}_tokens_per_s",
                     f"{tps:.0f}tok/s {note}", us_per_tok))
        for metric in ("latency", "ttft"):
            for p in ("p50", "p99"):
                v = s[f"{metric}_{p}_s"]
                rows.append((f"serve_{engine}_{label}_{metric}_{p}",
                             f"{v * 1e3:.1f}ms", v * 1e6))
        arch = run_kw.get("arch", "recurrentgemma-2b")
        nl = run_kw.get("num_layers")
        record["engines"][engine][label] = {
            "arch": f"{arch}(smoke{f',{nl}L' if nl else ''})",
            "tokens_per_s": tps,
            "latency_p50_s": s["latency_p50_s"],
            "latency_p99_s": s["latency_p99_s"],
            "ttft_p50_s": s["ttft_p50_s"],
            "ttft_p99_s": s["ttft_p99_s"],
            "wall_s": s["wall_s"],
            "timed_tokens": s["timed_tokens"],
            "faults_injected": s["faults_injected"],
            "windows": s["windows"],
            "discarded_tokens": s["discarded_tokens"],
            "prefills": s["prefills"],
            "prefill_chunks": s["prefill_chunks"],
            "prefill_chunk_tokens": s["prefill_chunk_tokens"],
            "host_stalls": s["host_stalls"],
            "host_stall_s": s["host_stall_s"],
            "retries": s["retries"],
            "acceptance_rate": s.get("acceptance_rate", 0.0),
            "tokens_per_step": s.get("tokens_per_step", 0.0),
            "draft_tokens": s.get("draft_tokens", 0),
            "rejected_draft_tokens": s.get("rejected_draft_tokens", 0),
        }
    eng = record["engines"]
    blocking, overlap = f"window{WINDOW}_blocking", f"window{WINDOW}_overlap"
    for label in ("steady", "faulted"):
        base = eng["stepwise"][label]["tokens_per_s"]
        blk = eng[blocking][label]["tokens_per_s"]
        ovl = eng[overlap][label]["tokens_per_s"]
        record[f"speedup_{label}"] = blk / base if base > 0 else 0.0
        record[f"overlap_speedup_{label}"] = ovl / blk if blk > 0 else 0.0
        record[f"overlap_ttft_p99_ratio_{label}"] = (
            eng[overlap][label]["ttft_p99_s"] /
            eng[blocking][label]["ttft_p99_s"]
            if eng[blocking][label]["ttft_p99_s"] > 0 else 0.0)
    rows.append(("serve_window_speedup",
                 f"{record['speedup_steady']:.2f}x_steady", 0.0))
    rows.append(("serve_overlap_speedup",
                 f"{record['overlap_speedup_faulted']:.2f}x_faulted", 0.0))
    spec, spec_base = f"window{WINDOW}_spec", f"window{WINDOW}_overlap_qwen3"
    for label in ("steady", "faulted"):
        base = eng[spec_base][label]["tokens_per_s"]
        record[f"spec_speedup_{label}"] = (
            eng[spec][label]["tokens_per_s"] / base if base > 0 else 0.0)
    rows.append(("serve_spec_speedup",
                 f"{record['spec_speedup_steady']:.2f}x_steady_"
                 f"acc{eng[spec]['steady']['acceptance_rate']:.2f}", 0.0))
    paged_rows, paged_record = bench_paged_capacity()
    rows.extend(paged_rows)
    record["paged"] = paged_record
    tracer_rows, tracer_record = bench_tracer_overhead()
    rows.extend(tracer_rows)
    record["tracer"] = tracer_record
    elastic_rows, elastic_record = bench_elastic()
    rows.extend(elastic_rows)
    record["elastic"] = elastic_record
    return rows, record


def run():
    rows, _ = bench_all()
    return rows


def smoke(window: int = WINDOW) -> None:
    """CI decode-hotpath gate: windowed must not be slower than stepwise.

    Tiny workload (compile time excluded by the warm request); asserts the
    window engine's steady tokens/s ≥ the per-token baseline so the gate
    fails if the zero-sync path regresses to per-token host round trips.
    """
    base = _serve_once(dict(window=0), n_requests=4, max_new=32, prompt_len=3)
    win = _serve_once(dict(window=window, overlap=False), n_requests=4,
                      max_new=32, prompt_len=3)
    b, w = base["tokens_per_s_timed"], win["tokens_per_s_timed"]
    print(f"decode-hotpath smoke: stepwise {b:.0f} tok/s, "
          f"window{window} {w:.0f} tok/s ({w / max(b, 1e-9):.2f}x)")
    # small tolerance: the real win is ≥2x, but a single OS preemption on a
    # loaded CI box must not read as a regression
    assert w >= 0.9 * b, (
        f"windowed decode ({w:.0f} tok/s) slower than stepwise ({b:.0f} "
        "tok/s) — the zero-sync window path has regressed")


def smoke_overlap(window: int = WINDOW) -> None:
    """CI overlap gate: on faulted admission-heavy traffic the overlapped
    engine must not be slower than the blocking-window engine — fails if the
    stall-free path regresses to blocking prefills between windows."""
    kw = dict(n_requests=8, max_new=24, prompt_len=PROMPT_LEN,
              fault_every=FAULT_EVERY)
    blk = _serve_once(dict(window=window, overlap=False), **kw)
    ovl = _serve_once(dict(window=window, overlap=True), **kw)
    b, o = blk["tokens_per_s_timed"], ovl["tokens_per_s_timed"]
    print(f"overlap smoke (faulted): blocking {b:.0f} tok/s "
          f"({blk['host_stalls']} stalls, {blk['host_stall_s'] * 1e3:.0f}ms "
          f"stalled), overlapped {o:.0f} tok/s ({ovl['host_stalls']} stalls) "
          f"— {o / max(b, 1e-9):.2f}x")
    assert ovl["host_stalls"] == 0, "overlapped engine blocked on a prefill"
    # same noise tolerance as the decode-hotpath gate
    assert o >= 0.9 * b, (
        f"overlapped serving ({o:.0f} tok/s) slower than blocking windows "
        f"({b:.0f} tok/s) — chunked-prefill fusion has regressed")


def smoke_paged(window: int = WINDOW) -> None:
    """CI paged gate: the paged engine must be token-bit-exact vs the
    contiguous overlap engine on identical (steady *and* faulted) traffic,
    never stall the host, and sustain ≥ 2× the contiguous slot count on a
    mixed-length workload at an equal HBM budget — small-scale versions of
    the ISSUE-4 acceptance criteria."""
    cfg = smoke_config(PAGED_ARCH)
    max_len, page = 64, 16

    def serve(paged, inject_at=None):
        rep = Replica(cfg, config=EngineConfig(
            num_slots=2, max_len=max_len, window=window, overlap=True,
            max_request_retries=6, paged=paged, page_size=page))
        reqs = [Request(id=i, prompt=tuple(5 + i + j for j in range(9)),
                        max_new_tokens=16) for i in range(5)]
        for r in reqs:
            assert rep.submit(r) is None
        out, steps = {}, 0
        while not rep.idle():
            if steps == inject_at:
                # poison a decoding lane both engines will actually consume
                eligible = [i for i in rep.sched.active_slots()
                            if rep.sched.slots[i].pending is None]
                if eligible:
                    rep.inject_state_fault(eligible[0])
            for resp in rep.step():
                out[resp.id] = resp
            steps += 1
            assert steps < 2000
        assert all(r.status == "ok" for r in out.values())
        if paged:
            rep.alloc.check()
        return rep, out

    for label, inject_at in (("steady", None), ("faulted", 8)):
        _, base = serve(False, inject_at)
        rep, got = serve(True, inject_at)
        assert sorted(got) == sorted(base)
        for i in base:
            assert got[i].tokens == base[i].tokens, (
                f"paged engine diverged from contiguous on {label} traffic "
                f"(request {i})")
        assert rep.metrics.host_stalls == 0, "paged engine stalled the host"
        print(f"paged smoke ({label}): bit-exact over {len(base)} requests")

    # capacity: mixed lens, 2× slots on the contiguous pool byte budget
    budget_pages = 2 * (max_len // page)
    prompts = (4, 40, 8, 12, 6, 32, 10, 8)

    def mixed(paged, slots):
        rep = Replica(cfg, config=EngineConfig(
            num_slots=slots, max_len=max_len, window=window, overlap=True,
            paged=paged, page_size=page,
            page_budget=budget_pages if paged else None))
        for i, plen in enumerate(prompts):
            assert rep.submit(Request(
                id=i, prompt=tuple(3 + i + j for j in range(plen)),
                max_new_tokens=8)) is None
        steps = 0
        n_ok = 0
        while not rep.idle():
            n_ok += sum(r.status == "ok" for r in rep.step())
            steps += 1
            assert steps < 4000
        assert n_ok == len(prompts), "dropped requests under paging pressure"
        return rep.metrics.peak_active_slots

    contig_peak = mixed(False, 2)
    paged_peak = mixed(True, 4)
    print(f"paged smoke (capacity): {paged_peak} concurrent slots paged vs "
          f"{contig_peak} contiguous at equal HBM budget")
    assert paged_peak >= 2 * contig_peak, (
        f"paged engine sustained {paged_peak} slots vs {contig_peak} "
        "contiguous — the capacity win has regressed")


def smoke_spec(window: int = WINDOW) -> None:
    """CI speculative gate: the spec engine must emit token-bit-exact output
    vs the overlap engine on identical steady AND faulted traffic (every
    emitted token is a full-model argmax, so draft-and-verify must be
    invisible in the stream), accept a non-zero fraction of drafts, and never
    stall the host — small-scale ISSUE-5 acceptance criteria."""
    cfg = smoke_config(SPEC_ARCH)

    def serve(speculate, inject):
        rep = Replica(cfg, config=EngineConfig(
            num_slots=2, max_len=MAX_LEN, window=window, overlap=True,
            max_request_retries=6, speculate=speculate,
            draft_len=SPEC_DRAFT_LEN, draft_layers=SPEC_DRAFT_LAYERS), seed=0)
        reqs = [Request(id=i, prompt=tuple(5 + i + j for j in range(9)),
                        max_new_tokens=16) for i in range(5)]
        for r in reqs:
            assert rep.submit(r) is None
        out, steps, injected = {}, 0, 0
        while not rep.idle():
            if inject and not injected:
                # poison a decoding lane both engines will actually consume
                eligible = [i for i in rep.sched.active_slots()
                            if rep.sched.slots[i].pending is None]
                if eligible and rep.inject_state_fault(
                        eligible[0]) is not None:
                    injected += 1
            for resp in rep.step():
                out[resp.id] = resp
            steps += 1
            assert steps < 2000
        assert all(r.status == "ok" for r in out.values())
        assert not inject or injected == 1
        return rep, out

    for label, inject in (("steady", False), ("faulted", True)):
        _, base = serve(False, inject)
        rep, got = serve(True, inject)
        assert sorted(got) == sorted(base)
        for i in base:
            assert got[i].tokens == base[i].tokens, (
                f"speculative engine diverged from overlap on {label} "
                f"traffic (request {i})")
        acc = rep.metrics.acceptance_rate()
        assert acc > 0, "speculation accepted no drafts"
        assert rep.metrics.host_stalls == 0, "spec engine stalled the host"
        print(f"spec smoke ({label}): bit-exact over {len(base)} requests, "
              f"acceptance {acc:.2f}, "
              f"{rep.metrics.tokens_per_step():.2f} tok/step")


def smoke_trace(window: int = WINDOW,
                out_path: str | None = None) -> None:
    """CI trace gate: on identical faulted overlap traffic, a replica with an
    enabled tracer must emit a token-bit-exact stream vs the no-op default
    (tracing is pure observation), the default must record zero events, and
    the dumped trace must pass the full post-mortem round-trip — every traced
    request reaches exactly one terminal span, every fault event resolves to
    a recovery lane or a terminal answer (``trace_tool.py --check`` runs the
    same validation on the artifact this gate writes)."""
    out_path = out_path or _artifact("trace-smoke.json")
    from repro.obs import Tracer, dump_trace, request_timelines, validate

    cfg = smoke_config("recurrentgemma-2b")
    n_requests = 6

    def serve(tracer):
        rep = Replica(cfg, config=EngineConfig(
            num_slots=2, max_len=MAX_LEN, window=window, overlap=True,
            max_request_retries=6), tracer=tracer)
        reqs = [Request(id=i, prompt=tuple(5 + i + j for j in range(9)),
                        max_new_tokens=16) for i in range(n_requests)]
        for r in reqs:
            assert rep.submit(r) is None
        out, steps, injected = {}, 0, 0
        while not rep.idle():
            if steps >= 4 and not injected:
                # poison a decoding lane the next window will consume
                eligible = [i for i in rep.sched.active_slots()
                            if rep.sched.slots[i].pending is None]
                if eligible and rep.inject_state_fault(
                        eligible[0]) is not None:
                    injected += 1
            for resp in rep.step():
                out[resp.id] = resp
            steps += 1
            assert steps < 2000
        assert injected == 1, "fault injection never landed"
        assert all(r.status == "ok" for r in out.values())
        return rep, out

    tr = Tracer()
    _, traced = serve(tr)
    rep_plain, plain = serve(None)
    assert sorted(traced) == sorted(plain)
    for i in plain:
        assert traced[i].tokens == plain[i].tokens, (
            f"tracing changed the token stream (request {i}) — "
            "observation must be pure")
    assert rep_plain.trace.num_events == 0, (
        "the no-op tracer recorded events")
    trace = dump_trace(out_path, tr)
    n = len(trace["traceEvents"])
    assert n > 0
    assert any(e["cat"] == "fault" for e in trace["traceEvents"]), (
        "injected fault left no fault span in the trace")
    problems = validate(trace)
    assert not problems, problems
    timelines = request_timelines(trace)
    assert len(timelines) == n_requests, (
        f"expected {n_requests} traced requests, got {len(timelines)}")
    print(f"trace smoke: bit-exact over {len(plain)} requests, {n} events "
          f"-> {out_path}, validate OK")


def smoke_elastic(window: int = WINDOW,
                  out_path: str | None = None,
                  ledger_path: str | None = None) -> None:
    """CI elastic gate: the ISSUE-8 acceptance story at smoke scale. A 3-rank
    group serves 24 requests with the durable ledger on; rank 2 is killed at
    round 2 (ULFM shrink + re-route), then the WHOLE fleet stops at round 4 —
    only the fsync'd write-ahead log survives. A new incarnation restarts
    from the log alone, replays the outstanding set onto the survivors, and
    regrows to 3 ranks by re-admitting the killed rank through the
    non-blocking join. Zero drops, every stream bit-exact vs a clean run,
    and the merged two-incarnation trace passes the post-mortem check
    (``trace_tool.py --check`` re-validates the artifacts this gate writes —
    the ledger and trace CI uploads are the ones that passed)."""
    out_path = out_path or _artifact("elastic-smoke-trace.json")
    ledger_path = ledger_path or _artifact("elastic-smoke.wal")
    from repro.core.faults import FaultSchedule, FaultSpec
    from repro.obs import validate
    from repro.obs.trace import merge_trace_dicts
    from repro.serve import ServeGroup
    from repro.serve.ledger import replay as replay_ledger

    for stale in (out_path, ledger_path):
        if os.path.exists(stale):
            os.remove(stale)     # a prior run's WAL must not replay into ours
    cfg = smoke_config("recurrentgemma-2b")
    group = ServeGroup(cfg, 3, max_ranks=3,
                       config=EngineConfig(num_slots=2, max_len=MAX_LEN,
                                           window=window, overlap=True,
                                           max_request_retries=6, trace=True))
    n = 24
    mk = lambda: [Request(id=i, prompt=tuple(5 + i + j for j in range(8)),
                          max_new_tokens=12) for i in range(n)]
    clean = group.serve(mk())
    assert all(r.ok for r in clean.responses.values())
    r1 = group.serve(mk(), faults=FaultSchedule(
        [FaultSpec(step=2, kind="kill", rank=2)]),
        ledger_path=ledger_path, crash_at=4)
    assert r1.crashed, "the fleet stop never fired"
    assert len(r1.responses) < n, "nothing was outstanding at the crash"
    r2 = group.serve_from_ledger(ledger_path, joins=[1])
    merged = {**r1.responses, **r2.responses}
    assert sorted(merged) == list(range(n)), "dropped requests across the crash"
    assert all(r.ok for r in merged.values())
    assert 2 in r2.joined, "the killed rank never rejoined"
    assert r2.replayed, "no requests were replayed from the ledger"
    for rid, resp in merged.items():
        assert tuple(resp.tokens) == tuple(clean.responses[rid].tokens), (
            f"request {rid} diverged from the clean run — the crash/replay/"
            "regrow leaked into the token stream")
    trace = merge_trace_dicts(r1.trace(), r2.trace())
    problems = validate(trace)
    assert not problems, problems
    with open(out_path, "w") as f:
        json.dump(trace, f)
    rep = replay_ledger(ledger_path)
    print(f"elastic smoke: {len(merged)}/{n} answered across the fleet crash "
          f"(bit-exact), {len(r2.replayed)} replayed from {rep.records} WAL "
          f"records, rank 2 rejoined (epoch {r2.epoch}) "
          f"-> {out_path}, {ledger_path}")


def smoke_tp(window: int = WINDOW,
             out_path: str | None = None) -> None:
    """CI tensor-parallel gate: the ISSUE-9 acceptance story at smoke scale.

    (1) *Bit-exactness*: the ``tp=2`` engine (storage sharded over the
    "model" mesh axis, compute replicated inside the shard_mapped window,
    per-shard error words OR-folded at retirement) must emit token-bit-exact
    streams vs the single-device window engine on identical traffic — steady,
    AND with a ``STATE_FAULT`` word injected on *one shard only* (the fold
    must latch it on every shard and LFLR must recover to the clean streams).
    (2) *Shard loss*: inside a 2-rank ServeGroup, losing one shard of rank 1
    is a hard fault of the whole replica — RANK_FAILED → ULFM shrink →
    re-route, zero dropped requests — and the dumped group trace passes the
    post-mortem check, shard-fanout rules included (``trace_tool.py --check``
    re-validates the artifact this gate writes)."""
    out_path = out_path or _artifact("tp-smoke-trace.json")
    import numpy as np

    from repro.core.errors import ErrorCode
    from repro.core.faults import FaultSchedule, FaultSpec
    from repro.obs import validate
    from repro.serve import ServeGroup

    ndev = len(jax.devices())
    assert ndev >= TP, (
        f"tp={TP} smoke needs {TP} devices, found {ndev} — run with "
        f"XLA_FLAGS=--xla_force_host_platform_device_count={TP}")
    cfg = smoke_config(TP_ARCH)
    n_requests = 4

    def shard_injector(shard, code, at=3):
        # one-shard word injection at dispatch `at`, window step 1, slot 0:
        # the OR-fold must make it indistinguishable from an all-shard fault
        def inject(index, shape):
            if index != at or len(shape) != 3:
                return None
            w = np.zeros(shape, np.uint32)
            w[shard, 1, 0] = np.uint32(code)
            return w
        return inject

    def serve(tp, injector=None):
        rep = Replica(cfg, config=EngineConfig(
            num_slots=2, max_len=MAX_LEN, window=window, overlap=True,
            max_request_retries=6, tp=tp), fault_injector=injector)
        reqs = [Request(id=i, prompt=tuple(5 + i + j for j in range(9)),
                        max_new_tokens=16) for i in range(n_requests)]
        for r in reqs:
            assert rep.submit(r) is None
        out, steps = {}, 0
        while not rep.idle():
            for resp in rep.step():
                out[resp.id] = resp
            steps += 1
            assert steps < 2000
        assert all(r.status == "ok" for r in out.values())
        return rep, out

    _, base = serve(1)
    for label, injector in (
            ("steady", None),
            ("faulted", shard_injector(0, int(ErrorCode.STATE_FAULT)))):
        rep, got = serve(TP, injector)
        assert sorted(got) == sorted(base)
        for i in base:
            assert got[i].tokens == base[i].tokens, (
                f"tp={TP} engine diverged from single-device on {label} "
                f"traffic (request {i})")
        counts = rep.metrics.fault_counts()
        if injector is None:
            assert not counts, f"steady tp run recorded faults: {counts}"
        else:
            assert counts.get("STATE_FAULT") == 1, (
                f"one-shard injection did not latch exactly once: {counts}")
        print(f"tp smoke ({label}): bit-exact over {len(base)} requests, "
              f"tp={TP}")

    # shard loss inside a group: RANK_FAILED -> shrink -> re-route, no drops
    group = ServeGroup(cfg, 2, config=EngineConfig(
        num_slots=2, max_len=48, window=window, overlap=True,
        max_request_retries=6, tp=TP, trace=True))
    reqs = [Request(id=i, prompt=tuple(5 + i + j for j in range(8)),
                    max_new_tokens=12) for i in range(6)]
    res = group.serve(reqs, faults=FaultSchedule(
        [FaultSpec(step=1, kind="shard_kill", rank=1, shard=1)]))
    assert sorted(res.responses) == list(range(len(reqs))), (
        "dropped requests across the shard loss")
    assert all(r.ok for r in res.responses.values())
    assert res.rerouted, "no requests were re-routed off the dead replica"
    trace = res.trace()
    names = {e.get("name") for e in trace["traceEvents"]}
    assert {"shard_loss", "replica_kill", "ulfm_shrink", "reroute"} <= names, (
        f"shard-loss causality chain incomplete: {sorted(names)}")
    problems = validate(trace)
    assert not problems, problems
    with open(out_path, "w") as f:
        json.dump(trace, f)
    print(f"tp smoke (shard loss): {len(res.responses)}/{len(reqs)} answered "
          f"after losing shard 1 of rank 1 ({len(res.rerouted)} re-routed) "
          f"-> {out_path}, validate OK")


def smoke_multihost(out_path: str | None = None,
                    ledger_path: str | None = None) -> None:
    """CI multi-host gate: the ISSUE-10 acceptance story at smoke scale.

    (1) *SIGKILL leg* (real engine): 3 worker **processes**, each owning one
    real :class:`Replica` (params rebuilt per process from the shared
    PRNGKey), serve 9 requests under the heartbeat supervisor with the
    durable WAL on; worker 1 is SIGKILL'd once 2 responses have been retired
    fleet-wide. The dead process must be *detected* by missed heartbeats
    (suspect → evict, never by the socket EOF shortcut), *mapped*
    (``RANK_FAILED`` latched into the surviving group word) and *repaired*
    (epoch shrink agreed over the socket transport, outstanding requests
    re-routed from the WAL) — zero drops, every stream token-bit-exact vs an
    in-process single-replica reference, detection-to-evict within
    ``2 × suspect_timeout``, and at least one survivor retirement lands
    *inside* the detection window (survivors never block on the dead peer).
    (2) *SIGSTOP leg* (sim backend): a worker stopped for half the suspect
    timeout and resumed must be suspected and then **cleared — never
    evicted** (the slow-but-alive false-positive guard), still zero drops
    and bit-exact. The merged two-leg trace passes the post-mortem check,
    host-eviction rules included (``trace_tool.py --check`` re-validates
    the artifact this gate writes)."""
    out_path = out_path or _artifact("multihost-smoke-trace.json")
    ledger_path = ledger_path or _artifact("multihost-smoke.wal")
    from repro.core.faults import FaultSchedule, FaultSpec
    from repro.obs import validate
    from repro.obs.trace import merge_trace_dicts
    from repro.serve import MultiHostSupervisor, sim_tokens

    if os.path.exists(ledger_path):
        os.remove(ledger_path)   # a prior run's WAL must not replay into ours
    arch = "qwen3-1.7b"
    suspect_timeout = 0.8
    n = 9
    mk = lambda: [Request(id=i, prompt=tuple(5 + i + j for j in range(8)),
                          max_new_tokens=12) for i in range(n)]
    engine = EngineConfig(num_slots=2, max_len=32)

    # --- SIGKILL leg: real replicas across real process boundaries
    sup = MultiHostSupervisor(3, backend="replica", arch=arch, config=engine,
                              suspect_timeout=suspect_timeout,
                              heartbeat_interval=0.05, trace=True,
                              ledger_path=ledger_path, timeout=180.0)
    res = sup.serve(mk(), faults=FaultSchedule(
        [FaultSpec(step=2, kind="host_kill", rank=1)]))

    # in-process reference: same arch/seed/engine as every worker process,
    # built only once the workers have exited (on an accelerator the first
    # process to touch JAX holds the chip)
    from repro.models import build_model
    cfg = smoke_config(arch)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    ref_rep = Replica(cfg, params=params, config=engine)
    ref, steps = {}, 0
    for r in mk():
        assert ref_rep.submit(r) is None
    while not ref_rep.idle():
        for resp in ref_rep.step():
            ref[resp.id] = resp
        steps += 1
        assert steps < 2000
    assert sorted(ref) == list(range(n))
    assert sorted(res.responses) == list(range(n)), (
        "dropped requests across the host loss")
    assert all(r.ok for r in res.responses.values())
    for i, resp in res.responses.items():
        assert tuple(resp.tokens) == tuple(ref[i].tokens), (
            f"request {i} diverged from the in-process reference — the "
            "process boundary / eviction / re-route leaked into the stream")
    assert res.evicted == (1,), f"expected worker 1 evicted, got {res.evicted}"
    assert res.rerouted, "no requests were re-routed off the dead worker"
    det = res.detection[1]
    lat = det["evict_ts"] - det["kill_ts"]
    assert lat <= 2 * suspect_timeout, (
        f"detection-to-evict {lat:.3f}s exceeds 2x suspect_timeout")
    mid = [rid for (ts, rank, rid) in res.retires
           if det["kill_ts"] < ts < det["evict_ts"] and rank != 1]
    assert mid, ("no survivor retired a response inside the detection "
                 "window — survivors blocked on the dead peer")

    # --- SIGSTOP leg: paused-then-resumed worker must NOT be evicted
    sup2 = MultiHostSupervisor(3, backend="sim",
                               suspect_timeout=suspect_timeout,
                               heartbeat_interval=0.05, trace=True,
                               sim_tokens_per_step=2, sim_step_delay_s=0.01,
                               timeout=120.0)
    # distinct ids: the merged two-leg trace must keep one terminal span
    # per traced request
    reqs2 = [Request(id=100 + i, prompt=tuple(5 + i + j for j in range(8)),
                     max_new_tokens=12) for i in range(n)]
    res2 = sup2.serve(reqs2, faults=FaultSchedule(
        [FaultSpec(step=1, kind="host_stop", rank=2,
                   magnitude=0.5 * suspect_timeout)]))
    assert sorted(res2.responses) == [100 + i for i in range(n)]
    for rid, resp in res2.responses.items():
        assert tuple(resp.tokens) == sim_tokens(
            tuple(5 + (rid - 100) + j for j in range(8)), 12), (
            f"request {rid} diverged from the sim oracle under SIGSTOP")
    assert res2.evicted == (), (
        f"SIGSTOP within the suspect timeout must never evict, "
        f"got {res2.evicted}")
    assert 2 in res2.stopped and 2 in res2.suspected and 2 in res2.resumed, (
        "the stop leg never exercised the suspect -> clear path")

    trace = merge_trace_dicts(res.trace(), res2.trace())
    problems = validate(trace)
    assert not problems, problems
    names = {e.get("name") for e in trace["traceEvents"]}
    assert {"host_kill", "host_suspect", "host_evict", "ulfm_shrink",
            "reroute", "epoch", "host_stop", "host_suspect_clear"} <= names, (
        f"host causality chain incomplete: {sorted(names)}")
    with open(out_path, "w") as f:
        json.dump(trace, f)
    print(f"multihost smoke: {len(res.responses)}/{n} answered after "
          f"SIGKILL of worker 1 (bit-exact, {len(res.rerouted)} re-routed, "
          f"evict {lat:.2f}s <= {2 * suspect_timeout:.2f}s, {len(mid)} "
          f"survivor retires in-window); SIGSTOP leg suspected+cleared, "
          f"0 evictions -> {out_path}, {ledger_path}")


if __name__ == "__main__":
    import sys

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if "--smoke" in sys.argv:
        if "--overlap" in sys.argv:
            smoke_overlap()
        elif "--paged" in sys.argv:
            smoke_paged()
        elif "--spec" in sys.argv:
            smoke_spec()
        elif "--trace" in sys.argv:
            smoke_trace()
        elif "--elastic" in sys.argv:
            smoke_elastic()
        elif "--tp" in sys.argv:
            smoke_tp()
        elif "--multihost" in sys.argv:
            smoke_multihost()
        else:
            smoke()
    else:
        for name, derived, us in run():
            print(f"{name},{us:.2f},{derived}")
