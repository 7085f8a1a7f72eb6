#!/usr/bin/env python3
"""Smoke run of the serving path on a TPU at qwen3-1.7b's published widths.

    python chip_smoke.py                    # one chip: serve, fault, reference
    python chip_smoke.py --four-chips       # four chips: tp=4 vs tp=1, 4-rank group
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse   # smoke widths on the CPU

This is a smoke run, not a benchmark: the seconds it prints say that the path
ran and what compiling cost, and are no throughput claim.

One chip (the default), in one process:

1. **Device check.** The first device must be a TPU; anything else exits
   non-zero before a result is printed.
2. **Serve.** ``qwen3-1.7b`` from ``configs/qwen3_1_7b.py`` unchanged (bf16,
   params from ``PRNGKey(seed)``) behind
   ``Replica(cfg, config=EngineConfig(num_slots=8, max_len=2048, window=8))``
   — the overlapped window engine — after ``warmup()``. 16 requests with
   prompt lengths drawn from the seed in 64..512 tokens, 64 new tokens each;
   every request must come back ``OK``.
3. **Fault.** The same requests again on the same engine, with
   ``inject_state_fault`` on a decoding slot midway: the window wait must
   raise (a fault record names the slot), LFLR must re-prefill it (its
   response carries a retry), every request must come back ``OK`` and every
   token stream must equal the clean phase's (same-engine determinism,
   DESIGN §3.1/§3.6).
4. **Reference.** The replica is freed, then a float32 forward pass
   (``Model.forward(..., impl="ref")`` under ``highest`` matmul precision) is
   teacher-forced on prompt + generated tokens for 2 requests. At every
   generated position the engine's token must have a reference logit within
   :data:`MARGIN_SIGMA` standard deviations (of that position's reference
   logits) of the reference maximum.

``--four-chips`` runs only the four-chip phase: ``EngineConfig(tp=4)`` against
a tp=1 replica on the same requests (equal tokens; every sharded leaf a
quarter per chip), then a 4-rank ``ServeGroup`` with each rank on its own
chip, one rank killed, no request dropped.

``--rehearse`` runs the same phases at ``smoke_config`` widths on the CPU —
the only way this script runs without a TPU.

The last line of stdout is exactly one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

#: The reference check's margin. The engine runs bf16 weights and
#: activations; the reference runs float32 at ``highest`` precision. bf16
#: rounding (2^-9 relative) through 28 residual layers moves the final hidden
#: state by a few percent, so a logit moves by a few hundredths of the logit
#: spread σ. The engine's token must score within 0.1 σ of the reference
#: maximum at every generated position; a random token scores ~4 σ below it.
MARGIN_SIGMA = 0.1

ARCH = "qwen3-1.7b"
ENGINE = dict(num_slots=8, max_len=2048, window=8)
# (requests, shortest prompt, longest prompt, new tokens per request)
TRAFFIC = (16, 64, 512, 64)
TRAFFIC_FOUR_CHIPS = (8, 64, 256, 32)
REFERENCE_REQUESTS = 2


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _say(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Backend-compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit costs only its retrieval)."""

    def __init__(self, monitoring):
        self._m = monitoring
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0

    def _duration(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __enter__(self):
        self._m.register_event_duration_secs_listener(self._duration)
        self._m.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        self._m.unregister_event_duration_listener(self._duration)
        self._m.unregister_event_listener(self._event)
        return False

    def line(self) -> str:
        return (f"{self.seconds:.1f} s in {self.compiles} backend compiles, "
                f"{self.cache_hits} persistent-cache hits")


def make_requests(seed: int, vocab: int, traffic: tuple):
    """Seeded requests: prompt lengths uniform in [lo, hi], random token ids."""
    import numpy as np
    from repro.serve import Request

    n, lo, hi, new = traffic
    rng = np.random.default_rng(seed)
    lens = rng.integers(lo, hi + 1, size=n)
    return [Request(id=i, prompt=tuple(int(t) for t in
                                       rng.integers(1, vocab, size=int(L))),
                    max_new_tokens=new)
            for i, L in enumerate(lens)]


def _fresh(requests):
    """New Request objects with the same ids, prompts and budgets."""
    from repro.serve import Request
    return [Request(id=r.id, prompt=r.prompt, max_new_tokens=r.max_new_tokens)
            for r in requests]


def _serve(replica, requests, *, inject_at=None):
    """Drive ``replica.step()`` until idle. With ``inject_at``, poison one
    decoding slot at that step (one with at least two windows of its budget
    left, so the fault lands before the request can finish). Returns
    ``(responses by id, steps, (slot, request id) or None)``."""
    for r in requests:
        _check(replica.submit(r) is None, f"request {r.id} rejected")
    out, steps, injected = {}, 0, None
    while not replica.idle():
        if inject_at is not None and injected is None and steps >= inject_at:
            K = replica.window
            for s in replica.sched.slots:
                if (s.active and not s.prefilling and s.generated
                        and len(s.generated) + 2 * K < s.req.max_new_tokens):
                    _check(replica.inject_state_fault(s.idx) == s.idx,
                           f"inject_state_fault missed slot {s.idx}")
                    injected = (s.idx, s.req.id)
                    break
        for resp in replica.step():
            out[resp.id] = resp
        steps += 1
    return out, steps, injected


def _all_ok(responses, requests, phase: str) -> None:
    _check(sorted(responses) == sorted(r.id for r in requests),
           f"{phase}: answered {sorted(responses)}")
    bad = {i: r.status for i, r in responses.items() if not r.ok}
    _check(not bad, f"{phase}: not OK: {bad}")
    for r in requests:
        got = len(responses[r.id].tokens)
        _check(got == r.max_new_tokens,
               f"{phase}: request {r.id} has {got} tokens, "
               f"wanted {r.max_new_tokens}")


def _device_line(jax) -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _memory(jax, label: str) -> None:
    for d in jax.devices():
        stats = d.memory_stats() or {}
        shown = [f"{k}={v}" for k, v in sorted(stats.items())
                 if k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")]
        _say(f"memory {label} [{d.id}]: "
             + (", ".join(shown) or "not reported by this backend"))


# ------------------------------------------------------------------ one chip
def phase_serve(jax, cfg, params, requests):
    from repro.serve import EngineConfig, Replica

    t0 = time.perf_counter()
    replica = Replica(cfg, params, config=EngineConfig(**ENGINE))
    t_build = time.perf_counter() - t0
    t0 = time.perf_counter()
    replica.warmup()
    t_warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    clean, steps, _ = _serve(replica, _fresh(requests))
    wall = time.perf_counter() - t0
    _all_ok(clean, requests, "serve")
    toks = sum(len(r.tokens) for r in clean.values())
    _say(f"serve: replica built in {t_build:.1f} s, warmup (compiles) "
         f"{t_warm:.1f} s; {len(clean)} requests OK, {toks} tokens "
         f"generated in {steps} steps, {wall:.2f} s wall "
         "(smoke run, not a benchmark)")
    return replica, clean, steps


def phase_fault(replica, requests, clean, clean_steps):
    n_faults = len(replica.metrics.faults)
    t0 = time.perf_counter()
    faulted, steps, injected = _serve(replica, _fresh(requests),
                                      inject_at=clean_steps // 2)
    wall = time.perf_counter() - t0
    _check(injected is not None, "fault: no decoding slot to poison")
    slot, rid = injected
    records = replica.metrics.faults[n_faults:]
    _check(any(slot in rec.slots for rec in records),
           f"fault: no window wait raised for slot {slot} ({records})")
    _all_ok(faulted, requests, "fault")
    _check(faulted[rid].retries >= 1,
           f"fault: request {rid} was not re-prefilled (retries=0)")
    diff = [i for i in clean if clean[i].tokens != faulted[i].tokens]
    _check(not diff, f"fault: token streams differ from clean for {diff}")
    _say(f"fault: poisoned slot {slot} (request {rid}) at step "
         f"{clean_steps // 2}; {len(records)} fault record(s), actions "
         f"{sorted({rec.action for rec in records})}; request {rid} "
         f"retries={faulted[rid].retries}; all {len(faulted)} OK and "
         f"identical to the clean streams; {steps} steps, {wall:.2f} s wall")


def phase_reference(jax, cfg, params, requests, clean):
    import jax.numpy as jnp
    import numpy as np
    from repro.models import build_model

    model32 = build_model(cfg.replace(dtype="float32"))
    picked = requests[:REFERENCE_REQUESTS]
    seqs = [list(r.prompt) + list(clean[r.id].tokens[:-1]) for r in picked]
    width = max(len(s) for s in seqs)
    # causal: right-padding changes no logit at a real position
    batch = np.zeros((len(seqs), width), np.int32)
    for b, s in enumerate(seqs):
        batch[b, :len(s)] = s

    @jax.jit
    def ref_logits(p, tokens):
        p32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), p)
        logits, _ = model32.forward(p32, tokens, impl="ref")
        return logits

    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(ref_logits(params, jnp.asarray(batch)))
    worst, agree, total = 0.0, 0, 0
    for b, r in enumerate(picked):
        gen = clean[r.id].tokens
        P = len(r.prompt)
        for i, tok in enumerate(gen):
            row = logits[b, P - 1 + i]
            _check(bool(np.all(np.isfinite(row))),
                   f"reference: non-finite logits, request {r.id} pos {i}")
            gap = float(row.max() - row[tok]) / float(row.std())
            worst = max(worst, gap)
            agree += int(int(row.argmax()) == tok)
            total += 1
            _check(gap <= MARGIN_SIGMA,
                   f"reference: request {r.id} token {i} ({tok}) scores "
                   f"{gap:.4f} sigma below the reference max "
                   f"(margin {MARGIN_SIGMA})")
    _say(f"reference: {total} generated positions of {len(picked)} requests "
         f"within {MARGIN_SIGMA} sigma of the float32 maximum (worst "
         f"{worst:.4f} sigma; engine token == reference argmax at "
         f"{agree}/{total}); {time.perf_counter() - t0:.1f} s")


def run_one_chip(jax, cfg, seed: int) -> None:
    from repro.models import build_model

    t0 = time.perf_counter()
    params = build_model(cfg).init(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    _say(f"setup: {cfg.name} ({cfg.num_layers} layers, d_model "
         f"{cfg.d_model}, vocab {cfg.vocab_size}, {cfg.dtype}), {n} params "
         f"from PRNGKey({seed}) in {time.perf_counter() - t0:.1f} s")
    requests = make_requests(seed, cfg.vocab_size, TRAFFIC)
    _say(f"traffic: {len(requests)} requests, prompt lengths "
         f"{sorted(len(r.prompt) for r in requests)}, "
         f"{TRAFFIC[3]} new tokens each")
    replica, clean, steps = phase_serve(jax, cfg, params, requests)
    _memory(jax, "after serve")
    phase_fault(replica, requests, clean, steps)
    del replica
    gc.collect()
    _memory(jax, "after freeing the replica")
    phase_reference(jax, cfg, params, requests, clean)


# ---------------------------------------------------------------- four chips
def _check_quarters(jax, tree, what: str, tp: int) -> int:
    """Every leaf sharded over the "model" axis holds 1/tp of its bytes on
    each of tp distinct devices. Returns the number of such leaves."""
    from jax.sharding import NamedSharding

    n = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        sh = leaf.sharding
        if not (isinstance(sh, NamedSharding) and "model" in
                jax.tree_util.tree_leaves(tuple(sh.spec))):
            continue
        shards = leaf.addressable_shards
        _check(len({s.device for s in shards}) == tp,
               f"{what}: a sharded leaf spans {len(shards)} devices")
        for s in shards:
            _check(s.data.nbytes * tp == leaf.nbytes,
                   f"{what}: shard of {leaf.shape} holds {s.data.shape}")
        n += 1
    _check(n > 0, f"{what}: no leaf is sharded over 'model'")
    return n


def run_four_chips(jax, cfg, seed: int) -> None:
    from repro.core.faults import FaultSchedule, FaultSpec
    from repro.models import build_model
    from repro.serve import EngineConfig, Replica, ServeGroup

    tp = 4
    _check(len(jax.devices()) >= tp,
           f"--four-chips needs {tp} devices, found {len(jax.devices())}")
    params = build_model(cfg).init(jax.random.PRNGKey(seed))
    requests = make_requests(seed, cfg.vocab_size, TRAFFIC_FOUR_CHIPS)
    _say(f"traffic: {len(requests)} requests, prompt lengths "
         f"{sorted(len(r.prompt) for r in requests)}, "
         f"{TRAFFIC_FOUR_CHIPS[3]} new tokens each")

    streams = {}
    for width in (1, tp):
        t0 = time.perf_counter()
        replica = Replica(cfg, params, config=EngineConfig(**ENGINE, tp=width))
        if width > 1:
            # the replica holds its sharded copy; free the unsharded one
            # (4 GB of chip 0) before the sharded window gathers its own
            del params
            n_p = _check_quarters(jax, replica.params, "params", width)
            n_c = _check_quarters(jax, replica.caches, "caches", width)
            _say(f"tp={width}: {n_p} param and {n_c} cache leaves sit a "
                 f"quarter per chip")
        replica.warmup()
        out, steps, _ = _serve(replica, _fresh(requests))
        _all_ok(out, requests, f"tp={width}")
        streams[width] = {i: r.tokens for i, r in out.items()}
        _say(f"tp={width}: {len(out)} requests OK in {steps} steps, "
             f"{time.perf_counter() - t0:.1f} s wall including compiles")
        del replica
        gc.collect()
    diff = [i for i in streams[1] if streams[1][i] != streams[tp][i]]
    _check(not diff, f"tp={tp} tokens differ from tp=1 for requests {diff}")
    _say(f"tp={tp} tokens equal tp=1 tokens for all {len(streams[1])} "
         "requests")

    t0 = time.perf_counter()
    group = ServeGroup(cfg, tp, config=EngineConfig(**ENGINE), seed=seed,
                       timeout=600.0)
    placed = [str(group.device_of(r)) for r in range(tp)]
    _check(len(set(placed)) == tp, f"group ranks share chips: {placed}")
    killed = 2
    result = group.serve(_fresh(requests), faults=FaultSchedule(
        [FaultSpec(step=3, kind="kill", rank=killed)]))
    _check([r.rank for r in result.reports if r.killed] == [killed],
           f"group: rank {killed} was not the one killed")
    _all_ok(result.responses, requests, "group")
    diff = [i for i in streams[1]
            if streams[1][i] != result.responses[i].tokens]
    _check(not diff, f"group tokens differ from the tp=1 replica for {diff}")
    devices = sorted(result.report(r).device for r in range(tp)
                     if r != killed)
    _check(len(set(devices)) == tp - 1,
           f"group survivors did not serve from distinct chips: {devices}")
    _say(f"group: ranks on {placed}; rank {killed} killed, "
         f"{len(result.rerouted)} requests re-routed, 0 dropped, all "
         f"{len(result.responses)} OK and equal to tp=1; survivors served "
         f"from {devices}; {time.perf_counter() - t0:.1f} s wall")


# ---------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phase (tp=4, 4-rank group)")
    ap.add_argument("--rehearse", action="store_true",
                    help="smoke widths on the CPU (JAX_PLATFORMS=cpu)")
    args = ap.parse_args(argv)

    if args.rehearse and "jax" not in sys.modules:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if not os.path.isdir(os.path.join(_SRC, "repro")):
        print(f"chip_smoke: no repro package under {_SRC}; run from a "
              "checkout", file=sys.stderr)
        return 2
    if _SRC not in sys.path:
        sys.path.insert(0, _SRC)

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    dev = _device_line(jax)
    want = "cpu" if args.rehearse else "tpu"
    if dev["platform"] != want:
        print(f"chip_smoke: expected platform {want!r}, JAX reports "
              f"{dev['platform']!r} ({dev['kind']}); "
              + ("--rehearse runs on the CPU only" if args.rehearse else
                 "no TPU found, refusing to run on another backend"),
              file=sys.stderr)
        return 1
    _say(f"device: {dev['kind']} x{dev['count']} ({dev['platform']}); "
         f"compile cache {cache_dir}")
    _memory(jax, "at start")

    from repro.configs import get_config, smoke_config
    cfg = smoke_config(ARCH) if args.rehearse else get_config(ARCH)
    t0 = time.perf_counter()
    with CompileClock(jax.monitoring) as clock:
        try:
            if args.four_chips:
                run_four_chips(jax, cfg, args.seed)
            else:
                run_one_chip(jax, cfg, args.seed)
        except SmokeFailure as e:
            print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
            return 1
    _say(f"compile: {clock.line()}")
    _say(f"total: {time.perf_counter() - t0:.1f} s wall "
         f"({'rehearsal' if args.rehearse else 'smoke run'}, "
         "not a benchmark)")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
