"""End-to-end tests for the multi-host fault domain (DESIGN §3.9).

Every "host" here is a real OS process: the supervisor spawns
``scripts/worker.py`` subprocesses, talks to them over the length-prefixed
socket protocol, runs the heartbeat failure detector, and executes host
faults by *signalling the processes* (SIGKILL / SIGSTOP+SIGCONT). The
acceptance contract under test:

* a SIGKILL'd worker is *detected* (missed heartbeats → suspect → evict,
  within ``2 × suspect_timeout``), *mapped* (``RANK_FAILED`` latched into
  the surviving group word) and *repaired* (epoch shrink agreed over the
  socket transport, outstanding requests re-routed from the durable WAL) —
  zero drops, bit-exact token streams;
* survivors keep decoding *during* detection (they never block on the dead
  peer — the star-topology emax has no collective to hang in);
* a SIGSTOP'd worker resumed within ``suspect_timeout`` is suspected and
  then cleared, never evicted (slow-but-alive ≠ dead).

The sim backend (deterministic arithmetic decode, no jit) keeps these fast;
one test runs the real Replica engine across the process boundary to pin
param-rebuild bit-exactness.
"""
import os

import pytest

from repro.core.faults import FaultSchedule, FaultSpec
from repro.obs import validate
from repro.serve import (
    AgreeDecision,
    EngineConfig,
    MultiHostSupervisor,
    Request,
    agree_round,
    sim_tokens,
)

SUSPECT_TIMEOUT = 0.6
N = 12


def mk_requests(n=N, prompt_len=8, max_new=12, id0=0):
    return [Request(id=id0 + i,
                    prompt=tuple(5 + i + j for j in range(prompt_len)),
                    max_new_tokens=max_new) for i in range(n)]


def mk_staggered(n=N, prompt_len=8):
    """Heterogeneous generation lengths: early ids retire quickly (arming
    the retire-count fault trigger) while late ids are still mid-decode, so
    a host kill always finds outstanding work to re-route."""
    return [Request(id=i, prompt=tuple(5 + i + j for j in range(prompt_len)),
                    max_new_tokens=6 + 4 * i) for i in range(n)]


def sim_oracle(reqs):
    return {r.id: sim_tokens(r.prompt, r.max_new_tokens) for r in reqs}


def sim_supervisor(nranks=3, **kw):
    kw.setdefault("suspect_timeout", SUSPECT_TIMEOUT)
    kw.setdefault("heartbeat_interval", 0.05)
    kw.setdefault("sim_tokens_per_step", 2)
    kw.setdefault("sim_step_delay_s", 0.01)
    kw.setdefault("timeout", 90.0)
    return MultiHostSupervisor(nranks, backend="sim", **kw)


# ---------------------------------------------------------------- agreement
def test_agree_round_decisions():
    # a higher agreed epoch always wins, before any close consideration
    assert agree_round(0, 3, 2) == AgreeDecision("reconfigure", 3)
    assert agree_round(5, 3, 2) == AgreeDecision("reconfigure", 3)
    # drained + agreement settled: close (or hold while a join is pending)
    assert agree_round(0, 2, 2) == AgreeDecision("close", 2)
    assert agree_round(0, 2, 2, hold_close=True) == AgreeDecision("hold", 2)
    # work remaining on the agreed epoch: keep serving
    assert agree_round(4, 2, 2) == AgreeDecision("continue", 2)


# -------------------------------------------------------------- construction
def test_supervisor_validates_eagerly():
    with pytest.raises(ValueError):
        MultiHostSupervisor(1)                       # needs >= 2 workers
    with pytest.raises(ValueError):
        MultiHostSupervisor(3, backend="gpu")        # unknown backend
    with pytest.raises(ValueError):
        MultiHostSupervisor(3, suspect_timeout=0.0)  # detector params
    with pytest.raises(ValueError):
        MultiHostSupervisor(3, evict_factor=3.0)


def test_one_replica_worker_per_chip(monkeypatch):
    """Replica workers each get a chip of their own; more workers than
    chips is refused up front instead of hanging at start-up."""
    from repro.serve import multihost

    monkeypatch.setattr(multihost, "host_tpu_chips", lambda: 2)
    with pytest.raises(ValueError, match="one process per chip"):
        MultiHostSupervisor(3, backend="replica")
    sup = MultiHostSupervisor(2, backend="replica")
    assert [sup._worker_env(r)["TPU_VISIBLE_CHIPS"] for r in (0, 1)] == \
        ["0", "1"]


def test_sim_workers_stay_off_accelerators(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert sim_supervisor()._worker_env(0)["JAX_PLATFORMS"] == "cpu"
    from repro.serve.multihost import host_tpu_chips
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert host_tpu_chips() == 0


def test_rejects_device_fault_kinds():
    sup = sim_supervisor()
    with pytest.raises(ValueError, match="host faults"):
        sup.serve(mk_requests(2), faults=FaultSchedule(
            [FaultSpec(step=1, kind="kill", rank=0)]))


# ------------------------------------------------------------------ clean run
def test_clean_run_is_bit_exact_and_stable():
    reqs = mk_requests()
    res = sim_supervisor(trace=True).serve(reqs)
    assert sorted(res.responses) == [r.id for r in reqs]
    assert all(r.ok for r in res.responses.values())
    oracle = sim_oracle(reqs)
    for rid, resp in res.responses.items():
        assert tuple(resp.tokens) == oracle[rid]
    assert res.evicted == () and res.suspected == () and res.rerouted == ()
    assert res.epoch == 0
    assert not validate(res.trace())


# --------------------------------------------------------- SIGKILL: the story
def test_sigkill_detect_map_repair_zero_drop():
    """The tentpole contract end to end: SIGKILL a worker process
    mid-decode; survivors keep retiring during detection; the dead host is
    suspected, evicted within the latency bound, membership repaired through
    an epoch shrink, outstanding work re-routed from the WAL — zero drops,
    every stream bit-exact."""
    reqs = mk_staggered()
    sup = sim_supervisor(trace=True)
    res = sup.serve(reqs, faults=FaultSchedule(
        [FaultSpec(step=3, kind="host_kill", rank=2)]))

    # zero drops, bit-exact
    assert sorted(res.responses) == [r.id for r in reqs]
    assert all(r.ok for r in res.responses.values())
    oracle = sim_oracle(reqs)
    for rid, resp in res.responses.items():
        assert tuple(resp.tokens) == oracle[rid], (
            f"request {rid} diverged across the host loss")

    # detected + repaired
    assert res.evicted == (2,)
    assert res.rerouted, "nothing re-routed off the dead worker"
    assert res.epoch >= 1, "membership was never repaired"
    det = res.detection[2]
    assert det["suspect_ts"] > det["kill_ts"]
    assert det["evict_ts"] - det["kill_ts"] <= 2 * SUSPECT_TIMEOUT, (
        "detection-to-evict exceeded the 2x suspect_timeout bound")

    # survivors never block: retirements land INSIDE the detection window
    in_window = [rid for (ts, rank, rid) in res.retires
                 if det["kill_ts"] < ts < det["evict_ts"] and rank != 2]
    assert in_window, ("no survivor retired a response between the kill and "
                       "the eviction — survivors blocked on the dead peer")

    # the trace tells the whole causal story and passes the post-mortem
    # rules (host_evict needs a preceding host_suspect + a following epoch
    # that excludes the dead rank)
    trace = res.trace()
    names = {e.get("name") for e in trace["traceEvents"]}
    assert {"host_kill", "host_suspect", "host_evict", "replica_kill",
            "ulfm_shrink", "reroute", "epoch", "rank_failed"} <= names, (
        f"causality chain incomplete: {sorted(names)}")
    assert not validate(trace)
    # RANK_FAILED was latched by the *survivors* (the mapped group word)
    latched = [e for e in trace["traceEvents"]
               if e.get("name") == "rank_failed"]
    assert latched and all(e["pid"] != 2 for e in latched)


def test_sigkill_with_wal_reroutes_durably(tmp_path):
    """The re-route across the process loss is WAL-backed: every request has
    a retire record, the dead worker's outstanding ones have route records
    onto survivors, and the replayed ledger agrees with the live outcome."""
    from repro.serve.ledger import replay as replay_ledger

    wal = str(tmp_path / "multihost.wal")
    reqs = mk_staggered()
    res = sim_supervisor(ledger_path=wal).serve(reqs, faults=FaultSchedule(
        [FaultSpec(step=3, kind="host_kill", rank=1)]))
    assert sorted(res.responses) == [r.id for r in reqs]
    assert res.evicted == (1,)
    assert res.rerouted
    rep = replay_ledger(wal)
    assert sorted(rep.responses) == [r.id for r in reqs]
    assert rep.outstanding() == []
    assert rep.epoch >= 1
    assert 1 not in rep.members
    # the dead worker's outstanding requests were re-routed on the record:
    # their last known owner in the replayed WAL is a survivor
    for rid in res.rerouted:
        assert rep.routes[rid] != 1


# ------------------------------------------------- SIGSTOP: false positives
def test_sigstop_within_timeout_is_never_evicted():
    """The acceptance criterion's guard: a worker stopped for less than
    ``suspect_timeout`` and resumed must be suspected (the detector noticed)
    and cleared (the late beat proved liveness) but NEVER evicted — and the
    run stays zero-drop bit-exact."""
    reqs = mk_requests()
    res = sim_supervisor(trace=True).serve(reqs, faults=FaultSchedule(
        [FaultSpec(step=2, kind="host_stop", rank=1,
                   magnitude=0.5 * SUSPECT_TIMEOUT)]))
    assert sorted(res.responses) == [r.id for r in reqs]
    oracle = sim_oracle(reqs)
    for rid, resp in res.responses.items():
        assert tuple(resp.tokens) == oracle[rid]
    assert res.stopped == (1,)
    assert res.evicted == (), (
        f"SIGSTOP under suspect_timeout evicted {res.evicted} — the "
        "slow-but-alive false-positive guard is broken")
    assert 1 in res.suspected and 1 in res.resumed
    assert res.epoch == 0, "membership changed without a death"
    trace = res.trace()
    names = {e.get("name") for e in trace["traceEvents"]}
    assert {"host_stop", "host_resume", "host_suspect",
            "host_suspect_clear"} <= names
    assert "host_evict" not in names
    assert not validate(trace)


def test_stop_then_kill_interleaving():
    """A stopped-and-resumed worker and a killed one on the same run: only
    the killed one is evicted, the resumed one finishes its share."""
    reqs = mk_requests()
    res = sim_supervisor(trace=True).serve(reqs, faults=FaultSchedule([
        FaultSpec(step=1, kind="host_stop", rank=0,
                  magnitude=0.4 * SUSPECT_TIMEOUT),
        FaultSpec(step=4, kind="host_kill", rank=2),
    ]))
    assert sorted(res.responses) == [r.id for r in reqs]
    oracle = sim_oracle(reqs)
    for rid, resp in res.responses.items():
        assert tuple(resp.tokens) == oracle[rid]
    assert res.evicted == (2,)
    assert res.stopped == (0,)
    assert 0 not in res.evicted
    assert not validate(res.trace())


# ------------------------------------------------------- real engine backend
@pytest.mark.slow
def test_replica_backend_bit_exact_across_process_kill():
    """The real engine across real process boundaries: every worker process
    rebuilds params from the shared PRNGKey, one is SIGKILL'd mid-decode,
    and the surviving streams stay token-bit-exact vs an in-process
    single-replica reference (proving param rebuild + eviction + re-route
    never leak into the model's token stream)."""
    import jax

    from repro.configs import smoke_config
    from repro.models import build_model
    from repro.serve import Replica

    arch = "qwen3-1.7b"
    engine = EngineConfig(num_slots=2, max_len=32)
    reqs = mk_requests(n=8, max_new=8)

    cfg = smoke_config(arch)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    ref_rep = Replica(cfg, params=params, config=engine)
    ref, steps = {}, 0
    for r in mk_requests(n=8, max_new=8):
        assert ref_rep.submit(r) is None
    while not ref_rep.idle():
        for resp in ref_rep.step():
            ref[resp.id] = resp
        steps += 1
        assert steps < 2000

    sup = MultiHostSupervisor(3, backend="replica", arch=arch, config=engine,
                              suspect_timeout=0.8, heartbeat_interval=0.05,
                              timeout=180.0)
    res = sup.serve(reqs, faults=FaultSchedule(
        [FaultSpec(step=2, kind="host_kill", rank=1)]))
    assert sorted(res.responses) == [r.id for r in reqs]
    assert all(r.ok for r in res.responses.values())
    assert res.evicted == (1,)
    for rid, resp in res.responses.items():
        assert tuple(resp.tokens) == tuple(ref[rid].tokens), (
            f"request {rid} diverged from the in-process reference")
    det = res.detection[1]
    assert det["evict_ts"] - det["kill_ts"] <= 2 * 0.8


# ------------------------------------------------------------- entry points
def test_worker_script_exists_and_is_default_cmd():
    from repro.serve.multihost import _default_worker_cmd

    cmd = _default_worker_cmd()
    assert cmd[-1].endswith(("worker.py", "repro.serve.multihost"))
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert os.path.exists(os.path.join(here, "scripts", "worker.py"))
