"""Tensor-parallel replicas: cross-shard error-word reconciliation (ISSUE 9).

A ``tp=2`` replica shards the decode/verify/prefill windows over a "model"
mesh axis (storage sharded, compute replicated inside the shard_mapped
window) and OR-folds the per-shard ``(K, slots)`` error words across the
axis, so a fault detected on any shard latches identically on all shards.
The contract under test:

* the TP engine's token streams are **bit-exact** vs the single-device
  window engine — steady state, faulted (LFLR re-prefill), paged
  (PAGE_FAULT reclaim) and speculative (DRAFT_REJECT attribution-only)
  alike;
* a shard-injected fault is indistinguishable at retirement from an
  all-shard one — same recovery, same per-``(step, slot)`` attribution,
  same streams;
* a TP shard loss inside a ServeGroup is a hard fault of the owning
  replica: RANK_FAILED → ULFM shrink → re-route, zero request drops;
* the fuzz corpus replays clean on the TP engine kit;
* :class:`~repro.serve.config.EngineConfig` is the one construction path —
  old kwargs still work for one release behind a ``DeprecationWarning``.

Runs on CPU with forced host devices (conftest sets
``XLA_FLAGS=--xla_force_host_platform_device_count=2``).
"""
import dataclasses
import pathlib
import warnings

import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core.errors import ErrorCode
from repro.core.errors import strip_codes
from repro.core.faults import FaultSchedule, FaultSpec
from repro.models import build_model
from repro.obs import postmortem
from repro.obs.trace import SHARD_TID, Tracer, merge_traces
from repro.serve import OK, EngineConfig, Replica, Request
from repro.serve.group import ServeGroup

MAX_LEN = 64
TP = 2

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < TP,
    reason=f"tp={TP} needs {TP} devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=2)")


@pytest.fixture(scope="module")
def env():
    cfg = smoke_config("qwen3-1.7b")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


def _config(tp, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("window", 4)
    kw.setdefault("overlap", True)
    return EngineConfig(tp=tp, **kw)


def _replica(env, tp, *, config_kw=None, **kw):
    cfg, params = env
    return Replica(cfg, params=params, config=_config(tp, **(config_kw or {})),
                   **kw)


def _requests(n, max_new=8, prompt_len=5):
    return [Request(id=i, prompt=tuple(5 + i + j for j in range(prompt_len)),
                    max_new_tokens=max_new) for i in range(n)]


def _serve_all(rep, reqs):
    for r in reqs:
        assert rep.submit(r) is None
    out, steps = {}, 0
    while not rep.idle():
        for resp in rep.step():
            out[resp.id] = resp
        steps += 1
        assert steps < 500
    return out


def _streams(out):
    return {i: (r.status, tuple(r.tokens)) for i, r in out.items()}


# ---------------------------------------------------------------- bit-exactness
@pytest.mark.parametrize("variant", ["plain", "paged", "spec"])
def test_tp_token_bit_exact_vs_single_device(env, variant):
    """Every TP engine variant reproduces the single-device window engine's
    token streams exactly: sharded storage + replicated compute + the
    post-scan word fold must be invisible in the output."""
    kw = {}
    if variant == "paged":
        kw = dict(paged=True, page_size=8)
    elif variant == "spec":
        kw = dict(speculate=True, draft_len=2)
    ref = _streams(_serve_all(_replica(env, 1, config_kw=kw), _requests(4)))
    got = _streams(_serve_all(_replica(env, TP, config_kw=kw), _requests(4)))
    assert got == ref
    assert all(s == OK for s, _ in got.values())


# --------------------------------------------------------- shard reconciliation
def _shard_injector(shard, code, at=3):
    """Inject ``code`` at dispatch ``at``, window step 1, slot 0 — on one
    shard (``shard >= 0``) or on every shard (``shard = -1``)."""
    def inject(index, shape):
        if index != at or len(shape) != 3:
            return None
        w = np.zeros(shape, np.uint32)
        tgt = slice(None) if shard < 0 else shard
        w[tgt, 1, 0] = np.uint32(code)
        return w
    return inject


@pytest.mark.parametrize("shard", [0, 1, -1])
def test_shard_injected_fault_latches_on_all_shards(env, shard):
    """The OR-fold across the model axis makes a fault injected on one shard
    indistinguishable from one injected on all shards: same LFLR recovery,
    same per-(step, slot) attribution, same bit-exact final streams."""
    clean = _streams(_serve_all(_replica(env, TP), _requests(3)))
    tracer = Tracer(pid=0)
    rep = _replica(env, TP, tracer=tracer,
                   fault_injector=_shard_injector(
                       shard, int(ErrorCode.STATE_FAULT)))
    got = _serve_all(rep, _requests(3))
    assert _streams(got) == clean                     # recovery invisible
    assert rep.metrics.fault_counts() == {"STATE_FAULT": 1}
    faults = [e for e in tracer.events() if e["name"] == "fault"]
    assert len(faults) == 1
    # exact (step, slot) attribution survives the cross-shard fold
    assert faults[0]["args"]["slot"] == 0
    assert faults[0]["args"]["step"] == 1
    assert faults[0]["args"]["code"] & int(ErrorCode.STATE_FAULT)
    # the reconciliation fans out to every shard lane in the trace
    fanouts = [e for e in tracer.events() if e["name"] == "shard_fanout"]
    assert sorted(e["args"]["shard"] for e in fanouts) == list(range(TP))
    assert all(e["tid"] == SHARD_TID + e["args"]["shard"] for e in fanouts)
    assert postmortem.validate(merge_traces(tracer)) == []


def test_tp_paged_page_fault_reclaim_bit_exact(env):
    """A PAGE_FAULT word injected on one shard of the paged TP engine drives
    the page-reclaim lane exactly like the single-device engine: ledger
    repaired, streams bit-exact."""
    kw = dict(paged=True, page_size=8)
    clean = _streams(_serve_all(_replica(env, TP, config_kw=kw),
                                _requests(3)))
    rep = _replica(env, TP, config_kw=kw,
                   fault_injector=_shard_injector(
                       1, int(ErrorCode.PAGE_FAULT)))
    got = _serve_all(rep, _requests(3))
    assert _streams(got) == clean
    # one fault record + the page-reclaim ledger record riding alongside it
    # (same double entry the single-device paged engine makes)
    assert rep.metrics.fault_counts().get("PAGE_FAULT") == 2
    assert any(f.action == "page_reclaim" for f in rep.metrics.faults)
    rep.alloc.check()                                 # ledger intact


def test_tp_missing_fanout_is_a_postmortem_problem():
    """The post-mortem's TP rule: a shard_fanout group that does not cover
    every shard of its (pid, window) key is flagged."""
    tr = Tracer(pid=0)
    tr.instant("shard_fanout", "shard", tid=SHARD_TID, shard=0, tp=2,
               window=3, code=1)
    probs = postmortem.validate(merge_traces(tr))
    assert any("shard" in p for p in probs), probs


# ------------------------------------------------------------------ shard loss
def test_shard_loss_shrinks_group_with_zero_drops(env):
    """kind="shard_kill": one shard of a TP replica dies → the whole replica
    is a RANK_FAILED hard fault → ULFM shrink + ledger re-route; every
    accepted request is still answered OK, and the trace chains the shard
    loss to the replica kill."""
    cfg, _ = env
    group = ServeGroup(cfg, 2, config=_config(TP, max_len=48, trace=True))
    faults = FaultSchedule(
        [FaultSpec(step=1, kind="shard_kill", rank=1, shard=1)])
    out = group.serve(_requests(6, max_new=6, prompt_len=4), faults=faults)
    assert set(out.responses) == set(range(6))        # zero drops
    assert all(r.status == OK for r in out.responses.values())
    assert out.rerouted                               # dead rank's work moved
    trace = out.trace()
    events = {e["name"] for e in trace["traceEvents"]}
    assert {"shard_loss", "replica_kill", "ulfm_shrink", "reroute"} <= events
    loss = next(e for e in trace["traceEvents"] if e["name"] == "shard_loss")
    assert loss["args"]["shard"] == 1 and loss["args"]["tp"] == TP
    assert postmortem.validate(trace) == []


def test_group_ranks_serve_from_their_own_devices(env):
    """A tp=1 group puts rank r's params and caches on device r: with as many
    devices as ranks no two ranks share one, and a kill moves the dead
    rank's requests to a survivor on its own device with zero drops."""
    cfg, _ = env
    group = ServeGroup(cfg, TP, config=_config(1, max_len=48))
    placed = [group.device_of(r) for r in range(TP)]
    assert len(set(placed)) == TP
    out = group.serve(_requests(6, max_new=6, prompt_len=4),
                      faults=FaultSchedule([FaultSpec(step=1, kind="kill",
                                                      rank=0)]))
    assert set(out.responses) == set(range(6))
    assert all(r.status == OK for r in out.responses.values())
    assert out.report(1).device == str(placed[1])


# --------------------------------------------------------------- corpus replay
_CORPUS = sorted((pathlib.Path(__file__).parent / "fuzz_corpus")
                 .glob("seed_overlap_0_*.json"))


@pytest.mark.parametrize("path", _CORPUS, ids=lambda p: p.stem)
def test_fuzz_corpus_replays_on_tp_kit(path):
    """The promoted overlap-engine corpus re-targeted at the TP kit must pass
    every oracle: completeness, bit-exactness vs the TP clean reference,
    page/trace invariants, no wedge."""
    from repro.fuzz import load_entry, run_trajectory

    traj = dataclasses.replace(load_entry(str(path))["trajectory"],
                               engine="overlap_tp")
    res = run_trajectory(traj)
    assert res.violations == [], res.violations


def test_fuzz_shard_targeted_op_round_trips_and_runs():
    from repro.fuzz import Op, Trajectory, run_trajectory

    traj = Trajectory(seed=5, engine="overlap_tp", n_requests=2, max_new=6,
                      ops=(Op("word", cycle=2, slot=0, step=1,
                              code=int(ErrorCode.STATE_FAULT), shard=1),))
    assert Trajectory.loads(traj.dumps()) == traj
    res = run_trajectory(traj)
    assert res.violations == []
    assert ("STATE_FAULT", "restore_good", "overlap_tp") in res.cells
    with pytest.raises(ValueError, match="non-TP engine"):
        Trajectory(seed=0, engine="overlap",
                   ops=(Op("word", cycle=1, code=1, shard=0),))


# ----------------------------------------------------------------- EngineConfig
class TestEngineConfig:
    def test_from_flags(self):
        c = EngineConfig.from_flags("win=8,spec=1,dlen=3,tp=2,page=16")
        assert (c.window, c.speculate, c.draft_len, c.tp) == (8, True, 3, 2)
        assert c.paged and c.page_size == 16          # page= implies paged
        assert EngineConfig.from_flags("paged,win=4").paged is True
        assert EngineConfig.from_flags("", num_slots=7).num_slots == 7
        # overrides beat the flag string
        assert EngineConfig.from_flags("slots=2", num_slots=5).num_slots == 5
        with pytest.raises(ValueError, match="unknown engine flag"):
            EngineConfig.from_flags("wnidow=8")

    def test_legacy_kwargs_are_hard_type_errors(self, env):
        # the one-release deprecation shim is gone: engine-shape kwargs on
        # the owners are plain TypeErrors now — config=EngineConfig(...) is
        # the only construction path
        cfg, params = env
        with pytest.raises(TypeError, match="num_slots"):
            Replica(cfg, params=params, num_slots=2, max_len=32, window=4)
        with pytest.raises(TypeError, match="max_len"):
            ServeGroup(cfg, 2, max_len=32)
        # no DeprecationWarning path remains anywhere in construction
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            rep = Replica(cfg, params=params,
                          config=EngineConfig(num_slots=2, max_len=32,
                                              window=4))
        assert rep.config.window == 4 and rep.config.num_slots == 2

    def test_unknown_kwarg_still_a_type_error(self, env):
        cfg, params = env
        with pytest.raises(TypeError, match="num_slotz"):
            Replica(cfg, params=params, num_slotz=2)

    def test_config_is_the_construction_path(self, env):
        c = _config(1, num_slots=3, max_len=32)
        rep = _replica(env, 1, config_kw=dict(num_slots=3, max_len=32))
        assert rep.config == c
        assert rep.sched.num_slots == 3 and rep.max_len == 32

    def test_tp_needs_devices(self, env):
        with pytest.raises(ValueError, match="devices"):
            _replica(env, 64)


# ------------------------------------------------------------------ strip_codes
def test_strip_codes_shared_helper():
    """One ignore-mask implementation serves DeviceFuture.fault_steps and the
    window enumeration (and the TP fold): attribution-only bits are stripped,
    words that carried only them zero out, and ignore=0 is the identity."""
    words = np.array([int(ErrorCode.DRAFT_REJECT),
                      int(ErrorCode.STATE_FAULT) | int(ErrorCode.DRAFT_REJECT),
                      0], np.uint32)
    got = np.asarray(strip_codes(words, int(ErrorCode.DRAFT_REJECT)))
    assert got.tolist() == [0, int(ErrorCode.STATE_FAULT), 0]
    assert strip_codes(words, 0) is words
