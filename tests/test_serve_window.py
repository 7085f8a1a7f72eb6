"""Zero-sync decode windows: token-identical to the engine-independent
greedy reference on clean traffic, bit-exact LFLR recovery from mid-window
faults, EOS/budget boundary handling (trailing tokens discarded, lanes
backfilled), the host-sync budget (syncs scale with steps / K, not steps),
and the EngineConfig surface that builds the engine."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import count_syncs, greedy_reference

from repro.configs import smoke_config
from repro.core.errors import ErrorCode
from repro.launch.steps import make_cache_prefill
from repro.models import build_model
from repro.serve import FAILED, OK, EngineConfig, Replica, Request, ServeGroup
from repro.serve.replica import SERVE_PROBES

MAX_LEN = 64


@pytest.fixture(scope="module")
def env():
    cfg = smoke_config("recurrentgemma-2b")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


def _replica(env, window, **kw):
    cfg, params = env
    conf = {k: kw.pop(k) for k in list(kw) if k in EngineConfig.__dataclass_fields__}
    conf.setdefault("num_slots", 2)
    conf.setdefault("max_len", MAX_LEN)
    return Replica(cfg, params=params,
                   config=EngineConfig(window=window, **conf), **kw)


def _requests(n, max_new=12):
    return [Request(id=i, prompt=(10 + i, 20 + i, 30 + i),
                    max_new_tokens=max_new) for i in range(n)]


def _serve_all(rep, reqs, inject_at=None):
    for r in reqs:
        assert rep.submit(r) is None
    out, steps = [], 0
    while not rep.idle():
        if inject_at is not None and steps == inject_at:
            assert rep.inject_state_fault(0) == 0
        out.extend(rep.step())
        steps += 1
        assert steps < 1000
    return {r.id: r for r in out}


# ------------------------------------------------------------- clean traffic
def test_window_decode_token_identical_to_stepwise(env):
    """The K-step on-device scan must reproduce per-token greedy decode
    exactly (the engine-independent reference: a fresh prefill per token),
    including backfill chains (5 requests over 2 slots)."""
    cfg, params = env
    clean = greedy_reference(cfg, params, _requests(5), MAX_LEN)
    for K in (1, 4, 8):
        rep = _replica(env, K)
        got = _serve_all(rep, _requests(5))
        assert sorted(got) == sorted(clean)
        for i in clean:
            assert got[i].status == OK
            assert got[i].tokens == clean[i], (K, i)
        m = rep.metrics
        assert m.windows > 0
        # every committed decode token came through a window, none per-token
        assert m.decode_tokens == sum(len(r.tokens) for r in got.values())


@pytest.mark.parametrize("flags,want", [
    ("window=8", dict(window=8)),
    ("win=4,overlap=1", dict(window=4, overlap=True)),
    ("window=8,spec=1,dlen=4,dlayers=2",
     dict(window=8, speculate=True, draft_len=4, draft_layers=2)),
], ids=["window", "overlap", "spec"])
def test_engine_config_from_flags(flags, want):
    """``EngineConfig.from_flags`` is the one parser of engine-shape flag
    strings."""
    conf = EngineConfig.from_flags(flags)
    assert conf == EngineConfig(**want)
    for field, value in want.items():
        assert getattr(conf, field) == value


@pytest.mark.parametrize("kw,match", [
    (dict(window=0), "window"),
    (dict(overlap=False), "overlap"),
    (dict(paged=True, window=0), "window"),
    (dict(speculate=True, window=0), "window"),
    (dict(speculate=True, window=8, overlap=False), "overlap"),
    (dict(tp=2, window=0), "window"),
    (dict(tp=2, window=4, overlap=False), "overlap"),
    (dict(tp=0), "tp must be"),
], ids=["window0", "overlap_off", "paged_window0", "spec_window0",
        "spec_overlap_off", "tp_window0", "tp_overlap_off", "tp0"])
def test_engine_config_rejects(kw, match):
    """Construction fails at the config, in one place, for every owner
    (Replica, ServeGroup, the fuzz kits): the stepwise (``window=0``) and
    blocking-window (``overlap=False``) engines are gone, with or without
    paging, speculation or tensor parallelism."""
    with pytest.raises(ValueError, match=match):
        EngineConfig(**kw)


def test_fused_prefill_matches_loop_prefill(env):
    """The fori_loop-fused prefill (window mode's admission/LFLR path) must
    be bit-identical to the PR-1 per-token loop across lengths."""
    cfg, params = env
    loop = make_cache_prefill(cfg, SERVE_PROBES)
    fused = make_cache_prefill(cfg, SERVE_PROBES, fused=True)
    for prompt in [(11, 22, 33), (5, 6, 7, 8), (3,) * 7,
                   tuple(range(1, 14))]:
        toks = np.asarray([prompt], np.int32)
        l1, c1, w1 = loop(params, toks, MAX_LEN)
        l2, c2, w2 = fused(params, toks, MAX_LEN)
        assert int(w1) == int(w2) == 0
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
        for a, b in zip(jax.tree_util.tree_leaves(c1),
                        jax.tree_util.tree_leaves(c2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------------- faults
@pytest.mark.parametrize("inject_at", [1, 2, 3])
def test_midwindow_fault_recovers_exact_trajectory(env, inject_at):
    """A STATE_FAULT latched mid-window is attributed to its exact (step,
    slot) at the boundary; LFLR re-prefill replays greedy from the last
    committed token, so the final trajectory is the fault-free one."""
    cfg, params = env
    clean = greedy_reference(cfg, params, _requests(2, max_new=14), MAX_LEN)
    rep = _replica(env, 4)
    faulty = _serve_all(rep, _requests(2, max_new=14), inject_at=inject_at)
    assert faulty[0].status == OK and faulty[0].retries == 1
    assert faulty[0].tokens == clean[0]
    # deferred detection is still per-sequence: the co-batched lane committed
    # its whole window and never noticed
    assert faulty[1].status == OK and faulty[1].retries == 0
    assert faulty[1].tokens == clean[1]
    assert rep.metrics.fault_counts().get("STATE_FAULT") == 1


def test_persistent_fault_evicts_without_stale_refault(env):
    """A lane that re-faults on every window is answered FAILED after the
    retry budget — and the eviction also invalidates the lane in the in-flight
    speculative window, so the already-computed stale fault is not recorded a
    second time (which would spuriously escalate the policy toward ROLLBACK)."""
    rep = _replica(env, 4, num_slots=2)
    real_win = rep._decode_window

    def cursed(params, caches, tokens, pos, *chunk_args):
        toks, words, next_tok, caches = real_win(params, caches, tokens, pos,
                                                 *chunk_args)
        words = words.at[1, 0].set(
            words[1, 0] | jnp.uint32(int(ErrorCode.STATE_FAULT)))
        return toks, words, next_tok, caches

    rep._decode_window = cursed
    out = _serve_all(rep, _requests(2, max_new=16))
    assert out[0].status == FAILED and out[0].retries == 3
    assert out[1].status == OK and len(out[1].tokens) == 16
    # 3 real faults (one per LFLR retry); the stale speculative windows —
    # both the mid-recovery ones and the post-eviction one — record nothing
    assert len(rep.metrics.faults) == 3, rep.metrics.faults


def test_window_group_kill_zero_dropped_requests(env):
    """The PR-1 fault contract survives the window engine: a replica kill
    mid-serve shrinks the group and re-routes — zero dropped requests."""
    from repro.core.faults import FaultSchedule, FaultSpec

    cfg, _ = env
    group = ServeGroup(cfg, 3, config=EngineConfig(num_slots=2,
                                                   max_len=MAX_LEN, window=4))
    reqs = [Request(id=i, prompt=(5 + i, 6 + i, 7 + i), max_new_tokens=6)
            for i in range(9)]
    res = group.serve(reqs, faults=FaultSchedule(
        [FaultSpec(step=2, kind="kill", rank=1)]))
    assert [r.rank for r in res.reports if r.killed] == [1]
    assert sorted(res.responses) == list(range(9))
    assert all(r.ok for r in res.responses.values())
    assert {r.replica for r in res.responses.values()} <= {0, 2}


# ------------------------------------------------------- window boundaries
def test_eos_midwindow_discards_trailing_and_backfills(env):
    """EOS inside a decode-only window: the lane commits up to EOS, the
    over-decoded trailing tokens are discarded, and the freed slot is
    backfilled at the boundary. (EOS in the window a lane flips from
    prefill to decode is in test_serve_overlap.py.)"""
    rep = _replica(env, 4, num_slots=2, eos_id=777)
    real_win = rep._decode_window
    dispatched = []

    def eos_at_step1(params, caches, tokens, pos, *chunk_args):
        toks, words, next_tok, caches = real_win(params, caches, tokens, pos,
                                                 *chunk_args)
        dispatched.append(True)
        if len(dispatched) == 2:   # slot 0's first window with no chunk
            toks = toks.at[1, 0].set(777)   # slot 0 emits EOS at step 1
        return toks, words, next_tok, caches

    rep._decode_window = eos_at_step1
    out = _serve_all(rep, _requests(3, max_new=12))
    assert sorted(out) == [0, 1, 2]
    # slot 0: window 1 feeds the 3-token prompt (steps 0-2) and commits
    # steps 2-3; window 2 commits step 0 and the EOS, trailing dropped
    assert out[0].status == OK
    assert out[0].tokens[-1] == 777 and len(out[0].tokens) == 4
    assert rep.metrics.discarded_tokens > 0
    # the freed lane was backfilled: the queued request completed in full
    assert out[2].status == OK and len(out[2].tokens) == 12
    # co-batched lane unaffected
    assert out[1].status == OK and len(out[1].tokens) == 12


def test_budget_finish_midwindow_discards_trailing(env):
    """max_new_tokens not divisible by K: the finishing window commits only
    the remaining budget and discards the over-decoded tail."""
    rep = _replica(env, 8, num_slots=1)
    out = _serve_all(rep, _requests(1, max_new=10))
    assert out[0].status == OK and len(out[0].tokens) == 10
    assert rep.metrics.discarded_tokens > 0


# ---------------------------------------------------------- host-sync budget
def test_host_sync_budget_scales_with_steps_over_K(env, monkeypatch):
    """Regression fence for the zero-sync contract: a serve run's host syncs
    must scale with ``steps / K``, not with ``steps`` — a future edit that
    sneaks a per-token readback back in fails this."""
    reqs = lambda: _requests(4, max_new=16)  # noqa: E731

    def run(window):
        rep = _replica(env, window, num_slots=4)
        return rep, _serve_all(rep, reqs())

    # warm the compiles outside the counted region
    run(8), run(4), run(1)
    syncs = {}
    for K in (1, 4, 8):
        syncs[K], (rep, out) = count_syncs(monkeypatch, lambda: run(K))
        assert all(r.status == OK for r in out.values())
        m = rep.metrics
        # ≤ 2 syncs per retired window (word + token block), plus slack for
        # jit-internal transfers — nothing may scale per token, and
        # admission (chunks inside the windows) costs no sync of its own.
        assert syncs[K] <= 2 * m.windows + 4, (K, syncs[K], m.windows)
    # bigger windows → strictly fewer syncs; a one-step window pays per token
    assert syncs[8] < syncs[4] < syncs[1], syncs
