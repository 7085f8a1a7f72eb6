"""Stall-free serving: chunked prefill fused into decode windows.

The engine (``Replica(config=EngineConfig(window=K))``) must be bit-exact vs
the engine-independent greedy reference while never stalling the host:
admission and LFLR recovery ride the fused decode+prefill window as
background lanes (``make_prefill_decode_window``), a fault mid-chunk
re-queues the lane without blocking, host syncs stay O(steps / K) even with
a lane active, and the TTFT of a late-admitted request is bounded by its
chunk windows — not by a full-prompt prefill.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import count_syncs, greedy_reference

from repro.configs import smoke_config
from repro.core.device_channel import DeviceFuture
from repro.launch.steps import make_cache_prefill, make_chunked_prefill
from repro.models import build_model
from repro.serve import (
    EXPIRED,
    OK,
    AdmissionPolicy,
    ContinuousBatchingScheduler,
    EngineConfig,
    Replica,
    Request,
    RequestQueue,
)
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


def _requests(n, max_new=12, prompt_len=3):
    return [Request(id=i, prompt=tuple(10 + i + j for j in range(prompt_len)),
                    max_new_tokens=max_new) for i in range(n)]


def _serve_all(rep, reqs, inject_at=None, inject_slot=0):
    for r in reqs:
        assert rep.submit(r) is None
    out, steps = {}, 0
    while not rep.idle():
        if inject_at is not None and steps == inject_at:
            assert rep.inject_state_fault(inject_slot) == inject_slot
        for resp in rep.step():
            out[resp.id] = resp
        steps += 1
        assert steps < 1000
    return out


# ---------------------------------------------------------------- bit-exactness
@pytest.mark.parametrize("prompt_len", [3, 11])
def test_overlap_token_identical_to_blocking(env, prompt_len):
    """Chunked prefill fused into the window must reproduce the greedy
    reference's token streams exactly (a one-shot prefill per token) —
    including prompts longer than K (multi-window chunking) and backfill
    chains (5 requests over 2 slots)."""
    cfg, params = env
    reference = greedy_reference(cfg, params,
                                 _requests(5, prompt_len=prompt_len), MAX_LEN)
    for K in (1, 4, 8):
        rep = _replica(env, K)
        got = _serve_all(rep, _requests(5, prompt_len=prompt_len))
        assert sorted(got) == sorted(reference)
        for i in reference:
            assert got[i].status == OK
            assert got[i].tokens == reference[i], (K, i)
        m = rep.metrics.summary()
        # the stall-free contract: every prompt token fed through a fused
        # chunk
        assert m["prefill_chunk_tokens"] == 5 * prompt_len
        assert m["decode_tokens"] == sum(len(r.tokens) for r in got.values())


def test_chunked_prefill_chain_matches_full_prefill(env):
    """make_chunked_prefill chained over an existing cache is bit-identical
    to the one-shot fused prefill — the property that makes a prefill split
    across decode windows reproduce the synchronous trajectory exactly."""
    cfg, params = env
    full = make_cache_prefill(cfg, SERVE_PROBES, fused=True)
    model = build_model(cfg)
    for C, prompt in [(4, tuple(range(3, 14))), (5, (7, 8, 9)),
                      (3, tuple(range(2, 8)))]:
        chunked = make_chunked_prefill(cfg, SERVE_PROBES, chunk=C)
        l_ref, c_ref, w_ref = full(params, np.asarray([prompt], np.int32),
                                   MAX_LEN)
        cache = model.init_cache(1, MAX_LEN)
        word = jnp.uint32(0)
        logits = None
        for lo in range(0, len(prompt), C):
            part = prompt[lo:lo + C]
            padded = np.zeros((1, C), np.int32)
            padded[0, :len(part)] = part
            logits, cache, w = chunked(params, cache, padded,
                                       jnp.int32(len(part)), jnp.int32(lo))
            word = word | w
        assert int(word) == int(w_ref) == 0
        np.testing.assert_array_equal(np.asarray(logits), np.asarray(l_ref))
        for a, b in zip(jax.tree_util.tree_leaves(cache),
                        jax.tree_util.tree_leaves(c_ref)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------- faults
@pytest.mark.parametrize("inject_at", [1, 2])
def test_fault_mid_chunked_prefill_recovers_without_stall(env, inject_at):
    """A STATE_FAULT latched while a lane is mid-chunked-prefill (12-token
    prompt over K=4 → three chunk windows) re-queues the lane from position 0
    and replays to the exact clean trajectory; the co-batched slot never
    stalls (its stream is bit-identical) and the host never blocks."""
    reqs = lambda: [Request(id=0, prompt=(3, 5, 7), max_new_tokens=14),  # noqa: E731
                    Request(id=1, prompt=tuple(range(20, 32)),
                            max_new_tokens=8)]
    cfg, params = env
    clean = greedy_reference(cfg, params, reqs(), MAX_LEN)
    rep = _replica(env, 4)
    got = _serve_all(rep, reqs(), inject_at=inject_at, inject_slot=1)
    assert got[1].status == OK and got[1].retries == 1
    assert got[1].tokens == clean[1]
    assert got[0].status == OK and got[0].retries == 0
    assert got[0].tokens == clean[0]
    assert rep.metrics.fault_counts().get("STATE_FAULT") == 1


def test_eos_midwindow_overlap_discards_trailing_and_backfills(env):
    """Overlapped engine window boundaries: EOS emitted in the same window a
    lane flips from prefill to decode commits up to EOS and discards the
    rest; the freed slot is backfilled with a fresh lane."""
    rep = _replica(env, 4, num_slots=2, eos_id=777)
    real_win = rep._decode_window
    fired = []

    def eos_late(params, caches, tokens, pos, chunk, rem):
        toks, words, nxt, caches = real_win(params, caches, tokens, pos,
                                            chunk, rem)
        if not fired:           # first dispatched window only
            fired.append(True)
            toks = toks.at[3, 0].set(777)   # step 3 ≥ flip step (rem-1 = 2)
        return toks, words, nxt, caches

    rep._decode_window = eos_late
    out = _serve_all(rep, _requests(3, max_new=12))
    assert sorted(out) == [0, 1, 2]
    # slot 0: prompt chunk fed steps 0-2, flip at step 2, EOS at step 3
    assert out[0].status == OK
    assert out[0].tokens[-1] == 777 and len(out[0].tokens) == 2
    # freed lane backfilled; co-batched lane unaffected
    assert out[2].status == OK and len(out[2].tokens) == 12
    assert out[1].status == OK and len(out[1].tokens) == 12


def test_deadline_expiry_mid_prefill_lane(env):
    """A lane whose deadline passes mid-chunked-prefill is evicted EXPIRED at
    the next boundary — a half-built lane can never wedge the replica."""
    t = [0.0]
    rep = _replica(env, 4, clock=lambda: t[0])
    assert rep.submit(Request(id=0, prompt=tuple(range(30, 42)),
                              max_new_tokens=8, deadline=0.5)) is None
    assert rep.submit(Request(id=1, prompt=(4, 5, 6),
                              max_new_tokens=6)) is None
    out = {}
    steps = 0
    while not rep.idle():
        for resp in rep.step():
            out[resp.id] = resp
        t[0] += 0.3             # deadline passes after the first chunk window
        steps += 1
        assert steps < 200
    assert out[0].status == EXPIRED
    assert out[1].status == OK and len(out[1].tokens) == 6


# ------------------------------------------------------------ host-sync budget
def test_host_sync_budget_with_lane_active(env, monkeypatch):
    """Host syncs stay O(steps / K) *while lanes are prefilling*: six
    9-token prompts (each chunked over three windows) add no sync of their
    own — admission and recovery are free of host round trips — and the
    streams equal the greedy reference."""
    cfg, params = env
    reqs = lambda: _requests(6, max_new=12, prompt_len=9)  # noqa: E731

    def run():
        rep = _replica(env, 4, num_slots=2)
        return rep, _serve_all(rep, reqs())

    run()                       # warm the compiles
    syncs, (rep, out) = count_syncs(monkeypatch, run)
    assert all(r.status == OK for r in out.values())
    reference = greedy_reference(cfg, params, reqs(), MAX_LEN)
    for i in reference:
        assert out[i].tokens == reference[i]
    m = rep.metrics
    # ≤ 2 syncs per retired window (word + token block) + slack; nothing
    # scales with admission count
    assert m.prefill_chunk_tokens == 6 * 9
    assert syncs <= 2 * m.windows + 4, (syncs, m.windows)


# ----------------------------------------------------------------------- TTFT
def test_late_admission_ttft_bounded_and_non_interfering(env):
    """A request admitted mid-stream gets its first token within its chunk
    windows + pipeline depth (here: prompt ≤ K → 3 scheduler steps), and the
    already-decoding slot's trajectory is bit-exact vs an undisturbed run —
    admission never stalls or perturbs the healthy lanes."""
    cfg, params = env

    def run(admit_late):
        rep = _replica(env, 4, num_slots=2)
        assert rep.submit(Request(id=0, prompt=(9, 8, 7),
                                  max_new_tokens=20)) is None
        out, late_at, late_done = {}, None, None
        steps = 0
        while not rep.idle():
            if admit_late and steps == 3:
                assert rep.submit(Request(id=1, prompt=(40, 41, 42),
                                          max_new_tokens=1)) is None
                late_at = steps
            for resp in rep.step():
                out[resp.id] = resp
                if resp.id == 1:
                    late_done = steps
            steps += 1
            assert steps < 500
        return rep, out, late_at, late_done

    _, alone, _, _ = run(False)
    rep, both, late_at, late_done = run(True)
    assert both[0].tokens == alone[0].tokens          # non-interference
    assert both[1].status == OK and len(both[1].tokens) == 1
    # chunk rides the next dispatched window; its flip token retires one
    # window later (double-buffered pipeline) — never a blocking prefill
    assert late_done - late_at <= 3, (late_at, late_done)


# ------------------------------------------------------------- window planning
def test_prefill_budget_staggers_lane_starts():
    """The per-window token budget splits decode steps vs prefill chunks:
    fresh lanes start oldest-first within the budget, an in-progress lane
    always continues (no-park invariant), and liveness overrides the budget
    when nothing else can make progress."""
    q = RequestQueue(AdmissionPolicy(max_total_len=64))
    sched = ContinuousBatchingScheduler(3, q, prefill_budget=4)
    for i in range(3):
        assert q.submit(Request(id=i, prompt=tuple(range(8 + i, 14 + i)),
                                max_new_tokens=4)) is None
    admitted = sched.backfill()
    assert [slot for slot, _ in admitted] == [0, 1, 2]
    for slot, _ in admitted:
        sched.begin_prefill(slot)

    plan = sched.plan_prefill(window=4)
    # budget 4 = one chunk: oldest lane starts (liveness would force it
    # anyway), the other two defer with rem=0
    assert plan[0].rem == 4 and plan[0].fresh and not plan[0].exhausts
    assert plan[1].rem == 0 and plan[2].rem == 0
    assert plan[0].tokens == tuple(range(8, 12))

    plan = sched.plan_prefill(window=4)
    # in-progress lane 0 continues first (2 remaining of its 6-token prompt)
    # and exhausts; the leftover budget (2) cannot cover lane 1's first chunk
    # (4), so fresh lanes keep deferring — full-chunk-or-defer
    assert plan[0].rem == 2 and plan[0].exhausts and not plan[0].fresh
    assert sched.slots[0].pending is None             # flipped to decoding
    assert plan[1].rem == 0 and plan[2].rem == 0

    plan = sched.plan_prefill(window=4)
    assert plan[1].rem == 4 and plan[1].fresh         # full budget again
    assert plan[2].rem == 0                           # 4-4=0 left, defers
    plan = sched.plan_prefill(window=4)
    assert plan[1].rem == 2 and plan[1].exhausts
    assert plan[2].rem == 0                           # 2 left < 4 first chunk
    plan = sched.plan_prefill(window=4)
    assert plan[2].rem == 4 and plan[2].fresh
    plan = sched.plan_prefill(window=4)
    assert plan[2].rem == 2 and plan[2].exhausts
    assert sched.plan_prefill(window=4) == {}         # all lanes flipped


def test_prefill_budget_below_window_cannot_starve():
    """A budget smaller than one window could never cover any first chunk
    (full-chunk-or-defer), so a fresh lane would defer forever while another
    slot decodes — the effective budget is clamped to ≥ window instead."""
    q = RequestQueue(AdmissionPolicy(max_total_len=64))
    sched = ContinuousBatchingScheduler(2, q, prefill_budget=2)
    for i in range(2):
        assert q.submit(Request(id=i, prompt=tuple(range(8, 14)),
                                max_new_tokens=4)) is None
    for slot, _ in sched.backfill():
        sched.begin_prefill(slot)
    # flip lane 0 to decoding so the liveness override alone cannot save
    # lane 1 — only the clamp admits it
    for _ in range(2):
        sched.plan_prefill(window=4)
    assert sched.slots[0].pending is None
    plan = sched.plan_prefill(window=4)
    assert plan[1].rem == 4 and plan[1].fresh         # started, not starved
    plan = sched.plan_prefill(window=4)
    assert plan[1].rem == 2 and plan[1].exhausts


def test_device_future_done_is_nonblocking_probe():
    fut = DeviceFuture(outputs=jnp.arange(4), word=jnp.uint32(0))
    jax.block_until_ready(fut.word)
    assert fut.done()
    fut.wait()
    assert fut.done()
