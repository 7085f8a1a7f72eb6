"""Paged KV cache with fault-scoped page ownership (ISSUE 4).

The paged engine (``Replica(window=K, paged=True)``) pools full-attention
KV into a shared page pool addressed through a device-resident page table.
Contracts fenced here:

* token-bit-exactness vs the contiguous overlap engine on identical traffic,
  steady and faulted (the gathered view is bit-equal to the contiguous
  cache, so greedy trajectories cannot diverge);
* LFLR page reclaim is fault-scoped: recovering one lane frees + re-acquires
  *its* pages only — co-slot pages are untouched and co-slot streams
  bit-exact;
* pool exhaustion preempts the oldest lane back into the queue (zero dropped
  requests) and the ledger stays consistent;
* page-table corruption surfaces in-band as ``PAGE_FAULT`` at the wait and
  the LFLR re-queue repairs the mapping;
* the paged chunked prefill chain reproduces the contiguous prefill bits.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core.errors import ErrorCode
from repro.launch.paging import PagedLayout, pages_for
from repro.launch.steps import make_cache_prefill, make_chunked_prefill
from repro.models import build_model
from repro.serve import OK, EngineConfig, Replica, Request
from repro.serve.replica import SERVE_PROBES

MAX_LEN = 32
PAGE = 8
WINDOW = 4


@pytest.fixture(scope="module")
def env():
    cfg = smoke_config("qwen3-1.7b")     # pure full attention: all KV paged
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


def _replica(env, *, paged, **kw):
    cfg, params = env
    conf = {k: kw.pop(k) for k in list(kw) if k in EngineConfig.__dataclass_fields__}
    conf.setdefault("num_slots", 2)
    conf.setdefault("max_len", MAX_LEN)
    conf.setdefault("window", WINDOW)
    conf.setdefault("max_request_retries", 4)
    conf.setdefault("page_size", PAGE)
    return Replica(cfg, params=params,
                   config=EngineConfig(paged=paged, **conf), **kw)


def _requests(n, max_new=8, prompt_len=5):
    return [Request(id=i, prompt=tuple(10 + i + j for j in range(prompt_len)),
                    max_new_tokens=max_new) for i in range(n)]


def _serve_all(rep, reqs, inject_at=None, inject_slot=None, hook=None):
    for r in reqs:
        assert rep.submit(r) is None
    out, steps = {}, 0
    while not rep.idle():
        if inject_at is not None and steps == inject_at:
            slot = inject_slot
            if slot is None:             # first decoding lane both engines run
                decoding = [i for i in rep.sched.active_slots()
                            if rep.sched.slots[i].pending is None]
                slot = decoding[0] if decoding else None
            if slot is not None:
                assert rep.inject_state_fault(slot) == slot
        if hook is not None:
            hook(rep, steps)
        for resp in rep.step():
            out[resp.id] = resp
        steps += 1
        assert steps < 2000
    return out


# --------------------------------------------------------------- bit-exactness
@pytest.mark.parametrize("label,inject_at", [("steady", None), ("faulted", 6)])
def test_paged_bit_exact_vs_contiguous(env, label, inject_at):
    """Same traffic, same injections: the paged engine's token streams must
    equal the contiguous overlap engine's exactly, with zero host stalls and
    a consistent ledger afterwards."""
    base = _serve_all(_replica(env, paged=False), _requests(5),
                      inject_at=inject_at)
    rep = _replica(env, paged=True)
    got = _serve_all(rep, _requests(5), inject_at=inject_at)
    assert sorted(got) == sorted(base)
    for i in base:
        assert got[i].status == base[i].status == OK
        assert got[i].tokens == base[i].tokens, (label, i)
    m = rep.metrics.summary()
    assert m["pages_allocated"] > 0
    assert m["pages_allocated"] == m["pages_freed"]   # all reclaimed at drain
    rep.alloc.check()


def test_paged_chunked_prefill_chain_matches_contiguous(env):
    """Chaining paged chunks through the pool is bit-identical to the
    contiguous fused prefill: same logits, and the gathered view equals the
    contiguous cache leaf-for-leaf."""
    cfg, params = env
    layout = PagedLayout(build_model(cfg).init_cache(1, MAX_LEN), MAX_LEN,
                         page_size=PAGE, num_pages=8)
    assert layout.has_paged_leaves
    full = make_cache_prefill(cfg, SERVE_PROBES, fused=True)
    chunked = make_chunked_prefill(cfg, SERVE_PROBES, chunk=4, paged=layout)
    prompt = tuple(range(3, 14))
    l_ref, c_ref, w_ref = full(params, np.asarray([prompt], np.int32), MAX_LEN)

    hybrid = layout.init_hybrid(build_model(cfg).init_cache(1, MAX_LEN), 2)
    table = layout.empty_table(2)
    slot = 1
    n_pages = pages_for(len(prompt) + 1, PAGE)
    table[slot, :n_pages] = np.arange(2, 2 + n_pages)    # arbitrary phys ids
    row = jnp.asarray(table[slot])
    word = jnp.uint32(0)
    logits = None
    for lo in range(0, len(prompt), 4):
        part = prompt[lo:lo + 4]
        padded = np.zeros((1, 4), np.int32)
        padded[0, :len(part)] = part
        logits, hybrid, w = chunked(params, hybrid, row, jnp.int32(slot),
                                    padded, jnp.int32(len(part)),
                                    jnp.int32(lo))
        word = word | w
    assert int(word) == int(w_ref) == 0
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(l_ref))
    view = layout.gather_slot(hybrid, row, jnp.int32(slot))
    for a, b in zip(jax.tree_util.tree_leaves(view),
                    jax.tree_util.tree_leaves(c_ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------- fault-scoped reclaim
def test_lflr_page_reclaim_leaves_coslot_pages_untouched(env):
    """A faulted lane frees and re-acquires *its own* pages; the co-batched
    slot's physical pages never move and its stream is bit-exact vs an
    undisturbed run — recovery is scoped to the smallest recoverable unit,
    now including memory ownership."""
    reqs = lambda: [Request(id=0, prompt=(3, 5, 7), max_new_tokens=24),  # noqa: E731
                    Request(id=1, prompt=tuple(range(20, 26)),
                            max_new_tokens=20)]
    clean = _serve_all(_replica(env, paged=False), reqs())

    rep = _replica(env, paged=True)
    snap = {}

    def hook(r, steps):
        s0, s1 = r.sched.slots[0], r.sched.slots[1]
        if ("s0" not in snap and steps >= 3
                and s0.active and s0.pending is None
                and s1.active and s1.pending is None):
            # both lanes decoding: snapshot ownership, poison slot 1
            snap["s0"] = r.alloc.owned(0)
            snap["s1"] = r.alloc.owned(1)
            assert snap["s0"] and snap["s1"]
            assert r.inject_state_fault(1) == 1
        elif "s0" in snap and r.sched.slots[0].active \
                and r.sched.slots[0].req.id == 0:
            # every step through detection + recovery: slot 0's physical
            # pages never move (reclaim is scoped to the faulted lane)
            assert r.alloc.owned(0)[:len(snap["s0"])] == snap["s0"]
            assert np.array_equal(
                r.page_table[0, :len(snap["s0"])], snap["s0"])
            snap["checked"] = True

    got = _serve_all(rep, reqs(), hook=hook)
    assert snap.get("checked"), "post-recovery ownership was never checked"
    assert got[1].status == OK and got[1].retries == 1
    assert got[0].status == OK and got[0].retries == 0
    for i in clean:
        assert got[i].tokens == clean[i].tokens
    rep.alloc.check()


# ---------------------------------------------------------------- capacity
def test_paged_capacity_doubles_slots_at_equal_budget(env):
    """Mixed prompt lengths on the page budget of two contiguous slots: the
    paged pool keeps at least twice as many lanes serving at once as the
    contiguous layout does, and answers every request."""
    max_len, page = 64, 16
    prompts = (4, 40, 8, 12, 6, 32, 10, 8)

    def peak(paged, slots):
        rep = _replica(env, paged=paged, num_slots=slots, max_len=max_len,
                       page_size=page, window=8,
                       page_budget=2 * (max_len // page) if paged else None)
        reqs = [Request(id=i, prompt=tuple(3 + i + j for j in range(n)),
                        max_new_tokens=8) for i, n in enumerate(prompts)]
        got = _serve_all(rep, reqs)
        assert sorted(got) == list(range(len(prompts)))
        assert all(r.status == OK for r in got.values())
        if paged:
            rep.alloc.check()
        return rep.metrics.peak_active_slots

    contiguous, paged = peak(False, 2), peak(True, 4)
    assert paged >= 2 * contiguous, (paged, contiguous)


# ------------------------------------------------------ exhaustion / eviction
def test_pool_exhaustion_evicts_oldest_drops_nothing(env):
    """A pool half the size the slots could demand: growth under load must
    preempt lanes (oldest first) back into the queue instead of dropping or
    wedging — every request still gets an OK answer and the tokens match an
    unpressured run."""
    base = _serve_all(_replica(env, paged=False, max_len=16), _requests(
        6, max_new=8, prompt_len=5))
    rep = _replica(env, paged=True, max_len=16, page_size=4, page_budget=5)
    got = _serve_all(rep, _requests(6, max_new=8, prompt_len=5))
    assert sorted(got) == sorted(base)
    for i in base:
        assert got[i].status == OK
        assert got[i].tokens == base[i].tokens, i
    m = rep.metrics.summary()
    assert m["page_evictions"] > 0, "pressure never triggered an eviction"
    assert m["peak_pages_in_use"] <= 5
    rep.alloc.check()


def test_scrub_staging_survives_eviction_recycled_ids(env):
    """Regression: growth inside one pre-dispatch prepare can evict a lane
    and immediately recycle its freed pages, so the raw new-id list exceeds
    ``num_pages`` (the same physical id granted twice). The fixed-size scrub
    staging buffer must dedupe rather than crash mid-step — exactly under
    the pool pressure the eviction path exists to survive."""
    rep = _replica(env, paged=True, num_slots=4, max_len=32, page_size=4,
                   page_budget=6, window=8)
    got = _serve_all(rep, _requests(6, max_new=6, prompt_len=5))
    assert sorted(got) == list(range(6))
    assert all(r.status == OK for r in got.values())
    assert rep.metrics.summary()["page_evictions"] > 0
    rep.alloc.check()


def test_watermark_gates_admission(env):
    """With a watermark the scheduler defers admission while headroom is
    thin instead of thrashing: requests still all complete, and concurrency
    stays within what the pool can grow."""
    rep = _replica(env, paged=True, max_len=16, page_size=4, page_budget=5,
                   page_watermark=1)
    got = _serve_all(rep, _requests(5, max_new=6, prompt_len=5))
    assert all(r.status == OK for r in got.values())
    rep.alloc.check()


def test_pool_smaller_than_max_len_cannot_livelock(env):
    """Regression: with a pool smaller than ``max_len`` (admission clamps to
    pool capacity), window over-decode used to push the growth target past
    what the pool can ever hold — the lane evicted the fleet, self-evicted,
    requeued and replayed forever. Growth and the page probe now clamp to
    pool capacity, so a legally admitted request always completes."""
    rep = _replica(env, paged=True, num_slots=1, max_len=64, page_size=16,
                   page_budget=3)          # pool = 48 positions < max_len
    req = Request(id=0, prompt=tuple(3 + j for j in range(20)),
                  max_new_tokens=24)       # total 44 <= 48: must be admitted
    assert rep.submit(req) is None
    got = _serve_all(rep, [])
    assert got[0].status == OK and len(got[0].tokens) == 24
    assert rep.metrics.summary()["page_evictions"] == 0
    rep.alloc.check()


def test_request_larger_than_pool_rejected_at_submit(env):
    """A request that could never fit in the pool must be REJECTED at
    admission, not deferred forever by the watermark gate."""
    rep = _replica(env, paged=True, max_len=32, page_size=8, page_budget=2)
    resp = rep.submit(Request(id=0, prompt=tuple(range(3, 21)),
                              max_new_tokens=8))     # 26 tokens > 16 capacity
    assert resp is not None and resp.status == "rejected"


# --------------------------------------------------------- in-band PAGE_FAULT
def test_page_table_corruption_raises_page_fault_and_recovers(env):
    """Unmapping a decoding lane's table row behind the allocator's back is
    ledger corruption: the in-band probe latches PAGE_FAULT, the wait raises,
    and the LFLR re-queue (free + re-acquire + scrub) rebuilds the mapping —
    the request still completes with the exact clean trajectory."""
    clean = _serve_all(_replica(env, paged=False), _requests(2, max_new=16))

    rep = _replica(env, paged=True)
    state = {}

    def hook(r, steps):
        s0 = r.sched.slots[0]
        if ("corrupted" not in state and steps >= 4
                and s0.active and s0.pending is None):
            r.page_table[0, :] = r.layout.sentinel    # device table corrupted
            state["corrupted"] = True

    got = _serve_all(rep, _requests(2, max_new=16), hook=hook)
    assert state.get("corrupted")
    for i in clean:
        assert got[i].status == OK
        assert got[i].tokens == clean[i].tokens, i
    counts = rep.metrics.fault_counts()
    assert counts.get("PAGE_FAULT", 0) >= 1, counts
    assert any(f.action == "page_reclaim" for f in rep.metrics.faults)
    rep.alloc.check()


def test_page_probe_word(env):
    cfg, _ = env
    layout = PagedLayout(build_model(cfg).init_cache(1, MAX_LEN), MAX_LEN,
                         page_size=PAGE, num_pages=4)
    table = jnp.asarray([[0, 1, layout.sentinel, layout.sentinel],
                         [layout.sentinel, 2, 3, 1],
                         [0, layout.sentinel, 2, 3]], jnp.int32)
    word = layout.probe(table, jnp.asarray([9, 1, 20], jnp.int32))
    # slot 0 writes pos 9 → pages 0..1 mapped: clean (trailing sentinels are
    # beyond the live region and must not trip)
    assert int(word[0]) == 0
    # slot 1 writes pos 1 → logical page 0 → sentinel: PAGE_FAULT
    assert int(word[1]) == int(ErrorCode.PAGE_FAULT)
    # slot 2 writes pos 20 (page 2, mapped) but READ page 1 is unmapped —
    # silent zero-reads must surface too
    assert int(word[2]) == int(ErrorCode.PAGE_FAULT)


def test_paged_degenerates_cleanly_without_pageable_leaves():
    """A hybrid arch (sliding-window rings + recurrent state, nothing with
    capacity == max_len) has no pageable leaves: paged=True must serve
    bit-identically to the contiguous engine with an idle ledger rather
    than wedging or misclassifying ring buffers as pages."""
    cfg = smoke_config("recurrentgemma-2b")
    params = build_model(cfg).init(jax.random.PRNGKey(0))

    def serve(paged):
        rep = Replica(cfg, params=params,
                      config=EngineConfig(num_slots=2, max_len=MAX_LEN,
                                          window=WINDOW, paged=paged,
                                          page_size=PAGE))
        return rep, _serve_all(rep, _requests(3))

    _, base = serve(False)
    rep, got = serve(True)
    assert not rep.layout.has_paged_leaves
    for i in base:
        assert got[i].status == OK and got[i].tokens == base[i].tokens
    assert rep.metrics.summary()["pages_allocated"] == 0


# -------------------------------------------------------------- paged fleet
def test_paged_group_kill_zero_dropped_requests(env):
    """The PR-1 hard-fault contract survives paging: a replica kill
    mid-serve shrinks the group and re-routes; survivors' page pools answer
    every request (each replica owns its own pool, the layout and jitted
    programs are shared)."""
    from repro.core.faults import FaultSchedule, FaultSpec
    from repro.serve import ServeGroup

    cfg, _ = env
    group = ServeGroup(cfg, 3,
                       config=EngineConfig(num_slots=2, max_len=MAX_LEN,
                                           window=WINDOW, paged=True,
                                           page_size=PAGE))
    reqs = [Request(id=i, prompt=(5 + i, 6 + i, 7 + i), max_new_tokens=6)
            for i in range(9)]
    res = group.serve(reqs, faults=FaultSchedule(
        [FaultSpec(step=2, kind="kill", rank=1)]))
    assert [r.rank for r in res.reports if r.killed] == [1]
    assert sorted(res.responses) == list(range(9))
    assert all(r.ok for r in res.responses.values())
    assert {r.replica for r in res.responses.values()} <= {0, 2}


# ----------------------------------------------------------- layout mechanics
def test_layout_classification_and_budget(env):
    cfg, _ = env
    one = build_model(cfg).init_cache(1, MAX_LEN)
    layout = PagedLayout(one, MAX_LEN, page_size=PAGE, num_pages=8)
    # qwen3 is pure full attention: every KV leaf paged, nothing dense
    n_leaves = len(jax.tree_util.tree_leaves(one))
    assert layout.has_paged_leaves
    assert layout.max_pages == MAX_LEN // PAGE
    assert layout.pool_bytes() == 8 * layout.page_bytes()
    assert (layout.contiguous_paged_bytes_per_slot()
            == layout.max_pages * layout.page_bytes())
    hybrid = layout.init_hybrid(one, 3)
    assert len(jax.tree_util.tree_leaves(hybrid)) == n_leaves
    # hybrid layers: paged leaves lead with num_pages, not num_slots
    for (path, leaf) in jax.tree_util.tree_flatten_with_path(hybrid)[0]:
        if layout.is_paged_path(path):
            assert leaf.shape[0] == 8
    with pytest.raises(ValueError, match="multiple"):
        PagedLayout(one, MAX_LEN, page_size=5, num_pages=8)


def test_oversized_watermark_request_still_served(env):
    """A request so large that pages + watermark exceed the pool can never
    pass the gated admission check — the headroom must be waived (admit when
    it plainly fits) or an accepted request would be deferred forever."""
    rep = _replica(env, paged=True, num_slots=1, max_len=64, page_size=16,
                   page_budget=4, page_watermark=1)
    req = Request(id=0, prompt=tuple(3 + j for j in range(50)),
                  max_new_tokens=8)     # needs 4 pages; 4+1 > pool of 4
    assert rep.submit(req) is None      # fits the pool outright: accepted
    got = _serve_all(rep, [])
    assert got[0].status == OK and len(got[0].tokens) == 8
    rep.alloc.check()


def test_gather_of_unmapped_pages_reads_zero(env):
    """The fill-mode gather is the bit-exactness linchpin: an unassigned
    logical page must read as zeros (= fresh contiguous cache), and a lane
    with a sentinel row must scatter nowhere."""
    cfg, _ = env
    one = build_model(cfg).init_cache(1, MAX_LEN)
    layout = PagedLayout(one, MAX_LEN, page_size=PAGE, num_pages=4)
    hybrid = layout.init_hybrid(one, 2)
    # fill pool page 2 with ones; map slot 0 → [2, sentinel...], slot 1 unmapped
    hybrid = jax.tree_util.tree_map(lambda p: jnp.ones_like(p), hybrid)
    table = layout.empty_table(2)
    table[0, 0] = 2
    views = layout.gather(hybrid, jnp.asarray(table))
    for leaf in jax.tree_util.tree_leaves(views):
        arr = np.asarray(leaf)
        cap_ax = arr.ndim - 3
        sl = [slice(None)] * arr.ndim
        sl[0], sl[cap_ax] = 0, slice(0, PAGE)
        assert np.all(arr[tuple(sl)] == 1)            # mapped page: content
        sl[cap_ax] = slice(PAGE, None)
        assert np.all(arr[tuple(sl)] == 0)            # unmapped: zeros
        assert np.all(arr[1] == 0)                    # whole slot unmapped
    # scatter through a sentinel row must drop every write
    poisoned = jax.tree_util.tree_map(lambda v: v + 7.0, views)
    back = layout.scatter(hybrid, poisoned, jnp.asarray(table))
    for old, new in zip(jax.tree_util.tree_leaves(hybrid),
                        jax.tree_util.tree_leaves(back)):
        o, n = np.asarray(old), np.asarray(new)
        assert np.all(n[3] == o[3])                   # page 3 never referenced
        assert np.all(n[2] == 8)                      # slot 0's mapped page
