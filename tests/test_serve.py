"""repro.serve: queue admission/deadlines, scheduler backfill, per-sequence
LFLR recovery, and ServeGroup shrink + re-route under a replica kill."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core.errors import ErrorCode
from repro.core.faults import FaultSchedule, FaultSpec
from repro.launch.steps import make_cache_prefill
from repro.models import build_model
from repro.serve import (
    EXPIRED,
    FAILED,
    OK,
    REJECTED,
    AdmissionPolicy,
    ContinuousBatchingScheduler,
    EngineConfig,
    Replica,
    Request,
    RequestQueue,
    ServeGroup,
)
from repro.serve.engine import SERVE_PROBES, build_engine


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def tick(self, dt=1.0):
        self.t += dt


# ------------------------------------------------------------------ queue
def test_admission_rejects_on_full_queue():
    q = RequestQueue(AdmissionPolicy(max_queue=2), clock=FakeClock())
    assert q.submit(Request(id=0, prompt=(1,))) is None
    assert q.submit(Request(id=1, prompt=(1,))) is None
    resp = q.submit(Request(id=2, prompt=(1,)))
    assert resp is not None and resp.status == REJECTED
    assert "queue full" in resp.detail
    assert len(q) == 2


def test_admission_rejects_oversized_request():
    q = RequestQueue(AdmissionPolicy(max_total_len=8), clock=FakeClock())
    resp = q.submit(Request(id=0, prompt=(1, 2, 3, 4, 5, 6), max_new_tokens=4))
    assert resp is not None and resp.status == REJECTED
    assert q.submit(Request(id=1, prompt=(1, 2, 3), max_new_tokens=4)) is None


def test_queue_pops_earliest_deadline_first():
    clk = FakeClock()
    q = RequestQueue(clock=clk)
    q.submit(Request(id=0, prompt=(1,), deadline=None))
    q.submit(Request(id=1, prompt=(1,), deadline=10.0))
    q.submit(Request(id=2, prompt=(1,), deadline=5.0))
    assert [q.pop().id for _ in range(3)] == [2, 1, 0]
    assert q.pop() is None


def test_queue_expires_requests_past_deadline():
    clk = FakeClock()
    q = RequestQueue(clock=clk)
    q.submit(Request(id=0, prompt=(1,), deadline=2.0))
    q.submit(Request(id=1, prompt=(1,), deadline=50.0))
    clk.tick(3.0)
    got = q.pop()                       # skips the expired one
    assert got is not None and got.id == 1
    assert [r.id for r in q.drain_expired()] == [0]
    assert len(q) == 0


# -------------------------------------------------------------- scheduler
def _sched(n_reqs, num_slots=2, max_new=2, deadline=None):
    clk = FakeClock()
    q = RequestQueue(clock=clk)
    for i in range(n_reqs):
        assert q.submit(Request(id=i, prompt=(10 + i,), max_new_tokens=max_new,
                                deadline=deadline)) is None
    return ContinuousBatchingScheduler(num_slots, q, replica=7, clock=clk), clk


def test_scheduler_backfills_freed_slot_after_eviction():
    sched, clk = _sched(3, num_slots=2, max_new=2)
    admitted = sched.backfill()
    assert [(s, r.id) for s, r in admitted] == [(0, 0), (1, 1)]
    assert sched.free_slots() == []          # request 2 must wait
    # finish slot 0 (max_new=2) while slot 1 is mid-flight
    assert sched.commit_token(0, 100) is None
    resp = sched.commit_token(0, 101)
    assert resp is not None and resp.status == OK and resp.tokens == (100, 101)
    assert resp.replica == 7
    assert sched.commit_token(1, 200) is None
    # the freed slot is backfilled with the waiting request
    admitted = sched.backfill()
    assert [(s, r.id) for s, r in admitted] == [(0, 2)]
    # the mid-flight lane kept its progress: its next input is its newest
    # token, at position prompt_len + generated - 1
    s1 = sched.slots[1]
    assert s1.generated == [200] and s1.seq_len - 1 == 1 + 1 - 1


def test_scheduler_expires_active_sequence_mid_decode():
    sched, clk = _sched(1, num_slots=1, max_new=10, deadline=2.5)
    sched.backfill()
    sched.commit_token(0, 5)
    clk.tick(3.0)
    out = sched.expire_active()
    assert len(out) == 1 and out[0].status == EXPIRED
    assert out[0].tokens == (5,)             # partial progress reported
    assert sched.free_slots() == [0]


def test_scheduler_drain_in_flight_for_reroute():
    sched, _ = _sched(2, num_slots=2, max_new=4)
    sched.backfill()
    sched.commit_token(0, 1)
    reqs = sched.drain_in_flight()
    assert sorted(r.id for r in reqs) == [0, 1]
    assert not sched.has_active()


# ---------------------------------------------------------------- replica
@pytest.fixture(scope="module")
def serve_env():
    cfg = smoke_config("recurrentgemma-2b")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    prefill_fn = make_cache_prefill(cfg, SERVE_PROBES)
    return cfg, params, prefill_fn, {}


def _replica(env, **kw):
    cfg, params, _, programs = env
    conf = {k: kw.pop(k) for k in list(kw) if k in EngineConfig.__dataclass_fields__}
    conf.setdefault("num_slots", 2)
    conf.setdefault("max_len", 48)
    conf = EngineConfig(**conf)
    # one window program per shape, shared like a ServeGroup shares it
    key = (conf.num_slots, conf.max_len, conf.window)
    if key not in programs:
        programs[key] = build_engine(cfg, params, conf)
    return Replica(cfg, params=params, config=conf, programs=programs[key],
                   **kw)


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


def _requests(n, max_new=6):
    return [Request(id=i, prompt=(10 + i, 20 + i, 30 + i), max_new_tokens=max_new)
            for i in range(n)]


def test_replica_serves_with_continuous_backfill(serve_env):
    rep = _replica(serve_env)
    out = _serve_all(rep, _requests(3, max_new=4))
    assert sorted(out) == [0, 1, 2]
    assert all(r.status == OK and len(r.tokens) == 4 for r in out.values())
    # request 2 only got a slot after an eviction: strictly later first token
    assert out[2].ttft_s > out[0].ttft_s and out[2].ttft_s > out[1].ttft_s
    s = rep.metrics.summary()
    assert s["statuses"] == {OK: 3} and s["faults"] == {}


def test_replica_lflr_reprefill_on_state_fault(serve_env):
    # 12 tokens over K=4 windows: slot 0 is still decoding at step 3
    clean = _serve_all(_replica(serve_env), _requests(2, max_new=12))
    rep = _replica(serve_env)
    faulty = _serve_all(rep, _requests(2, max_new=12), inject_at=3)
    # the paper's contract: the fault became an exception and was recovered —
    # recompute (LFLR), not restart, so the trajectory is bit-identical
    assert faulty[0].status == OK and faulty[0].retries == 1
    assert faulty[0].tokens == clean[0].tokens
    # per-sequence: the co-batched sequence never noticed
    assert faulty[1].status == OK and faulty[1].retries == 0
    assert faulty[1].tokens == clean[1].tokens
    assert rep.metrics.fault_counts().get("STATE_FAULT") == 1
    log = rep.metrics.to_event_log()
    assert len(log.faults()) >= 1


def test_replica_fails_unrecoverable_request_without_wedging(serve_env):
    rep = _replica(serve_env, max_request_retries=1)
    doomed_mark = 499
    real_win = rep._decode_window

    def cursed_window(params, caches, *rest):
        toks, words, next_tok, caches = real_win(params, caches, *rest)
        for s in rep.sched.slots:              # this request always re-faults
            if s.active and s.req.prompt[0] == doomed_mark:
                words = words.at[0, s.idx].set(
                    words[0, s.idx] | jnp.uint32(int(ErrorCode.STATE_FAULT)))
        return toks, words, next_tok, caches

    rep._decode_window = cursed_window
    out = _serve_all(rep, [
        Request(id=0, prompt=(doomed_mark, 2, 3), max_new_tokens=4),
        Request(id=1, prompt=(7, 8, 9), max_new_tokens=4),
    ])
    assert out[0].status == FAILED and out[0].retries == 2
    assert out[1].status == OK and len(out[1].tokens) == 4


def test_replica_expires_deadline_in_queue_and_mid_decode(serve_env):
    clk = FakeClock()
    rep = _replica(serve_env, num_slots=2, clock=clk)
    # slots are taken by two long requests; the third expires while queued
    assert rep.submit(Request(id=0, prompt=(1, 2), max_new_tokens=8,
                              deadline=4.0)) is None
    assert rep.submit(Request(id=1, prompt=(3, 4), max_new_tokens=8)) is None
    assert rep.submit(Request(id=2, prompt=(5, 6), max_new_tokens=2,
                              deadline=1.0)) is None
    out = {}
    for _ in range(12):
        clk.tick(1.0)
        out.update({r.id: r for r in rep.step()})
    assert out[2].status == EXPIRED and out[2].tokens == ()
    assert out[0].status == EXPIRED and len(out[0].tokens) >= 1   # mid-decode
    assert out[1].status == OK and len(out[1].tokens) == 8


def test_replica_stops_at_eos(serve_env):
    # learn which token greedy decode emits, then declare it EOS
    free = _serve_all(_replica(serve_env), _requests(1, max_new=4))
    eos = free[0].tokens[0]
    rep = _replica(serve_env, eos_id=eos)
    out = _serve_all(rep, _requests(1, max_new=4))
    assert out[0].status == OK and out[0].tokens == (eos,)


def test_slot_decode_matches_single_sequence_prefill(serve_env):
    """The vmapped per-slot step must agree with the plain decode path."""
    cfg, params, prefill_fn, _ = serve_env
    prompt = (11, 22, 33)
    rep = _replica(serve_env)
    out = _serve_all(rep, [Request(id=0, prompt=prompt, max_new_tokens=3)])
    # replay the whole sequence through the non-vmapped prefill path
    logits, _, word = prefill_fn(
        params, np.asarray([list(prompt) + list(out[0].tokens[:-1])], np.int32),
        48)
    assert int(word) == 0
    assert int(np.argmax(np.asarray(logits)[0, -1])) == out[0].tokens[-1]


# -------------------------------------------------------------- ServeGroup
@pytest.fixture(scope="module")
def group():
    cfg = smoke_config("recurrentgemma-2b")
    return ServeGroup(cfg, 3, config=EngineConfig(num_slots=2, max_len=48))


def test_group_survives_replica_kill_with_zero_dropped_requests(group):
    reqs = [Request(id=i, prompt=(5 + i, 6 + i, 7 + i), max_new_tokens=5)
            for i in range(9)]
    res = group.serve(reqs, faults=FaultSchedule(
        [FaultSpec(step=2, kind="kill", rank=1)]))
    assert [r.rank for r in res.reports if r.killed] == [1]
    # zero dropped: every accepted request got a terminal OK answer
    assert sorted(res.responses) == list(range(9))
    assert all(r.ok for r in res.responses.values())
    # the dead replica's unanswered requests were re-routed, not lost
    assert set(res.rerouted) and set(res.rerouted) <= set(range(9))
    for rank in (0, 2):
        report = res.report(rank)
        assert report is not None, res.reports[rank].exception
        shrinks = [e for e in report.events if e[0] == "shrink"]
        assert len(shrinks) == 1 and shrinks[0][2] == 2      # world 3 -> 2
    # answered by survivors only
    assert {r.replica for r in res.responses.values()} <= {0, 2}


def test_group_soft_fault_stays_local_and_everyone_answers(group):
    reqs = [Request(id=i, prompt=(40 + i, 41 + i), max_new_tokens=5)
            for i in range(6)]
    res = group.serve(reqs, faults=FaultSchedule(
        [FaultSpec(step=2, kind="state_nan", rank=0)]))
    assert sorted(res.responses) == list(range(6))
    assert all(r.ok for r in res.responses.values())
    assert res.rerouted == ()
    r0 = res.report(0)
    assert r0 is not None
    assert [e for e in r0.events if e[0] == "inject"]
    assert r0.metrics.fault_counts().get("STATE_FAULT") == 1
    # no shrink happened anywhere: soft faults are replica-local
    for rank in range(3):
        assert not [e for e in res.report(rank).events if e[0] == "shrink"]
