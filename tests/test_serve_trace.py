"""Fault-causality tracing (repro.obs): span causality per request, fault
events bit-matching the device error-word histories, kill -> shrink ->
re-route chains in a ServeGroup trace, the no-op tracer's bit-exactness, and
the EventLog/metrics export satellites (real timestamps, merged summaries)."""
import jax
import pytest

from repro.configs import smoke_config
from repro.core.errors import ErrorCode
from repro.core.faults import FaultSchedule, FaultSpec
from repro.core.resilient import Event, EventLog
from repro.models import build_model
from repro.obs import (
    ENGINE_TID,
    NULL_TRACER,
    NullTracer,
    Tracer,
    dump_trace,
    event_log_to_events,
    fault_report,
    group_chains,
    merge_traces,
    request_timelines,
    validate,
)
from repro.serve import (
    OK,
    EngineConfig,
    Replica,
    Request,
    ServeGroup,
    ServeMetrics,
)

MAX_LEN = 64


@pytest.fixture(scope="module")
def env():
    cfg = smoke_config("recurrentgemma-2b")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


def _replica(env, tracer, **kw):
    cfg, params = env
    conf = {k: kw.pop(k) for k in list(kw) if k in EngineConfig.__dataclass_fields__}
    conf.setdefault("num_slots", 2)
    conf.setdefault("max_len", MAX_LEN)
    conf.setdefault("window", 4)
    conf.setdefault("max_request_retries", 6)
    return Replica(cfg, params=params, config=EngineConfig(**conf),
                   tracer=tracer, **kw)


def _requests(n, max_new=10):
    return [Request(id=i, prompt=(10 + i, 20 + i, 30 + i),
                    max_new_tokens=max_new) for i in range(n)]


def _serve(rep, reqs, inject_at=None):
    for r in reqs:
        assert rep.submit(r) is None
    out, steps, injected = {}, 0, 0
    while not rep.idle():
        if inject_at is not None and steps >= inject_at and not injected:
            eligible = [i for i in rep.sched.active_slots()
                        if rep.sched.slots[i].pending is None]
            if eligible and rep.inject_state_fault(eligible[0]) is not None:
                injected += 1
        for resp in rep.step():
            out[resp.id] = resp
        steps += 1
        assert steps < 1000
    if inject_at is not None:
        assert injected == 1, "injection never landed"
    return out


def _by_name(events, name):
    return [e for e in events if e.get("name") == name]


def _args(ev):
    return ev.get("args") or {}


# -------------------------------------------------------- span causality
def test_clean_run_causal_timeline_per_request(env):
    """Every request's life is one ordered causal chain: submit -> slot
    assignment -> (chunks) -> decode spans -> first_token -> exactly one
    terminal request span containing all of it."""
    tr = Tracer()
    out = _serve(_replica(env, tr), _requests(3))
    assert all(r.status == OK for r in out.values())
    trace = merge_traces(tr)
    assert validate(trace) == []
    timelines = request_timelines(trace)
    assert sorted(timelines) == [0, 1, 2]
    for tid, evs in timelines.items():
        names = [e["name"] for e in evs]
        assert names[0] == "submit"
        assert names.count("request") == 1
        assert "slot_assign" in names
        assert "first_token" in names
        assert "decode" in names
        # wall-ordered causal chain
        assert names.index("submit") < names.index("slot_assign")
        assert names.index("slot_assign") < names.index("first_token")
        term = _by_name(evs, "request")[0]
        assert _args(term)["status"] == OK
        assert _args(term)["tokens"] == len(out[tid].tokens)
    # anonymous engine spans ride the engine lane, not a slot lane
    wins = _by_name(trace["traceEvents"], "window")
    assert wins and all(w["tid"] == ENGINE_TID for w in wins)


def test_overlap_chunks_traced(env):
    """Overlapped admission shows up as chunk events attributed to the
    request, and the chunk count matches the metrics counter."""
    tr = Tracer()
    rep = _replica(env, tr, num_slots=2)
    reqs = [Request(id=i, prompt=tuple(3 + i + j for j in range(9)),
                    max_new_tokens=8) for i in range(4)]
    out = _serve(rep, reqs)
    assert all(r.status == OK for r in out.values())
    chunks = _by_name(tr.events(), "chunk")
    assert len(chunks) == rep.metrics.prefill_chunks
    assert sum(_args(c)["tokens"] for c in chunks) == \
        rep.metrics.prefill_chunk_tokens
    assert all(_args(c)["trace_id"] is not None for c in chunks)


# ------------------------------------------------- fault span bit-matching
def test_window_fault_events_bitmatch_error_words(env):
    """The fault events carry, per attributed slot, the exact error word the
    ``(K, slots)`` history OR-fold read back: their OR equals the combined
    word the recovery policy saw (``FaultRecord.code``), and the causal chain
    fault -> recovery -> recovered closes."""
    tr = Tracer()
    rep = _replica(env, tr)
    # long generations: the faulted lane must still be mid-flight when the
    # deferred detection surfaces, so a recovery lane actually opens
    out = _serve(rep, _requests(3, max_new=24), inject_at=3)
    assert all(r.status == OK for r in out.values())
    records = list(rep.metrics.faults)
    assert records
    trace = merge_traces(tr)
    assert validate(trace) == []
    fault_evs = [e for e in trace["traceEvents"] if e["cat"] == "fault"]
    assert fault_evs
    rec = records[0]
    batch = [e for e in fault_evs
             if _args(e).get("action") == rec.action
             and _args(e)["slot"] in rec.slots]
    assert {(_args(e)["slot"]) for e in batch} == set(rec.slots)
    word = 0
    for e in batch:
        word |= _args(e)["code"]
        # class decomposition matches the word bit-for-bit
        assert set(_args(e)["code_names"]) == {
            c.name for c in ErrorCode(_args(e)["code"]).classes()}
        assert _args(e)["window"] is not None
    assert word == rec.code
    # the fault resolves into a completed recovery lane
    report = fault_report(trace)
    assert report and all(fr.resolved for fr in report)
    recovered = [fr for fr in report if fr.recovery is not None
                 and _args(fr.recovery)["outcome"] == "recovered"]
    assert recovered
    assert all(fr.recovery_s > 0 for fr in recovered)


def test_paged_page_events_and_eviction_requeue():
    """Paged-KV pressure: allocations, frees and evictions all leave page
    events; an evicted lane's requeue -> re-assignment stays on the same
    trace id, and the evicted request still finishes OK."""
    cfg = smoke_config("qwen3-1.7b")
    tr = Tracer()
    rep = Replica(cfg, config=EngineConfig(num_slots=4, max_len=64, window=4,
                                           overlap=True,
                                           max_request_retries=6, paged=True,
                                           page_size=16, page_budget=8),
                  tracer=tr)
    reqs = [Request(id=i, prompt=tuple(3 + i + j for j in range(8)),
                    max_new_tokens=12) for i in range(6)]
    out = _serve(rep, reqs)
    assert all(r.status == OK for r in out.values())
    m = rep.metrics
    evs = tr.events()
    assert len(_by_name(evs, "page_evict")) == m.page_evictions
    assert sum(_args(e)["pages"] for e in _by_name(evs, "page_alloc")) == \
        m.pages_allocated
    assert sum(_args(e)["pages"] for e in _by_name(evs, "page_free")) == \
        m.pages_freed
    assert validate(merge_traces(tr)) == []
    if m.page_evictions:
        ev = _by_name(evs, "page_evict")[0]
        tid = _args(ev)["trace_id"]
        names = [e["name"] for e in evs if _args(e).get("trace_id") == tid]
        # evicted -> requeued -> re-assigned a slot -> still answered
        i = names.index("page_evict")
        assert "requeue" in names[i:]
        assert "slot_assign" in names[names.index("requeue", i):]


def test_spec_draft_events_and_fault_word_strips_reject_bits():
    """Speculative windows: accepted/drafted counters trace per window; a
    real fault's event word may carry DRAFT_REJECT attribution bits, but
    masked by them it bit-matches the fault-raising combined word."""
    cfg = smoke_config("qwen3-1.7b")
    tr = Tracer()
    rep = Replica(cfg, config=EngineConfig(num_slots=2, max_len=64, window=4,
                                           overlap=True,
                                           max_request_retries=6,
                                           speculate=True, draft_len=2,
                                           draft_layers=1),
                  seed=0, tracer=tr)
    reqs = [Request(id=i, prompt=tuple(5 + i + j for j in range(6)),
                    max_new_tokens=10) for i in range(3)]
    out = _serve(rep, reqs, inject_at=3)
    assert all(r.status == OK for r in out.values())
    spec_evs = _by_name(tr.events(), "speculate")
    assert spec_evs
    assert sum(_args(e)["drafted"] for e in spec_evs) == \
        rep.metrics.draft_tokens
    assert sum(_args(e)["accepted"] for e in spec_evs) == \
        rep.metrics.accepted_draft_tokens
    records = list(rep.metrics.faults)
    assert records
    rec = records[0]
    fault_evs = [e for e in tr.events() if e["cat"] == "fault"
                 and _args(e).get("action") == rec.action]
    assert fault_evs
    word = 0
    for e in fault_evs:
        word |= _args(e)["code"]
    assert word & ~int(ErrorCode.DRAFT_REJECT) == rec.code
    assert validate(merge_traces(tr)) == []


# ---------------------------------------------------- group kill chain
def test_group_kill_shrink_reroute_one_connected_trace():
    """A replica kill produces one connected cross-replica chain in the
    merged trace: kill -> ulfm_shrink on every survivor -> reroute per moved
    request -> the re-routed requests' terminal spans on their new owner."""
    cfg = smoke_config("recurrentgemma-2b")
    group = ServeGroup(cfg, 3, config=EngineConfig(num_slots=2, max_len=48,
                                                   window=4, trace=True))
    reqs = [Request(id=i, prompt=(5 + i, 6 + i, 7 + i), max_new_tokens=5)
            for i in range(9)]
    res = group.serve(reqs, faults=FaultSchedule(
        [FaultSpec(step=2, kind="kill", rank=1)]))
    assert all(r.ok for r in res.responses.values())
    assert sorted(res.tracers) == [0, 1, 2]
    trace = res.trace()
    assert validate(trace) == []
    chains = group_chains(trace)
    assert len(chains) == 1
    chain = chains[0]
    assert chain["dead_rank"] == 1
    # both survivors observed the shrink; nobody lists the dead rank
    assert {s["pid"] for s in chain["shrinks"]} == {0, 2}
    assert all(1 not in _args(s)["survivors"] for s in chain["shrinks"])
    # every re-route names the dead rank as source, a survivor as target,
    # and the moved request reached a terminal span on its new owner
    routed = {_args(r)["request"] for r in chain["reroutes"]}
    assert routed == set(res.rerouted)
    for r in chain["reroutes"]:
        assert _args(r)["from_rank"] == 1
        assert _args(r)["to_rank"] in (0, 2)
        term = chain["terminals"][_args(r)["trace_id"]]
        assert term is not None and _args(term)["status"] == OK
        assert term["pid"] == _args(r)["to_rank"]
    # the dead rank's own spans (the cause half) survive in the merged trace
    assert any(e["pid"] == 1 and e["name"] == "replica_kill"
               for e in trace["traceEvents"])
    # satellite: the fleet-level merged summary
    s = res.summary()
    assert s["replicas"] == 3 and s["survivors"] == 2
    assert s["rerouted"] == len(res.rerouted)
    assert s["requests"] == 9 and s["statuses"] == {OK: 9}


# ------------------------------------------------ trace_tool round trip
@pytest.mark.parametrize("source", ["replica_fault", "group_kill"])
def test_dumped_trace_passes_trace_tool_check(env, tmp_path, source):
    """The post-mortem CLI re-validates a trace from disk: a faulted replica
    run's dump and a replica kill's merged group dump both pass
    ``scripts/trace_tool.py --check`` (exit 0), and a dump missing its
    terminal spans fails it (exit 1)."""
    import importlib.util
    import json
    import pathlib

    spec = importlib.util.spec_from_file_location(
        "trace_tool", pathlib.Path(__file__).parents[1] / "scripts"
        / "trace_tool.py")
    trace_tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trace_tool)

    path = tmp_path / "trace.json"
    if source == "replica_fault":
        tr = Tracer()
        out = _serve(_replica(env, tr), _requests(4), inject_at=3)
        assert all(r.status == OK for r in out.values())
        trace = dump_trace(str(path), tr)
        assert any(e["cat"] == "fault" for e in trace["traceEvents"])
    else:
        cfg, _ = env
        group = ServeGroup(cfg, 2, config=EngineConfig(
            num_slots=2, max_len=MAX_LEN, window=4, trace=True))
        res = group.serve(
            [Request(id=i, prompt=(5 + i, 6 + i, 7 + i), max_new_tokens=6)
             for i in range(6)],
            faults=FaultSchedule([FaultSpec(step=1, kind="kill", rank=1)]))
        assert all(r.ok for r in res.responses.values())
        trace = res.trace()
        path.write_text(json.dumps(trace))
    assert trace_tool.main([str(path), "--check"]) == 0
    cut = {**trace, "traceEvents": [
        e for e in trace["traceEvents"]
        if not (e.get("cat") == "request" and e.get("name") == "request")]}
    path.write_text(json.dumps(cut))
    assert trace_tool.main([str(path), "--check"]) == 1


# ----------------------------------------------- no-op tracer / sampling
def test_null_tracer_bit_exact_and_recordless(env):
    """The default (no tracer) serve path records zero events and emits the
    bit-identical token stream a traced replica does; an explicit
    NullTracer serves the same tokens. The null tracer's phase is one shared
    no-op context, and the traced run records every engine phase."""
    plain = _replica(env, None)
    assert isinstance(plain.trace, NullTracer) and not plain.trace.enabled
    base = _serve(plain, _requests(3), inject_at=3)
    assert plain.trace.num_events == 0
    assert NULL_TRACER.num_events == 0
    null = NullTracer()
    off = _serve(_replica(env, null), _requests(3), inject_at=3)
    assert null.num_events == 0
    ph = NULL_TRACER.phase("serve.step", window=1)
    assert ph is NULL_TRACER.phase("serve.wait") is null.phase("serve.commit")
    with ph as p:
        p.note(window=2)
    assert NULL_TRACER.num_events == 0 and null.num_events == 0
    tr = Tracer()
    got = _serve(_replica(env, tr), _requests(3), inject_at=3)
    assert sorted(got) == sorted(base) == sorted(off)
    for i in base:
        assert got[i].tokens == base[i].tokens == off[i].tokens, i
    assert tr.num_events > 0
    assert {e["name"] for e in tr.events() if e["cat"] == "phase"} == set(
        PHASES)


# ----------------------------------------------------- engine phases
PHASES = ("serve.step", "serve.admit", "serve.dispatch", "serve.wait",
          "serve.commit", "serve.recover")


def test_phase_records_one_span_with_its_arguments():
    """``Tracer.phase`` records one ``X`` event on the tracer's clock, on the
    engine lane, with the arguments given at open and those noted inside."""
    tr = Tracer(pid=3, clock=_clock([2.0, 2.5]))
    with tr.phase("serve.dispatch", window=7) as ph:
        ph.note(lanes=5, slots=8)
    (ev,) = tr.events()
    assert ev["name"] == "serve.dispatch" and ev["cat"] == "phase"
    assert ev["ph"] == "X" and ev["tid"] == ENGINE_TID and ev["pid"] == 3
    assert ev["ts"] == pytest.approx(2.0e6)
    assert ev["dur"] == pytest.approx(0.5e6)
    assert _args(ev) == {"window": 7, "lanes": 5, "slots": 8}


def _phases(events):
    return sorted((e for e in events if e.get("cat") == "phase"),
                  key=lambda e: e["ts"])


@pytest.mark.parametrize("inject_at", [None, 3], ids=["clean", "faulted"])
def test_phase_counters_sum_to_the_run_counters(env, inject_at):
    """Each step's phases nest inside its ``serve.step`` without
    overlapping; the dispatch counters (``prompt_tokens``, ``lanes`` of
    ``slots``) and commit counters (``committed``, ``discarded``) sum to
    what ``ServeMetrics`` counted over the run."""
    tr = Tracer()
    rep = _replica(env, tr, num_slots=3)
    reqs = [Request(id=i, prompt=tuple(3 + i + j for j in range(5 + 3 * i)),
                    max_new_tokens=12 + 4 * i) for i in range(5)]
    out = _serve(rep, reqs, inject_at=inject_at)
    assert all(r.status == OK for r in out.values())
    evs = _phases(tr.events())
    steps = [e for e in evs if e["name"] == "serve.step"]
    assert [_args(e)["step"] for e in steps] == list(
        range(steps[0]["args"]["step"], steps[0]["args"]["step"] + len(steps)))
    for st in steps:
        end = st["ts"] + st["dur"]
        kids = [e for e in evs if e["name"] != "serve.step"
                and st["ts"] <= e["ts"] < end]
        assert kids and kids[0]["name"] == "serve.admit"
        t = st["ts"]
        for k in kids:
            assert k["ts"] >= t and k["ts"] + k["dur"] <= end
            t = k["ts"] + k["dur"]
    disp = [_args(e) for e in evs if e["name"] == "serve.dispatch"]
    com = [_args(e) for e in evs if e["name"] == "serve.commit"]
    m = rep.metrics
    assert sum(d["prompt_tokens"] for d in disp) == m.prefill_chunk_tokens
    assert sum(d["prefill_lanes"] for d in disp) == m.prefill_chunks
    assert all(d["prefill_lanes"] <= d["lanes"] <= d["slots"] == 3
               for d in disp)
    assert sum(c["committed"] for c in com) == m.decode_tokens
    assert sum(c["discarded"] for c in com) == m.discarded_tokens
    assert len(com) == m.windows
    assert sorted(c["window"] for c in com) == sorted(
        d["window"] for d in disp)
    waits = [_args(e) for e in evs if e["name"] == "serve.wait"]
    assert len(waits) == m.windows
    assert sum(not w["ready"] for w in waits) == m.window_waits
    assert not _by_name(tr.events(), "window_wait")
    recover = [e for e in evs if e["name"] == "serve.recover"]
    assert bool(recover) == (inject_at is not None)


def test_phases_on_the_profiler_clock(env, tmp_path):
    """Under the JAX profiler every ``serve.*`` span of the tracer has an
    annotation of the same name on the host plane, with the same duration,
    and one offset maps the tracer's clock onto the trace's."""
    from jax.profiler import ProfileData

    tr = Tracer()
    rep = _replica(env, tr, num_slots=2)
    rep.warmup(max_new=8)
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = _serve(rep, _requests(3, max_new=12), inject_at=2)
    finally:
        jax.profiler.stop_trace()
    assert all(r.status == OK for r in out.values())
    (path,) = tmp_path.glob("**/*.xplane.pb")
    ann = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    ann.setdefault(e.name, []).append(
                        (e.start_ns * 1e-3, e.duration_ns * 1e-3,
                         dict(e.stats)))
    spans = {}
    for e in _phases(tr.events()):
        spans.setdefault(e["name"], []).append(e)
    assert set(spans) == set(PHASES) == set(ann)
    offsets = []
    for name, evs in spans.items():
        got = sorted(ann[name], key=lambda a: a[0])
        assert len(got) == len(evs), name
        for e, (start, dur, stats) in zip(evs, got):
            assert abs(dur - e["dur"]) <= 200.0, name      # µs
            for key in ("window", "step"):
                if key in _args(e):
                    assert stats[key] == _args(e)[key], name
            offsets.append(start - e["ts"])
    mid = sorted(offsets)[len(offsets) // 2]
    assert max(abs(o - mid) for o in offsets) <= 200.0


def test_sampling_is_deterministic_and_engine_spans_survive(env):
    """sample=0 keeps engine-scoped spans (windows) but no request-scoped
    ones; the sampling decision is a pure hash of the request id."""
    tr = Tracer(sample=0.0)
    out = _serve(_replica(env, tr), _requests(2))
    assert all(r.status == OK for r in out.values())
    evs = tr.events()
    assert _by_name(evs, "window")          # engine spans always kept
    assert _by_name(evs, "serve.dispatch")
    assert not _by_name(evs, "submit")
    assert all(_args(e).get("trace_id") is None for e in evs)
    assert all(r.trace_id is None for r in out.values())
    half = Tracer(sample=0.5)
    assert [half.sampled(i) for i in range(64)] == \
        [Tracer(sample=0.5).sampled(i) for i in range(64)]
    kept = sum(half.sampled(i) for i in range(1024))
    assert 0 < kept < 1024
    with pytest.raises(ValueError):
        Tracer(sample=1.5)


# ------------------------------------------- EventLog export satellites
def _clock(values):
    it = iter(values)
    last = [0.0]

    def tick():
        for v in it:
            last[0] = v
            return v
        return last[0]

    return tick


def test_to_event_log_emits_real_timestamps_in_wall_order():
    """Satellite 1: the serving EventLog export stamps every event with its
    real wall clock and emits the merged stream in wall order, so a training
    + serving post-mortem interleaves causally."""
    m = ServeMetrics(clock=_clock([10.0, 11.0, 12.0, 13.0]))
    from repro.serve.queue import Response
    m.record_response(Response(id=0, status=OK, tokens=(1,), latency_s=2.0))
    m.record_fault(step=3, code=int(ErrorCode.STATE_FAULT), action="skip",
                   slots=(0,))
    m.record_response(Response(id=1, status=OK, tokens=(2,), latency_s=1.0))
    log = m.to_event_log()
    stamps = [e.t for e in log.events]
    assert stamps == sorted(stamps) and all(t > 0 for t in stamps)
    kinds = [(e.kind, e.t) for e in log.events]
    assert kinds == [("ok", 10.0), ("fault", 11.0), ("ok", 12.0)]
    fault = log.faults()[0]
    assert fault.code == int(ErrorCode.STATE_FAULT) and fault.step == 3
    # responses are re-indexed by completion order
    assert [e.step for e in log.events if e.kind == "ok"] == [0, 1]
    # and the trace_event conversion keeps the ordering (spans start early)
    evs = event_log_to_events(log)
    assert [e["ts"] for e in evs] == [8.0e6, 11.0e6, 11.0e6]
    assert evs[0]["ph"] == "X" and evs[0]["dur"] == 2.0e6
    assert evs[1]["ph"] == "i"


def test_training_event_log_merges_with_serving_trace():
    """One post-mortem reads both worlds: executor EventLog events convert to
    the same trace_event schema and interleave with serving spans by ts."""
    log = EventLog()
    log.add(Event(step=0, kind="ok", duration_s=0.5, t=10.5))
    log.add(Event(step=1, kind="fault", code=int(ErrorCode.NONFINITE_LOSS),
                  action="restore_good", t=11.0))
    train = event_log_to_events(log, pid=7)
    assert all(e["cat"] == "train" and e["pid"] == 7 for e in train)
    tr = Tracer(clock=_clock([10.2]))
    tr.instant("submit", "request", trace_id=0)
    merged = merge_traces(tr)
    merged["traceEvents"].extend(train)
    from repro.obs import events_of
    names = [e["name"] for e in events_of(merged)]
    assert names == ["ok", "submit", "fault"]


def test_metrics_merged_pools_populations():
    """Satellite 2: ServeMetrics.merged sums counters, maxes peaks, pools
    responses so percentiles cover the fleet's population."""
    from repro.serve.queue import Response
    a = ServeMetrics(clock=_clock([1.0, 2.0]))
    b = ServeMetrics(clock=_clock([4.0, 5.0]))
    a.record_window(4, 1, 4)
    b.record_window(6, 0, 4)
    a.record_pages(allocated=3, in_use=3)
    b.record_pages(allocated=2, in_use=5)
    a.record_response(Response(id=0, status=OK, tokens=(1,), latency_s=1.0))
    b.record_response(Response(id=1, status=OK, tokens=(2,), latency_s=3.0))
    m = ServeMetrics.merged([a, b])
    assert m.decode_tokens == 10 and m.windows == 2
    assert m.pages_allocated == 5 and m.peak_pages_in_use == 5
    assert len(m.responses) == 2
    assert m.latency_percentiles()["p99"] > 2.0     # pooled, not averaged
    # fleet wall window spans min t0 .. max t_last across replicas
    assert m.tokens_per_s() == pytest.approx(10 / 3.0)
