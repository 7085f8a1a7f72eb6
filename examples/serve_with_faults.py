"""Serving demo on the ``repro.serve`` subsystem: continuous batching with the
paper's fault machinery fused in.

    PYTHONPATH=src python examples/serve_with_faults.py

Act 1 — one replica, a soft fault. A :class:`Replica` continuously batches
requests through the **stall-free decode window** engine (``window=4``,
overlapped admission): four greedy steps run fused on device per dispatch,
fault detection deferred to the window boundary, and every admission rides
the windows as a background prefill lane — chunked prompt tokens fed inside
the same scan, so the host never blocks on a prefill (reduced
recurrentgemma: hybrid RG-LRU + local attention, O(1) state per token).
Midway we flip a bit of one sequence's recurrent state (a simulated SDC —
the paper's soft-fault class). The ``DeviceFuture`` raises
``PropagatedError`` at the *window* wait; the ``(K, slots)`` word history
names the poisoned ``(step, slot)``, the clean prefix commits, and the
replica re-queues just that sequence as a fresh lane (LFLR: recompute,
don't restart) while its batch-mates keep decoding — recovery overlaps
progress, the paper's asynchrony applied end to end.

Act 2 — a replica fleet, a hard fault. A :class:`ServeGroup` of three
replicas serves a request stream; we kill one replica mid-flight. Survivors'
next health exchange raises (ULFM revoke → agree), they shrink 3 → 2 and
re-route the dead replica's unanswered requests — every accepted request is
answered, nothing deadlocks, nothing aborts.

Act 3 — the fleet itself dies, and comes back. The same group serves with a
durable write-ahead ledger (every submit / route / retirement a checksummed,
fsync'd record); we kill one replica mid-flight, then stop the *whole fleet*
two rounds later — the SIGKILL analogue, only the log survives. A new
incarnation restarts from the ledger alone (``serve_from_ledger``): answered
requests return bit-exact from their retire records, outstanding ones replay
onto the survivors, and the killed rank re-enters through the non-blocking
join (warm-up + state transfer as a background lane, then one widened epoch
— survivors never stall). Zero requests dropped across the crash, every
token stream bit-exact vs a clean run.

All acts run with fault-causality tracing on (``repro.obs``, DESIGN
§3.5/§3.7): every request's life is a span chain, every fault event carries
the exact device error word, and the merged traces — kill → shrink →
re-route → fleet stop → ledger replay → rejoin included — are dumped to
``artifacts/serve-trace.json`` / ``artifacts/serve-crash-trace.json`` (open
them in Perfetto, or run ``python scripts/trace_tool.py <file> --chains``)
and pretty-printed here.
"""
import json
import os
import sys

sys.path.insert(0, "src")

ARTIFACTS = os.environ.get("REPRO_ARTIFACTS", "artifacts")


def _artifact(name):
    os.makedirs(ARTIFACTS, exist_ok=True)
    return os.path.join(ARTIFACTS, name)

from repro.configs import smoke_config  # noqa: E402
from repro.core.faults import FaultSchedule, FaultSpec  # noqa: E402
from repro.obs import (  # noqa: E402
    Tracer,
    dump_trace,
    format_fault_report,
    format_timeline,
    group_chains,
    merge_trace_dicts,
    merge_traces,
    validate,
)
from repro.serve import EngineConfig, Replica, Request, ServeGroup  # noqa: E402


def act1_soft_fault(cfg):
    print("=== Act 1: decode windows + per-sequence LFLR on one replica ===")
    tracer = Tracer()
    replica = Replica(cfg, config=EngineConfig(num_slots=4, max_len=48,
                                               window=4), tracer=tracer)
    for i in range(6):      # 6 requests onto 4 slots: backfill is exercised
        rej = replica.submit(Request(id=i, prompt=(11 + i, 22 + i, 33 + i),
                                     max_new_tokens=12))
        assert rej is None, rej
    responses, steps = [], 0
    while not replica.idle():
        if steps == 1:
            slot = replica.inject_state_fault()
            print(f"window 1: injected NaN into slot {slot}'s recurrent "
                  "state (simulated SDC)")
        responses.extend(replica.step())
        steps += 1
    for r in sorted(responses, key=lambda r: r.id):
        print(f"  request {r.id}: {r.status}, tokens={list(r.tokens)}, "
              f"retries={r.retries}")
    s = replica.metrics.summary()
    print(f"  faults seen: {s['faults']}  |  {s['windows']} windows, "
          f"{s['discarded_tokens']} trailing tokens discarded  |  "
          f"{s['tokens_per_s']:.0f} tok/s, "
          f"p50 latency {s['latency_p50_s'] * 1e3:.0f} ms")
    print(f"  stall-free: {s['prefill_chunks']} prompt chunks fused into "
          f"windows ({s['prefill_chunk_tokens']} tokens), "
          f"TTFT p50 {s['ttft_p50_s'] * 1e3:.0f} ms")
    # the post-mortem view of the same run: the fault event carries the exact
    # device error word, joined to the recovery lane that resolved it
    trace = merge_traces(tracer)
    problems = validate(trace)
    assert not problems, problems
    print("  fault causality (repro.obs):")
    for line in format_fault_report(trace).splitlines():
        print(f"  {line}")
    faulted = [r for r in responses if r.retries]
    if faulted:
        print("  timeline of the faulted request:")
        for line in format_timeline(trace, faulted[0].trace_id).splitlines():
            print(f"  {line}")
    print()


def act2_hard_fault(cfg):
    print("=== Act 2: replica kill -> shrink + re-route on a ServeGroup ===")
    group = ServeGroup(cfg, 3, config=EngineConfig(num_slots=2, max_len=48,
                                                   trace=True))
    requests = [Request(id=i, prompt=(5 + i, 6 + i, 7 + i), max_new_tokens=6)
                for i in range(9)]
    result = group.serve(requests, faults=FaultSchedule(
        [FaultSpec(step=2, kind="kill", rank=1)]))
    print(f"  killed replicas: {[r.rank for r in result.reports if r.killed]}")
    print(f"  re-routed requests: {list(result.rerouted)}")
    for rank in (0, 2):
        report = result.report(rank)
        print(f"  rank {rank} events: {report.events}")
    answered = {i: r.status for i, r in sorted(result.responses.items())}
    by_replica = {}
    for r in result.responses.values():
        by_replica.setdefault(r.replica, 0)
        by_replica[r.replica] += 1
    print(f"  statuses: {answered}")
    print(f"  answered per replica: {by_replica}")
    assert all(r.ok for r in result.responses.values())
    print("  all accepted requests answered despite the kill — no deadlock, "
          "no abort")
    # the merged trace stitches all three ranks — the dead one included —
    # into one causal object: kill -> ulfm shrink -> ledger re-route ->
    # terminal answers on the survivors
    trace_path = _artifact("serve-trace.json")
    trace = dump_trace(trace_path, *(result.tracers[r]
                                     for r in sorted(result.tracers)))
    problems = validate(trace)
    assert not problems, problems
    n = len(trace["traceEvents"])
    print(f"  trace: {n} events from 3 replicas -> {trace_path} "
          "(perfetto/chrome://tracing, or scripts/trace_tool.py)")
    for c in group_chains(trace):
        routed = ", ".join(
            f"req {(r.get('args') or {}).get('request')}"
            f"->r{(r.get('args') or {}).get('to_rank')}"
            for r in c["reroutes"])
        print(f"  chain: replica {c['dead_rank']} killed -> shrink seen by "
              f"{sorted({s['pid'] for s in c['shrinks']})} -> [{routed}]")
    summary = result.summary()
    print(f"  fleet summary (merged): {summary['requests']} requests, "
          f"{summary['replicas']} replicas ({summary['survivors']} "
          f"survivors), {summary['rerouted']} re-routed, "
          f"p99 latency {summary['latency_p99_s'] * 1e3:.0f} ms")


def act3_crash_replay_regrow(cfg):
    print("=== Act 3: fleet crash -> ledger replay -> elastic regrow ===")
    ledger_path = _artifact("serve-ledger.wal")
    if os.path.exists(ledger_path):
        os.remove(ledger_path)      # a stale log must not replay into this run
    group = ServeGroup(cfg, 3, max_ranks=3,
                       config=EngineConfig(num_slots=2, max_len=48,
                                           trace=True))
    mk = lambda: [Request(id=i, prompt=(5 + i, 6 + i, 7 + i),
                          max_new_tokens=6) for i in range(12)]
    clean = group.serve(mk())

    # incarnation 1: rank 2 dies at round 2, the WHOLE fleet stops at round 4
    # — every rank is gone, only the fsync'd write-ahead ledger survives
    r1 = group.serve(mk(), faults=FaultSchedule(
        [FaultSpec(step=2, kind="kill", rank=2)]),
        ledger_path=ledger_path, crash_at=4)
    assert r1.crashed
    print(f"  incarnation 1: killed rank 2, then the whole fleet stopped — "
          f"{len(r1.responses)}/12 answered, "
          f"{os.path.getsize(ledger_path)} bytes of ledger survive")

    # incarnation 2: restart from the log alone, replay the outstanding set,
    # and re-admit the killed rank through the non-blocking join
    r2 = group.serve_from_ledger(ledger_path, joins=[1])
    merged = {**r1.responses, **r2.responses}
    assert sorted(merged) == list(range(12)), "requests dropped in the crash"
    assert all(r.ok for r in merged.values())
    for rid, resp in merged.items():
        assert tuple(resp.tokens) == tuple(clean.responses[rid].tokens)
    print(f"  incarnation 2: {len(r2.replayed)} requests replayed from the "
          f"ledger, rank 2 rejoined via non-blocking join (epoch {r2.epoch})")
    print("  zero drops across the crash; every stream bit-exact vs the "
          "clean run")

    # one causal story across both incarnations: kill -> shrink -> fleet
    # stop -> ledger replay -> state transfer -> rejoin, in a single trace
    trace = merge_trace_dicts(r1.trace(), r2.trace())
    problems = validate(trace)
    assert not problems, problems
    crash_path = _artifact("serve-crash-trace.json")
    with open(crash_path, "w") as f:
        json.dump(trace, f)
    names = [e["name"] for e in trace["traceEvents"] if e.get("cat") == "group"]
    story = [n for n in ("replica_kill", "ulfm_shrink", "fleet_stop",
                         "ledger_replay", "state_transfer", "replica_join")
             if n in names]
    print(f"  merged trace: {len(trace['traceEvents'])} events, group story "
          f"{' -> '.join(story)} -> {crash_path}")
    for c in group_chains(trace):
        if c["rejoins"]:
            a = c["rejoins"][0].get("args") or {}
            print(f"  chain: replica {c['dead_rank']} killed -> "
                  f"{len(c['reroutes'])} re-routes -> rejoined at epoch "
                  f"{a.get('epoch')} ({a.get('reason')})")


def main():
    cfg = smoke_config("recurrentgemma-2b")
    print(f"serving a reduced {cfg.name} ({cfg.num_layers} layers)\n")
    act1_soft_fault(cfg)
    act2_hard_fault(cfg)
    print()
    act3_crash_replay_regrow(cfg)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
