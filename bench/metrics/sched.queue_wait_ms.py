"""sched.queue_wait_ms: mean time from a request's acceptance (tracer
``submit``) to its first slot (tracer ``slot_assign``), in ms, over the
requests due in the window."""


def read(art):
    want = {r.rid for r in art["owed"]}
    sub, got = {}, {}
    for e in art["tracer"]:
        a = e.get("args") or {}
        t = a.get("trace_id")
        if t not in want:
            continue
        if e["name"] == "submit":
            sub.setdefault(t, e["ts"])
        elif e["name"] == "slot_assign":
            got.setdefault(t, e["ts"])
    waits = [(got[t] - sub[t]) * 1e-3 for t in got if t in sub]
    return sum(waits) / len(waits) if waits else None
