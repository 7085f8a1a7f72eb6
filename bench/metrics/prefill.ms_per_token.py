"""prefill.ms_per_token: per request due in the window, (first token − first
slot assignment) / prompt tokens, from the tracer's ``slot_assign`` and
``first_token`` instants; the mean, in ms."""


def read(art):
    plen = {r.rid: r.prompt_len for r in art["owed"]}
    assign, first = {}, {}
    for e in art["tracer"]:
        t = (e.get("args") or {}).get("trace_id")
        if t not in plen:
            continue
        if e["name"] == "slot_assign":
            assign.setdefault(t, e["ts"])
        elif e["name"] == "first_token":
            first.setdefault(t, e["ts"])
    vals = [(first[t] - assign[t]) * 1e-3 / plen[t] for t in first
            if t in assign]
    return sum(vals) / len(vals) if vals else None
