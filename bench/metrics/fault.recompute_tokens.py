"""fault.recompute_tokens: prompt plus generated tokens re-fed to rebuild a
faulted lane (the tracer's ``chunk`` tokens of the request from its first
fresh chunk after the ``fault`` until the chunk that exhausts it), mean per
fault in the window."""


def read(art):
    ev = sorted(art["tracer"], key=lambda e: e["ts"])
    lo, hi = art["run"].t_open * 1e6, art["run"].t_close * 1e6
    vals = []
    for i, e in enumerate(ev):
        a = e.get("args") or {}
        if e["name"] != "fault" or a.get("trace_id") is None:
            continue
        if not lo <= e["ts"] < hi:
            continue
        tid, n, started = a["trace_id"], 0, False
        for f in ev[i + 1:]:
            b = f.get("args") or {}
            if f["name"] != "chunk" or b.get("trace_id") != tid:
                continue
            if b.get("fresh"):
                if started:
                    break        # faulted again before it was rebuilt
                started = True
            if started:
                n += b["tokens"]
                if b.get("exhausts"):
                    vals.append(n)
                    break
    return sum(vals) / len(vals) if vals else None
