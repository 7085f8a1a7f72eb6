"""Readers of the window engine's phase spans (``serve.*``, the tracer's
``phase`` events) and the lane counters they carry, shared by the metrics
that read them.

A step is profiled when its ``serve.step`` starts inside
``art["profile_host"]``; a window is profiled when its ``serve.dispatch``
does. Both are on the tracer's clock (host seconds; events in µs). A
program without these spans gives no events, and every reader returns None.
"""
from __future__ import annotations

import bisect


def _args(e: dict) -> dict:
    return e.get("args") or {}


def _spans(events: list, name: str) -> list:
    return [e for e in events if e["name"] == name and e.get("ph") == "X"]


def _inside(art, e: dict) -> bool:
    lo, hi = art["profile_host"]
    return lo is not None and hi is not None and lo <= e["ts"] * 1e-6 < hi


def host_ms_per_window(art):
    """Host time of the profiled steps (each ``serve.step`` less the
    ``serve.wait`` spans inside it) over the windows those steps retired
    (their ``serve.wait`` spans), in ms."""
    waits = {}
    for w in _spans(art["tracer"], "serve.wait"):
        waits.setdefault(w["pid"], []).append((w["ts"], w["dur"]))
    for v in waits.values():
        v.sort()
    host_us, windows = 0.0, 0
    for st in _spans(art["tracer"], "serve.step"):
        if not _inside(art, st):
            continue
        ws = waits.get(st["pid"], [])
        end = st["ts"] + st["dur"]
        i = bisect.bisect_left(ws, (st["ts"], -1.0))
        inner = []
        while i < len(ws) and ws[i][0] < end:
            if ws[i][0] + ws[i][1] <= end:
                inner.append(ws[i][1])
            i += 1
        host_us += st["dur"] - sum(inner)
        windows += len(inner)
    return host_us / windows * 1e-3 if windows else None


def dispatches(art) -> list:
    """Args of the profiled windows' ``serve.dispatch`` spans."""
    return [_args(e) for e in _spans(art["tracer"], "serve.dispatch")
            if _inside(art, e) and "lanes" in _args(e)]


def occupancy(art):
    """Lanes holding a request over slots, summed over the profiled
    dispatches, in %."""
    ds = dispatches(art)
    slots = sum(d["slots"] for d in ds)
    return sum(d["lanes"] for d in ds) / slots * 100.0 if slots else None


def window_tokens(art) -> list:
    """``(prompt_tokens, committed)`` of each profiled window that was
    retired (its ``serve.commit`` is in the trace)."""
    fed = {(e["pid"], _args(e)["window"]): _args(e)["prompt_tokens"]
           for e in _spans(art["tracer"], "serve.dispatch")
           if _inside(art, e) and "prompt_tokens" in _args(e)}
    out = []
    for e in _spans(art["tracer"], "serve.commit"):
        key = (e["pid"], _args(e).get("window"))
        if key in fed and "committed" in _args(e):
            out.append((fed[key], _args(e)["committed"]))
    return out


def prefill_share(art):
    """Prompt tokens fed over prompt tokens plus tokens committed, over the
    profiled windows, in %."""
    ws = window_tokens(art)
    total = sum(p + c for p, c in ws)
    return sum(p for p, _ in ws) / total * 100.0 if total else None


def useful_tokens(art):
    """Prompt tokens fed plus tokens committed, per profiled window."""
    ws = window_tokens(art)
    return sum(p + c for p, c in ws) / len(ws) if ws else None
