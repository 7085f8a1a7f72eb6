"""Which lanes each decode window ran, and from what position, read from the
engine's tracer events.

A lane (one request's stay in a slot) starts at the window whose prompt
chunk is ``fresh`` (admission, or a restart after a fault) at position 0 and
then advances ``K`` positions every window it takes part in: a prefilling
lane feeds a chunk every window, and a decoding lane decodes every window.
A lane takes part in window ``w`` when the tracer has a ``chunk`` instant or
a ``decode`` span of it for ``w``.
"""
from __future__ import annotations

from collections import defaultdict


def _args(e: dict) -> dict:
    return e.get("args") or {}


def window_starts(events: list, K: int) -> dict:
    """``{window: [start position of each lane]}``."""
    fresh = defaultdict(list)         # trace id -> windows of fresh chunks
    seen = defaultdict(set)           # window -> trace ids
    for e in events:
        a = _args(e)
        if e["name"] == "chunk" and a.get("trace_id") is not None:
            seen[a["window"]].add(a["trace_id"])
            if a.get("fresh"):
                fresh[a["trace_id"]].append(a["window"])
        elif e["name"] == "decode" and a.get("trace_id") is not None:
            seen[a["window"]].add(a["trace_id"])
    out = {}
    for w, tids in seen.items():
        starts = []
        for t in tids:
            w0 = max((f for f in fresh[t] if f <= w), default=None)
            if w0 is not None:
                starts.append(K * (w - w0))
        out[w] = starts
    return out


def dispatch_times(events: list) -> dict:
    """``{window: dispatch time in seconds}`` from the engine's window spans."""
    return {_args(e)["window"]: e["ts"] * 1e-6 for e in events
            if e["name"] == "window" and e.get("ph") == "X"}


def windows_between(events: list, K: int, lo: float, hi: float) -> dict:
    """Lanes of the windows dispatched inside ``[lo, hi)`` (host seconds)."""
    starts = window_starts(events, K)
    t = dispatch_times(events)
    return {w: s for w, s in starts.items() if w in t and lo <= t[w] < hi}
