"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy/idle time.

What is read, by name:

- device planes ``/device:TPU:<n>``: the ``XLA Ops`` line (one event per
  operation that ran) and the ``XLA Modules`` line (one event per program
  run, named ``jit_<function>(<hash>)``);
- host threads (the ``/host:CPU`` plane): the benchmark's own annotations,
  ``bench.mark`` at the traced window's two ends and ``bench.*`` /
  ``replica.step`` around what the host was doing.

Busy time is the union of the operation intervals inside the window,
averaged over the chips; idle is the rest of the window. Each idle gap is
labelled with the host annotation that overlaps it most (``unlabelled`` when
none does). Per program: the device seconds and run count of each module,
named without its hash; a run the window's edge cuts counts for the share
inside, so seconds over runs is the time of one run.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

_HASH = re.compile(r"\(\d+\)$")


def _op_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` → ``fusion.12``."""
    head = name.split(" = ", 1)[0].strip()
    return head.lstrip("%") or name[:60]


def load(path: str) -> dict:
    """Events of one trace: ``{"ops": {dev: [(start, end, name)]},
    "modules": {dev: [(start, end, name)]}, "host": [(start, end, name)]}``,
    times in nanoseconds on the trace's own clock."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, host = defaultdict(list), defaultdict(list), []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name not in ("XLA Ops", "XLA Modules"):
                    continue
                dst = ops if line.name == "XLA Ops" else modules
                for e in line.events:
                    s = float(e.start_ns)
                    name = (_op_name(e.name) if line.name == "XLA Ops"
                            else _HASH.sub("", e.name))
                    dst[plane.name].append((s, s + float(e.duration_ns),
                                            name))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench.") or e.name == \
                            "replica.step":
                        s = float(e.start_ns)
                        host.append((s, s + float(e.duration_ns), e.name))
    for d, evs in ops.items():
        # name each operation within the program that ran it
        mods = sorted(modules.get(d, []))
        starts = [m[0] for m in mods]
        named = []
        for s, e, name in evs:
            i = bisect.bisect_right(starts, s) - 1
            prog = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
            named.append((s, e, f"{prog}/{name}"))
        ops[d] = named
    return {"ops": dict(ops), "modules": dict(modules), "host": host}


def union(intervals, lo: float, hi: float) -> list:
    """Merged ``(start, end)`` intervals clipped to ``[lo, hi]``."""
    out = []
    for s, e, *_ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def gaps_of(busy: list, lo: float, hi: float) -> list:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(gap: tuple, host: list) -> str:
    """The host annotation overlapping ``gap`` most; the innermost wins a
    tie (shorter span)."""
    best, best_key = "unlabelled", (0.0, 0.0)
    for s, e, name in host:
        if name == "bench.mark":
            continue
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov <= 0:
            continue
        key = (ov, -(e - s))
        if key > best_key:
            best, best_key = name, key
    return best


def self_times(events, lo: float, hi: float) -> dict:
    """Seconds·1e9 per operation name inside ``[lo, hi]``, each event less
    the events nested in it (a ``while`` holds its body's operations)."""
    out = defaultdict(float)
    stack: list = []          # [end, name, self]
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, nm, own = stack.pop()
            out[nm] += own
        own = max(0.0, min(e, hi) - max(s, lo))
        if stack:
            stack[-1][2] -= own
        stack.append([e, name, own])
    for _, nm, own in stack:
        out[nm] += own
    return out


def reduce(tr: dict, *, chips: int = 1, top: int = 10) -> dict:
    marks = sorted(s for s, _, n in tr["host"] if n == "bench.mark")
    if len(marks) >= 2:
        lo, hi = marks[0], marks[-1]
    else:   # no marks: the span of everything the device ran
        every = [x for v in tr["ops"].values() for x in v]
        lo = min(s for s, _, _ in every)
        hi = max(e for _, e, _ in every)
    window = (hi - lo) * 1e-9
    devs = sorted(tr["ops"])
    if len(devs) < chips:
        raise ValueError(f"trace holds {len(devs)} device(s), the cell runs "
                         f"on {chips}")
    busy_total, op_time, prog = 0.0, defaultdict(float), {}
    gap_list = []
    for d in devs:
        busy = union(tr["ops"][d], lo, hi)
        busy_total += sum(e - s for s, e in busy)
        for name, t in self_times(tr["ops"][d], lo, hi).items():
            op_time[name] += t
        gap_list += [(e - s, label((s, e), tr["host"]))
                     for s, e in gaps_of(busy, lo, hi)]
        for s, e, name in tr["modules"].get(d, []):
            ov = min(e, hi) - max(s, lo)
            if ov > 0:
                # a run cut by the window's edge counts for its share
                p = prog.setdefault(name, {"s": 0.0, "n": 0.0})
                p["s"] += ov * 1e-9
                p["n"] += ov / (e - s)
    n = len(devs)
    busy_s = busy_total * 1e-9 / n
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    gap_list.sort(key=lambda g: -g[0])
    by_label = defaultdict(float)
    for g, name in gap_list:
        by_label[name] += g * 1e-9 / n
    return {
        "window_s": window,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window if window > 0 else None,
        "programs": {k: {"s": v["s"] / n, "n": v["n"] / n}
                     for k, v in prog.items()},
        "device_ops": [[k, v * 1e-9 / n] for k, v in ops],
        "idle_gaps": [[name, g * 1e-9] for g, name in gap_list[:top]],
        "idle_by_host": dict(by_label),
    }


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def reduce_dir(directory: str, *, chips: int = 1) -> dict:
    return reduce(load(find_xplane(directory)), chips=chips)
