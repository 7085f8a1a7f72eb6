"""Readers shared by metrics that are split by the end-to-end metric they
move (``window.device_ms`` moves ``tpot_p90_ms`` in the chat cells and
``window.device_ms.code`` moves ``tok_per_s`` in the code cell): one
computation, one file per metric name under ``metrics/``."""
from __future__ import annotations

import counts
import lanes
from peaks import peaks


def window_device_ms(art):
    """Device time of one ``jit_window_step`` run, in ms, averaged over the
    runs in the traced part of the window."""
    p = art["device"]["programs"].get("jit_window_step")
    if not p or not p["n"]:
        return None
    return p["s"] / p["n"] * 1e3


def window_roofline(art):
    """The least time the chip could take for one window (the larger of its
    operations over peak FLOP/s and its bytes over peak HBM bandwidth, from
    ``counts.py`` for the lanes the window ran at their positions) over the
    window program's measured device time, in %; averaged per window over
    the windows dispatched while profiled."""
    p = art["device"]["programs"].get("jit_window_step")
    lo, hi = art["profile_host"]
    ws = lanes.windows_between(art["tracer"], art["window"], lo, hi)
    ws = {w: s for w, s in ws.items() if s}
    if not p or not p["n"] or not ws:
        return None
    pk = peaks(art["device_kind"])
    least = 0.0
    for starts in ws.values():
        f, b = counts.window_cost(art["config"], art["window"], starts)
        least += max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
    return least / len(ws) / (p["s"] / p["n"]) * 100.0


def model_mfu(art):
    """Model FLOPs of every lane-step the windows dispatched while profiled
    ran (``counts.py``, at each lane's position), over that span's wall
    seconds times the chip's peak bf16 FLOP/s, in %."""
    lo, hi = art["profile_host"]
    ws = lanes.windows_between(art["tracer"], art["window"], lo, hi)
    if not ws or hi <= lo:
        return None
    flops = sum(counts.window_cost(art["config"], art["window"], s)[0]
                for s in ws.values() if s)
    return flops / ((hi - lo) * peaks(art["device_kind"])["bf16_flops_per_s"]
                    ) * 100.0


def idle_share(art):
    """Share of the traced span in which no operation ran on the device
    (1 − union of the ``XLA Ops`` intervals / span), in %."""
    v = art["device"]["idle_share"]
    return None if v is None else v * 100.0
