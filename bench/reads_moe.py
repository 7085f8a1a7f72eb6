"""Readers of the expert layer's counters: the ``moe_pairs`` argument the
window engine puts on each window's ``serve.commit`` span (rows routed to
the held experts by the lanes that ran a request, summed over the window's
steps and the layers). A program without the counter gives no such
argument, and every reader returns None."""
from __future__ import annotations

import counts_moe
import lanes
import spans
from peaks import peaks


def window_pairs(events: list) -> dict:
    """``{(pid, window): moe_pairs}`` of the retired windows."""
    return {(e["pid"], spans._args(e)["window"]): spans._args(e)["moe_pairs"]
            for e in spans._spans(events, "serve.commit")
            if "moe_pairs" in spans._args(e)}


def pairs_per_expert_step(art):
    """Rows that share one read of a held expert's weights: ``moe_pairs``
    over the profiled windows (their ``serve.dispatch`` inside the profiled
    span), over held experts × layers × the windows' steps."""
    pairs = window_pairs(art["tracer"])
    got = [pairs[(e["pid"], spans._args(e)["window"])]
           for e in spans._spans(art["tracer"], "serve.dispatch")
           if spans._inside(art, e)
           and (e["pid"], spans._args(e).get("window")) in pairs]
    if not got:
        return None
    c = art["config"]
    reads = (len(got) * art["window"] * c["num_hidden_layers"]
             * c["num_experts_held"])
    return sum(got) / reads


def _profiled(art) -> list:
    """``(lane starts, moe_pairs)`` of each window dispatched while
    profiled that ran lanes and reported its pairs."""
    lo, hi = art["profile_host"]
    if lo is None or hi is None:
        return []
    ws = lanes.windows_between(art["tracer"], art["window"], lo, hi)
    pairs = {w: n for (_, w), n in window_pairs(art["tracer"]).items()}
    return [(s, pairs[w]) for w, s in ws.items() if s and w in pairs]


def window_roofline(art):
    """The least time for one window (the larger of ``counts_moe``'s
    operations over peak FLOP/s and its bytes over peak HBM bandwidth) over
    the window program's measured device time, in %; averaged per window
    over the profiled windows."""
    p = art["device"]["programs"].get("jit_window_step")
    ws = _profiled(art)
    if not p or not p["n"] or not ws:
        return None
    pk = peaks(art["device_kind"])
    least = 0.0
    for starts, n in ws:
        f, b = counts_moe.window_cost(art["config"], art["window"], starts, n)
        least += max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
    return least / len(ws) / (p["s"] / p["n"]) * 100.0


def model_mfu(art):
    """``counts_moe``'s operations of the windows dispatched while profiled,
    over that span's wall seconds times the chip's peak bf16 FLOP/s, in %."""
    lo, hi = art["profile_host"]
    ws = _profiled(art)
    if not ws or hi <= lo:
        return None
    flops = sum(counts_moe.window_cost(art["config"], art["window"], s, n)[0]
                for s, n in ws)
    return flops / ((hi - lo) * peaks(art["device_kind"])["bf16_flops_per_s"]
                    ) * 100.0
