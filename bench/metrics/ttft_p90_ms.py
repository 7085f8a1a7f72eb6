"""ttft_p90_ms: for every request due in the window, the time from when it
was due to its first token, in ms; the 90th percentile over all of them. A
request never answered counts as infinitely late (no value then)."""
import numpy as np


def read(art):
    vals = []
    for r in art["owed"]:
        if r.status != "ok" or r.ttft_s is None:
            return None
        vals.append((r.t_submit - r.t_due + r.ttft_s) * 1e3)
    return float(np.percentile(vals, 90)) if vals else None
