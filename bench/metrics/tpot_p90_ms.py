"""tpot_p90_ms: per answered request due in the window with two tokens or
more, (answer time − first-token time) / (tokens − 1), in ms; the 90th
percentile over all of them."""
import numpy as np


def read(art):
    vals = [((r.t_done - r.t_submit) - r.ttft_s) / (len(r.tokens) - 1) * 1e3
            for r in art["owed"]
            if r.status == "ok" and r.ttft_s is not None and len(r.tokens) > 1]
    return float(np.percentile(vals, 90)) if vals else None
