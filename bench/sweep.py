#!/usr/bin/env python3
"""Find an open-loop cell's knee: offer its mix at several rates, one process.

    python bench/sweep.py --workload qwen3-1.7b.chat --rates 0.7,0.8,0.9 --seconds 51 --seed 5

For each rate the cell's mix (every other parameter as committed) is offered
to a fresh ``Replica`` on the same seeded weights for the window and its
drain, with the engine's tracer on. One JSON line per rate: requests due,
unanswered after the drain, and for the first and the last third of the
requests due (by due time) the mean queue wait (tracer ``submit`` to
``slot_assign``) and the median time from due to first token. Below the knee
the last third waits no longer than the first; above it the queue grows all
through the window. The rate found is written into the mix file by hand
(``knee_per_s``, and ``rate_per_s`` at four fifths of it); no run searches.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def thirds(owed: list, events: list) -> list:
    sub, got = {}, {}
    for e in events:
        t = (e.get("args") or {}).get("trace_id")
        if e["name"] == "submit":
            sub.setdefault(t, e["ts"])
        elif e["name"] == "slot_assign":
            got.setdefault(t, e["ts"])
    owed = sorted(owed, key=lambda r: r.t_due)
    n = len(owed)
    out = []
    for part in (owed[:n // 3], owed[n - n // 3:]):
        waits = [(got[r.rid] - sub[r.rid]) * 1e-6 for r in part
                 if r.rid in got and r.rid in sub]
        ttft = sorted(r.t_submit - r.t_due + r.ttft_s for r in part
                      if r.status == "ok" and r.ttft_s is not None)
        out.append({"n": len(part),
                    "wait_s": sum(waits) / len(waits) if waits else None,
                    "ttft_p50_s": ttft[len(ttft) // 2] if ttft else None})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), HERE]
    cache = os.path.join(ROOT, ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import jax
    import numpy as np

    import drive as drive_mod
    import run
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import build_model
    from repro.obs.trace import Tracer
    from repro.serve import EngineConfig, Replica
    from traffic import make_plan
    from weights import make_weights

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    spec = run.cell_spec(args.workload)
    conf, mix = spec["config"], spec["mix"]
    if mix["loop"] != "open":
        raise SystemExit("sweep: only an open-loop mix has a rate")
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("sweep: no TPU")
    cfg = run.model_config(conf)
    layout = jax.eval_shape(lambda: build_model(cfg).init(
        jax.random.PRNGKey(0)))
    params = make_weights(layout, args.seed, conf["init_std"])
    for rate in (float(r) for r in args.rates.split(",")):
        clock = time.monotonic
        tracer = Tracer(clock=clock)
        replica = Replica(cfg, params, config=EngineConfig(**conf["engine"]),
                          tracer=tracer, clock=clock)
        replica.warmup(max_new=2 * replica.window)
        plan = make_plan(dict(mix, rate_per_s=rate), args.seed, args.seconds,
                         cfg.vocab_size)
        res = drive_mod.drive(replica, plan, seconds=args.seconds,
                              drain_s=mix["drain_s"],
                              rng=np.random.default_rng([args.seed, 1]),
                              clock=clock)
        owed = [r for r in res.records.values()
                if res.t_open <= r.t_due < res.t_close]
        first, last = thirds(owed, tracer.events())
        print(json.dumps({"rate_per_s": rate, "due": len(owed),
                          "unanswered": sum(r.status != "ok" for r in owed),
                          "windows": res.windows,
                          "window_wall_s": (res.snap_close[0]
                                            - res.snap_open[0])
                          / max(res.windows, 1),
                          "first_third": first, "last_third": last}),
              flush=True)
        del replica
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
