#!/usr/bin/env python3
"""Read the numbers a cell's limits are set from, many seeds in one process.

    python bench/calibrate.py --workload qwen3-1.7b.chat --seeds 1,2,3 --seconds 20

Each seed is one whole run of the cell (``run.run_cell``) at its own load
with a shorter window, with the float8 control in the program's place in
the comparison: it reads the control's widest gap, which decides
``correct``, and the served tokens' widest gap over the same positions. One
JSON line per seed, then the largest program gap and the smallest control
gap. Exits 1 if the control ever came out ``correct``. The benchmark's own
runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), HERE]
    cache = os.path.join(ROOT, ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import jax
    import run
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    spec = run.cell_spec(args.workload)
    gaps, controls, control_correct = [], [], 0
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run_cell(spec, seed=seed, seconds=args.seconds, trace=False,
                           chips=spec["cell"]["chips"], control=True)
        c = out["checks"]
        gaps.append(out["program_gap"])
        controls.append(c["gap_sigma"]["value"])
        control_correct += bool(out["correct"])
        print(json.dumps({"seed": seed, "gap": gaps[-1],
                          "control_gap": controls[-1],
                          "tokens": c["sampled_tokens"]["value"],
                          "control_correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"]}), flush=True)
        gc.collect()
    print(json.dumps({"workload": args.workload, "seeds": len(gaps),
                      "largest_gap": max(gaps),
                      "smallest_control_gap": min(controls),
                      "control_correct": control_correct}), flush=True)
    return 1 if control_correct else 0


if __name__ == "__main__":
    sys.exit(main())
