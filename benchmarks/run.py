"""Benchmark harness: one experiment per paper table/figure (+ beyond-paper).

Prints ``name,us_per_call,derived`` CSV:
  * Table I analogue  -> transport_latency (barrier, plain vs ULFM mode)
  * Figure 2 analogue -> error_propagation (black channel vs ULFM revoke)
  * beyond paper      -> detection_overhead (in-band device channel cost)
  * recovery costs    -> LFLR vs optimizer-reset vs rollback vs buddy store
  * roofline bounds   -> per-cell dominant-term bound from dry-run artifacts

Serving is measured on the chip by ``bench/run.py`` (``BENCHMARK.json``),
not here.

Flags:
  --only NAME     run a single section (e.g. --only recovery)
"""
from __future__ import annotations

import argparse
import sys


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    from . import (detection_overhead, error_propagation, recovery,
                   roofline_table, transport_latency)

    enable_compile_cache()

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default=None, metavar="NAME",
                    help="run a single section")
    args = ap.parse_args()

    print("name,us_per_call,derived")
    sections = [
        ("transport_latency", lambda: transport_latency.run(ranks=(2, 4, 8, 16))),
        ("error_propagation", lambda: error_propagation.run(ranks=(4, 8, 16, 32))),
        ("detection_overhead", detection_overhead.run),
        ("recovery", recovery.run),
        ("roofline", roofline_table.run),
    ]
    if args.only:
        sections = [(n, f) for n, f in sections if n == args.only]
        if not sections:
            raise SystemExit(f"unknown section: {args.only}")
    failed = []
    for name, fn in sections:
        try:
            for row_name, derived, us in fn():
                print(f"{row_name},{us:.2f},{derived}")
        except Exception as e:  # noqa: BLE001 — report every section, then fail
            failed.append(name)
            print(f"{name}_FAILED,0,{type(e).__name__}:{e}", file=sys.stderr)
            print(f"{name}_FAILED,0,0")
    if failed:
        raise SystemExit(f"failed sections: {', '.join(failed)}")


if __name__ == "__main__":
    main()
