#!/usr/bin/env python
"""Bench-regression tripwire over the BENCH_serving.json run history.

Compares the latest recorded serving run against the BEST of the last three
earlier runs for each engine × scenario cell — the tensor-parallel
``window8_tp2`` cells included, whenever the run carried them — plus the
paged-capacity, tracer-overhead and elastic-group cells, when carried, and fails — exit 1 — if tokens/s dropped
by more than the threshold (default 15%). Comparing against the best-of-3 baseline (not just
the single previous run) means one noisy-but-green draw cannot ratchet the
baseline down: a slow-but-passing run N doesn't lower the bar run N+1 must
clear, because runs N-1 and N-2 still anchor it. With fewer than two runs in
the history the gate skips cleanly (exit 0): a fresh clone or a brand-new
benchmark has nothing to regress against.

This reads the *committed* history only — it runs in milliseconds, so it sits
in ``scripts/check.sh`` and CI as a tripwire: a PR that appends a regressed
run (``python -m benchmarks.run --json``, which itself refuses dirty-tree
runs) fails the gate before review ever sees it.

Usage:
  python scripts/bench_gate.py [--history BENCH_serving.json]
                               [--max-regress 0.15]
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def _cells(record: dict):
    """Flatten one run record into {cell_name: tokens_per_s}."""
    out = {}
    for engine, scens in (record.get("engines") or {}).items():
        if not isinstance(scens, dict):
            continue
        for scen, cell in scens.items():
            if isinstance(cell, dict) and isinstance(
                    cell.get("tokens_per_s"), (int, float)):
                out[f"{engine}/{scen}"] = float(cell["tokens_per_s"])
    paged = record.get("paged")
    if isinstance(paged, dict):
        for side in ("contiguous", "paged"):
            cell = paged.get(side)
            if isinstance(cell, dict) and isinstance(
                    cell.get("tokens_per_s"), (int, float)):
                out[f"paged_capacity/{side}"] = float(cell["tokens_per_s"])
        if isinstance(paged.get("slot_capacity_ratio"), (int, float)):
            out["paged_capacity/slot_ratio"] = float(
                paged["slot_capacity_ratio"])
    tracer = record.get("tracer")
    if isinstance(tracer, dict):
        for side in ("noop", "enabled"):
            cell = tracer.get(side)
            if isinstance(cell, dict) and isinstance(
                    cell.get("tokens_per_s"), (int, float)):
                out[f"tracer/{side}"] = float(cell["tokens_per_s"])
    elastic = record.get("elastic")
    if isinstance(elastic, dict):
        # steady + durable ride the tripwire; the join ratio is asserted
        # inside bench_elastic itself (its best-of reading quantizes on
        # window-retire bursts, too noisy for a 15% history gate)
        for side in ("steady", "durable"):
            cell = elastic.get(side)
            if isinstance(cell, dict) and isinstance(
                    cell.get("tokens_per_s"), (int, float)):
                out[f"elastic/{side}"] = float(cell["tokens_per_s"])
    return out


def gate(history_path: str, max_regress: float) -> int:
    if not os.path.exists(history_path):
        print(f"bench gate: no history at {history_path} — skipping")
        return 0
    try:
        with open(history_path) as f:
            data = json.load(f)
    except json.JSONDecodeError as e:
        print(f"bench gate: {history_path} is not valid JSON ({e})")
        return 1
    runs = data.get("runs") if isinstance(data, dict) else None
    if not isinstance(runs, list) or len(runs) < 2:
        n = len(runs) if isinstance(runs, list) else 0
        print(f"bench gate: history has {n} run(s), need 2 — skipping")
        return 0
    latest = runs[-1]
    latest_cells = _cells(latest)
    if not latest_cells:
        print("bench gate: latest run carries no comparable cells — skipping")
        return 0
    # baseline = the 3 most recent earlier runs sharing at least one cell
    # with the latest; each cell is judged against its best value among them
    baseline_runs = []
    for cand in reversed(runs[:-1]):
        if set(_cells(cand)) & set(latest_cells):
            baseline_runs.append(cand)
        if len(baseline_runs) == 3:
            break
    if not baseline_runs:
        print("bench gate: no earlier run shares a cell with the latest — "
              "skipping")
        return 0
    baseline_cells: dict[str, float] = {}
    for cand in baseline_runs:
        for name, v in _cells(cand).items():
            baseline_cells[name] = max(baseline_cells.get(name, v), v)
    failures = []
    compared = 0
    for name in sorted(set(latest_cells) & set(baseline_cells)):
        old, new = baseline_cells[name], latest_cells[name]
        if old <= 0:
            continue
        compared += 1
        change = (new - old) / old
        status = "FAIL" if change < -max_regress else "ok"
        print(f"bench gate: {name:40s} {old:10.1f} -> {new:10.1f} "
              f"({change:+6.1%}) {status}")
        if change < -max_regress:
            failures.append((name, old, new, change))
    revs = ",".join(r.get("git_rev", "?") for r in baseline_runs)
    print(f"bench gate: compared {compared} cell(s), "
          f"{latest.get('git_rev', '?')} vs best of [{revs}]")
    if failures:
        for name, old, new, change in failures:
            print(f"bench gate: REGRESSION {name}: {old:.1f} -> {new:.1f} "
                  f"tok/s ({change:.1%} < -{max_regress:.0%})",
                  file=sys.stderr)
        return 1
    print("bench gate: OK")
    return 0


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--history", default="BENCH_serving.json",
                    help="run-history file (default: BENCH_serving.json)")
    ap.add_argument("--max-regress", type=float, default=0.15,
                    help="max fractional tokens/s drop (default 0.15)")
    args = ap.parse_args()
    raise SystemExit(gate(args.history, args.max_regress))


if __name__ == "__main__":
    main()
