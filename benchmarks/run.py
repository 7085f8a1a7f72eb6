"""Benchmark harness: one experiment per paper table/figure (+ beyond-paper).

Prints ``name,us_per_call,derived`` CSV:
  * Table I analogue  -> transport_latency (barrier, plain vs ULFM mode)
  * Figure 2 analogue -> error_propagation (black channel vs ULFM revoke)
  * beyond paper      -> detection_overhead (in-band device channel cost)
  * recovery costs    -> LFLR vs optimizer-reset vs rollback vs buddy store
  * roofline bounds   -> per-cell dominant-term bound from dry-run artifacts
  * serving           -> repro.serve steady-state tokens/s + latency
                         percentiles, clean vs injected-fault traffic, for
                         the per-token and decode-window engines

Flags:
  --json [PATH]   also append the serving benchmark to the run history in
                  PATH (default: BENCH_serving.json) as machine-readable
                  JSON — ``{"runs": [...]}``, one record per invocation with
                  the git rev + config, so the perf trajectory is tracked
                  across PRs instead of overwritten. The record is validated
                  against the serving schema before the file is touched, and
                  a dirty working tree is refused without ``--allow-dirty``
                  (a run that doesn't correspond to a commit would poison
                  the bench-regression gate's history).
  --allow-dirty   record a run even with uncommitted changes in the tree
  --only NAME     run a single section (e.g. --only serving)
"""
from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _git_rev() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except Exception:  # noqa: BLE001 - benchmarks must not die on metadata
        return "unknown"


def _dirty_paths(exclude: str) -> list[str]:
    """Uncommitted changes (`git status --porcelain`), minus the history file
    itself — appending run N+1 after run N inevitably dirties that one file."""
    try:
        out = subprocess.run(
            ["git", "status", "--porcelain"], cwd=_REPO,
            capture_output=True, text=True, timeout=10).stdout
    except Exception:  # noqa: BLE001 - no git ⇒ nothing to refuse on
        return []
    excl = os.path.relpath(os.path.abspath(exclude), _REPO)
    paths = []
    for line in out.splitlines():
        p = line[3:].split(" -> ")[-1].strip().strip('"')
        if p and p != excl:
            paths.append(p)
    return paths


# Required numeric keys per engine × scenario cell — the contract the
# bench-regression gate (scripts/bench_gate.py) depends on.
_CELL_KEYS = ("tokens_per_s", "latency_p50_s", "latency_p99_s",
              "ttft_p50_s", "ttft_p99_s", "wall_s", "timed_tokens")
_SCENARIOS = ("steady", "faulted")


def validate_serving_record(record: dict) -> list[str]:
    """Schema check for one serving run record; returns the violations
    (empty = valid). Extra keys are always allowed — the schema only pins
    what downstream tooling reads."""
    errs: list[str] = []
    if record.get("benchmark") != "serving":
        errs.append(f"benchmark must be 'serving', got "
                    f"{record.get('benchmark')!r}")
    if not isinstance(record.get("config"), dict):
        errs.append("config must be a dict")
    engines = record.get("engines")
    if not isinstance(engines, dict) or not engines:
        errs.append("engines must be a non-empty dict")
        return errs
    for engine, cells in engines.items():
        if not isinstance(cells, dict):
            errs.append(f"engines[{engine!r}] must be a dict")
            continue
        for scen in _SCENARIOS:
            cell = cells.get(scen)
            if not isinstance(cell, dict):
                errs.append(f"engines[{engine!r}] missing scenario {scen!r}")
                continue
            for key in _CELL_KEYS:
                v = cell.get(key)
                if not isinstance(v, (int, float)) or isinstance(v, bool) \
                        or not math.isfinite(v) or v < 0:
                    errs.append(f"engines[{engine!r}][{scen!r}][{key!r}] "
                                f"must be a finite number >= 0, got {v!r}")
    paged = record.get("paged")
    if paged is not None:
        for side in ("contiguous", "paged"):
            cell = paged.get(side) if isinstance(paged, dict) else None
            if not isinstance(cell, dict) or not isinstance(
                    cell.get("tokens_per_s"), (int, float)):
                errs.append(f"paged[{side!r}] must carry tokens_per_s")
    return errs


def _append_history(path: str, record: dict, *,
                    allow_dirty: bool = False) -> None:
    """Append ``record`` to the run history at ``path``.

    The file is ``{"benchmark": "serving", "runs": [...]}``; a pre-history
    file (one bare record, the PR-2 format) is migrated by becoming the
    first entry of the list. The record is schema-validated and the working
    tree must be clean (modulo the history file itself) unless
    ``allow_dirty`` — both guards keep the bench-gate history trustworthy.
    """
    errs = validate_serving_record(record)
    if errs:
        raise ValueError(
            "refusing to record a malformed serving run:\n  "
            + "\n  ".join(errs))
    dirty = _dirty_paths(exclude=path)
    if dirty and not allow_dirty:
        raise SystemExit(
            f"refusing to record a bench run from a dirty working tree "
            f"({len(dirty)} changed paths, e.g. {dirty[:3]}): the history "
            "maps runs to commits for the regression gate — commit first, "
            "or pass --allow-dirty to record anyway")
    record = dict(record)
    record["git_rev"] = _git_rev()
    record["date"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    history: dict = {"benchmark": record.get("benchmark", "serving"),
                     "runs": []}
    try:
        with open(path) as f:
            prev = json.load(f)
        if isinstance(prev, dict) and isinstance(prev.get("runs"), list):
            history["runs"] = prev["runs"]
        elif isinstance(prev, dict) and prev:
            history["runs"] = [prev]     # migrate the pre-history format
    except (FileNotFoundError, json.JSONDecodeError):
        pass
    history["runs"].append(record)
    with open(path, "w") as f:
        json.dump(history, f, indent=2, sort_keys=True)
        f.write("\n")


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    from . import (detection_overhead, error_propagation, recovery,
                   roofline_table, serving, transport_latency)

    enable_compile_cache()

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", nargs="?", const="BENCH_serving.json",
                    default=None, metavar="PATH",
                    help="write serving results to PATH as JSON")
    ap.add_argument("--allow-dirty", action="store_true",
                    help="record a run even with uncommitted changes")
    ap.add_argument("--only", default=None, metavar="NAME",
                    help="run a single section")
    args = ap.parse_args()

    if args.json and not args.allow_dirty:
        # fail BEFORE the multi-minute bench run, not after it
        dirty = _dirty_paths(exclude=args.json)
        if dirty:
            raise SystemExit(
                f"refusing to record a bench run from a dirty working tree "
                f"({len(dirty)} changed paths, e.g. {dirty[:3]}): commit "
                "first, or pass --allow-dirty to record anyway")

    serving_record = {}

    def serving_rows():
        rows, record = serving.bench_all()
        serving_record.update(record)
        return rows

    print("name,us_per_call,derived")
    sections = [
        ("transport_latency", lambda: transport_latency.run(ranks=(2, 4, 8, 16))),
        ("error_propagation", lambda: error_propagation.run(ranks=(4, 8, 16, 32))),
        ("detection_overhead", detection_overhead.run),
        ("recovery", recovery.run),
        ("roofline", roofline_table.run),
        ("serving", serving_rows),
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
    if args.json and serving_record:
        _append_history(args.json, serving_record,
                        allow_dirty=args.allow_dirty)
        print(f"appended run to {args.json}", file=sys.stderr)
    if failed:
        raise SystemExit(f"failed sections: {', '.join(failed)}")


if __name__ == "__main__":
    main()
