import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.
#   This module is the ONLY place the 512 placeholder devices are forced.

"""Multi-pod dry-run: lower + compile every (arch × input-shape) cell on the
production meshes and record memory/cost/collective analysis.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun                 # all cells, both meshes
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-1b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --multipod-only --out artifacts/dryrun

Success of ``lower().compile()`` for every cell on the 16×16 (single-pod) and
2×16×16 (multi-pod) meshes is deliverable (e); the JSON artifacts feed §Roofline.
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from ..configs import SHAPES, ARCHS, cell_skip_reason, get_config
from ..roofline.analysis import RooflineTerms, model_flops_for, peaks_for
from ..roofline.hlo import estimate_hbm_bytes, op_histogram, parse_collectives
from .mesh import make_production_mesh
from .steps import BASELINE, PerfOptions, input_specs, make_step_for

# the chip the production meshes are planned for (``launch/mesh.py``)
TARGET_DEVICE_KIND = "TPU v5 lite"


def _compile_variant(cfg, shape, mesh, impl, *, inner_unroll: bool = False,
                     perf: PerfOptions = BASELINE):
    """Compile one config variant; return (compiled, cost, coll, hlo)."""
    from jax.sharding import PartitionSpec as P

    from ..models import attention as attention_mod
    from ..models import moe as moe_mod
    from ..models import transformer as transformer_mod

    step = make_step_for(cfg, shape, impl=impl, perf=perf)
    args, shardings = input_specs(cfg, shape, mesh, perf=perf)
    donate = (0,) if shape.kind == "train" else (
        (1,) if shape.kind == "decode" else ())
    from . import steps as steps_mod

    prev = attention_mod.INNER_UNROLL
    prev_spec = transformer_mod.ACTIVATION_SPEC
    prev_espec = moe_mod.EXPERT_SPEC
    prev_mb = steps_mod.MB_UNROLL
    attention_mod.INNER_UNROLL = inner_unroll
    steps_mod.MB_UNROLL = inner_unroll
    if perf.seq_shard:
        dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
        transformer_mod.ACTIVATION_SPEC = P(dp, "model", None)
    if perf.ep_constraint:
        moe_mod.EXPERT_SPEC = P(None, "model", None, None)
    try:
        with mesh:
            jitted = jax.jit(step, in_shardings=shardings,
                             donate_argnums=donate)
            lowered = jitted.lower(*args)
            compiled = lowered.compile()
    finally:
        attention_mod.INNER_UNROLL = prev
        transformer_mod.ACTIVATION_SPEC = prev_spec
        moe_mod.EXPERT_SPEC = prev_espec
        steps_mod.MB_UNROLL = prev_mb
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)
    return compiled, cost, coll, hlo


def _corrected_costs(cfg, shape, mesh, impl, full_hlo, perf=BASELINE):
    """Exact per-step costs despite two CPU-backend artifacts:

    1. ``cost_analysis`` counts a ``lax.scan``/while body ONCE regardless of
       trip count (verified empirically). FLOPs are linear in depth, so two
       *unrolled* shallow variants (1 and 2 periods + remainder, inner scans
       unrolled) give an exact per-period delta:
       flops = v1 + (v2 − v1) × (num_periods − 1).
    2. ``bytes accessed`` sums ops *inside* fusion computations (VMEM/register
       traffic on a real TPU). HBM bytes and collective bytes are instead
       measured on the FULL compiled module with the fusion-boundary,
       while-trip-count-aware analyzer — no extrapolation (which CSE across
       unrolled microbatches would otherwise distort).

    Returns (flops, hbm_bytes, coll_bytes, coll_by_kind).
    """
    np_ = cfg.num_periods
    rem = len(cfg.remainder_layers)
    cfg1 = cfg.replace(num_layers=cfg.period + rem, scan_layers=False)
    cfg2 = cfg.replace(num_layers=2 * cfg.period + rem, scan_layers=False)
    _, c1, _, _ = _compile_variant(cfg1, shape, mesh, impl, inner_unroll=True,
                                   perf=perf)
    _, c2, _, _ = _compile_variant(cfg2, shape, mesh, impl, inner_unroll=True,
                                   perf=perf)
    f1, f2 = float(c1.get("flops", 0)), float(c2.get("flops", 0))
    flops = f1 + (f2 - f1) * (np_ - 1)
    est = estimate_hbm_bytes(full_hlo)
    return (flops, float(est["total_bytes"]), float(est["collective_total"]),
            est["collective_bytes_by_kind"])


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             impl: str = "auto", keep_hlo: bool = False,
             config_override=None, perf: PerfOptions = BASELINE) -> dict:
    """Lower + compile one cell; returns the artifact dict."""
    cfg = config_override or get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    rec: dict = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
        "ok": False,
    }
    t0 = time.monotonic()
    try:
        compiled, cost, coll, hlo = _compile_variant(cfg, shape, mesh, impl,
                                                      perf=perf)
        t_compile = time.monotonic() - t0
        mem = compiled.memory_analysis()
        flops, bytes_, coll_bytes, coll_by_kind = _corrected_costs(
            cfg, shape, mesh, impl, hlo, perf=perf)
        terms = RooflineTerms(
            chips=chips,
            hlo_flops_per_device=flops,
            hlo_bytes_per_device=bytes_,
            collective_bytes_per_device=coll_bytes,
            peaks=peaks_for(TARGET_DEVICE_KIND),
            model_flops=model_flops_for(cfg, shape),
        )
        rec.update(
            ok=True,
            compile_s=round(t_compile, 2),
            memory={
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "peak_live_bytes": (mem.argument_size_in_bytes
                                    + mem.output_size_in_bytes
                                    + mem.temp_size_in_bytes
                                    - mem.alias_size_in_bytes),
            },
            cost_raw={k: v for k, v in cost.items()
                      if k in ("flops", "bytes accessed", "transcendentals")},
            collectives_raw=coll.to_dict(),
            collectives_by_kind_corrected=coll_by_kind,
            roofline=terms.to_dict(),
            hlo_ops={k: v for k, v in list(op_histogram(hlo).items())[:20]},
        )
        if keep_hlo:
            rec["hlo_text"] = hlo
    except Exception as e:  # noqa: BLE001 - a failing cell is a reported bug
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.monotonic() - t0, 2)
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch (default: all)")
    ap.add_argument("--shape", default=None, help="one shape (default: all)")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--multipod-only", action="store_true")
    ap.add_argument("--singlepod-only", action="store_true")
    ap.add_argument("--impl", default="auto")
    ap.add_argument("--include-skipped", action="store_true",
                    help="attempt cells that are documented skips")
    ap.add_argument("--perf", default="",
                    help="perf levers, e.g. 'mb=8,ce=2048,sp=1,cacheseq=1'")
    args = ap.parse_args()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = []
    if not args.multipod_only:
        meshes.append(False)
    if not args.singlepod_only:
        meshes.append(True)

    failures = 0
    for arch in archs:
        for shape in shapes:
            reason = cell_skip_reason(arch, shape)
            if reason and not args.include_skipped:
                for mp in meshes:
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "ok": True, "skipped": reason}
                    _write(out_dir, rec)
                print(f"SKIP  {arch:24s} {shape:12s} ({reason})", flush=True)
                continue
            for mp in meshes:
                mesh_name = "2x16x16" if mp else "16x16"
                rec = run_cell(arch, shape, multi_pod=mp, impl=args.impl,
                               perf=PerfOptions.parse(args.perf))
                _write(out_dir, rec)
                if rec["ok"]:
                    r = rec["roofline"]
                    print(f"OK    {arch:24s} {shape:12s} {mesh_name:8s} "
                          f"compile={rec['compile_s']:7.1f}s "
                          f"dom={r['dominant']:10s} "
                          f"frac={r['roofline_fraction']:.3f} "
                          f"mem/dev={rec['memory']['peak_live_bytes']/2**30:.2f}GiB",
                          flush=True)
                else:
                    failures += 1
                    print(f"FAIL  {arch:24s} {shape:12s} {mesh_name:8s} "
                          f"{rec['error']}", flush=True)
    print(f"\ndone; failures={failures}")
    return 1 if failures else 0


def _write(out_dir: Path, rec: dict) -> None:
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json".replace("/", "_")
    (out_dir / name).write_text(json.dumps(rec, indent=1))


if __name__ == "__main__":
    raise SystemExit(main())
