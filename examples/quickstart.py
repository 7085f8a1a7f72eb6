"""Quickstart: resilient training end-to-end in ~a minute on CPU.

    PYTHONPATH=src python examples/quickstart.py [--steps 60] [--arch gemma3-1b]

Trains a reduced-config model on the deterministic synthetic stream through the
ResilientExecutor (detection + recovery always on), injecting one NaN-gradient
soft fault midway to show the propagate→skip path, and prints the loss curve.

Scale note: the same `make_train_step` is what the multi-pod dry-run lowers at
(16,16) / (2,16,16) mesh scale — see `repro.launch.dryrun`. For a ~100M-param
run use: --arch qwen3-1.7b --layers 8 --d-model 512 --steps 300 (slower).
"""
import argparse
import sys

sys.path.insert(0, "src")

import jax.numpy as jnp  # noqa: E402

from repro.configs import smoke_config  # noqa: E402
from repro.core import (  # noqa: E402
    ExecutorConfig,
    FaultSchedule,
    FaultSpec,
    ResilientExecutor,
)
from repro.core.recovery import RecoveryPolicy  # noqa: E402
from repro.launch.steps import make_reset_opt_fn  # noqa: E402
from repro.launch.train import build_train_setup  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--d-model", type=int, default=0)
    args = ap.parse_args()

    cfg = smoke_config(args.arch)
    if args.layers:
        cfg = cfg.replace(num_layers=args.layers)
    if args.d_model:
        cfg = cfg.replace(d_model=args.d_model)
    print(f"arch={cfg.name} (reduced: {cfg.num_layers}L d={cfg.d_model}) "
          f"batch={args.batch} seq={args.seq}")

    model, step_fn, state, pipe, _ = build_train_setup(
        cfg, batch_size=args.batch, seq_len=args.seq, total_steps=args.steps,
        lr=1e-3)
    executor = ResilientExecutor(
        step_fn, policy=RecoveryPolicy(can_shrink=False),
        config=ExecutorConfig(good_state_interval=10),
        reset_opt_fn=make_reset_opt_fn(cfg))

    faults = FaultSchedule([FaultSpec(step=args.steps // 2, kind="nan_grad")])

    probe_batch = next(iter(pipe))
    (_, m0) = executor.dispatch(state, probe_batch).wait()
    loss0 = float(m0["loss"])

    state, log = executor.run(state, iter(pipe), args.steps, faults=faults)
    ok = [e for e in log.events if e.kind == "ok"]
    fl = log.faults()
    print(f"\ncompleted {len(ok)} steps, {len(fl)} fault(s) handled:")
    for e in fl:
        print(f"  step {e.step}: code={e.code:#x} -> {e.action} ({e.detail})")
    print(f"final step counter: {int(state['step'])}")
    (_, metrics) = executor.dispatch(state, probe_batch).wait()
    loss1 = float(metrics["loss"])
    print(f"loss on probe batch: {loss0:.3f} -> {loss1:.3f} "
          f"(uniform ≈ {float(jnp.log(cfg.vocab_size)):.2f})")
    assert loss1 < loss0, "training did not descend"


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
