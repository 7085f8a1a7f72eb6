"""Fault-tolerant training demo: the paper's full exception taxonomy in one run.

    PYTHONPATH=src python examples/fault_tolerant_training.py

Injects, in one training run: a NaN gradient (skip), a corrupted batch (skip),
a loss spike (optimizer reset + lr decay — paper use case 2 'hierarchical
escalation'), a repeated-NaN burst (LFLR restore, then rollback from the async
disk checkpoint — use cases 1 and 3), and a straggler (watchdog). Prints the
event log: one line per exception → decision → recovery action.
"""
import sys
import tempfile

sys.path.insert(0, "src")

from repro.configs import smoke_config  # noqa: E402
from repro.checkpoint import Checkpointer  # noqa: E402
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
    cfg = smoke_config("qwen3-1.7b")
    model, step_fn, state, pipe, _ = build_train_setup(
        cfg, batch_size=4, seq_len=32, total_steps=60)

    faults = FaultSchedule([
        FaultSpec(step=8, kind="nan_grad"),
        FaultSpec(step=14, kind="bad_data"),
        FaultSpec(step=20, kind="spike_loss"),
        FaultSpec(step=30, kind="nan_loss"),
        FaultSpec(step=31, kind="nan_loss"),
        FaultSpec(step=32, kind="nan_loss"),
        FaultSpec(step=33, kind="nan_loss"),
        FaultSpec(step=34, kind="nan_loss"),
        FaultSpec(step=45, kind="straggle", magnitude=0.6),
    ])

    with tempfile.TemporaryDirectory() as d:
        executor = ResilientExecutor(
            step_fn,
            policy=RecoveryPolicy(can_shrink=False, max_soft_retries=3,
                                  escalate_window=10),
            config=ExecutorConfig(good_state_interval=5,
                                  checkpoint_interval=10),
            checkpointer=Checkpointer(d),
            reset_opt_fn=make_reset_opt_fn(cfg))
        state, log = executor.run(state, iter(pipe), 55, faults=faults)
        executor.checkpointer.wait()

        print(f"\n=== event log ({cfg.name}, 55 steps) ===")
        for e in log.events:
            if e.kind == "ok":
                continue
            print(f"step {e.step:3d} | {e.kind:10s} | code={e.code:#010x} | "
                  f"action={e.action or '-':16s} | {e.detail}")
        n_ok = sum(1 for e in log.events if e.kind == "ok")
        print(f"\n{n_ok} clean steps; survived "
              f"{len(log.faults())} faults + 1 straggler; "
              f"final step={int(state['step'])}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
