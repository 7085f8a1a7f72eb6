"""Elastic shrink demo: hard fault → ULFM shrink → LFLR restore → keep training.

    PYTHONPATH=src python examples/elastic_shrink.py

Runs the paper's full multi-controller choreography on the simulated cluster:
6 data-parallel hosts train a shared model through Comm/Future (every gradient
all-reduce is a Future whose wait() can raise the paper's exceptions). At step
10, host 2 dies (simulated node loss). The ULFM failure detector turns the
survivors' waits into CommCorruptedError; they agree, shrink 6→5, restore from
the buddy store, re-partition the batch stream, and finish all 30 steps.
"""
import sys

sys.path.insert(0, "src")

from repro.core.faults import FaultSchedule, FaultSpec  # noqa: E402
from repro.launch.elastic import elastic_train  # noqa: E402


def main():
    faults = FaultSchedule([
        FaultSpec(step=10, kind="kill", rank=2),
        FaultSpec(step=20, kind="nan_grad", rank=4),
    ])
    print("elastic training: 6 hosts, kill rank 2 @ step 10, "
          "NaN-grad on rank 4 @ step 20\n")
    results = elastic_train(6, steps=30, lr=0.2, faults=faults)
    for r in results:
        if r.killed:
            print(f"rank {r.rank}: DIED (hard fault)")
            continue
        if r.exception is not None:
            print(f"rank {r.rank}: EXCEPTION {r.exception!r}")
            continue
        v = r.value
        evs = "; ".join(f"{k}@{s}" + (f"→world={w}" if k == "shrink" else
                                      f" from ranks {w}")
                        for k, s, w in v.events)
        print(f"rank {r.rank}: steps={v.steps_done} "
              f"world {v.world_sizes[0]}→{v.world_sizes[-1]} "
              f"loss={v.final_loss:.2e} [{evs}]")
    survivors = [r.value for r in results if not r.killed and r.exception is None]
    assert all(v.world_sizes[-1] == 5 for v in survivors)
    print("\nall survivors finished on the shrunk (5-host) communicator; "
          "final losses < 5e-2 show training recovered.")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
