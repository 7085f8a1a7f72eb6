"""Coverage-guided fault-injection fuzzer for the serving stack.

Drives the real engines (the overlapped window replica and its paged /
tensor-parallel / speculative variants, the ULFM ServeGroup, and the
multihost real-process fault domain) end to end with seeded, fully
reproducible fault trajectories; measures coverage over the derived
(error code × recovery action × engine) matrix; judges every run against
the stack's own contracts (bit-exactness, zero drops, ledger invariants,
trace causality); and minimizes + promotes every counterexample into the
replayable regression corpus under ``tests/fuzz_corpus/``.

See DESIGN.md §3.6 for the architecture and ``scripts/fuzz.py`` for the CLI.
"""
from .campaign import CampaignReport, FuzzCampaign, load_entry, minimize, write_entry
from .coverage import Cell, CoverageDB, action_ladder, reachable_cells
from .mutator import FaultMutator
from .runner import RunResult, run_trajectory
from .trajectory import (
    ENGINES,
    GROUP_ENGINE,
    MULTIHOST_ENGINE,
    SINGLE_ENGINES,
    Op,
    Trajectory,
)

__all__ = [
    "CampaignReport", "FuzzCampaign", "load_entry", "minimize", "write_entry",
    "Cell", "CoverageDB", "action_ladder", "reachable_cells",
    "FaultMutator", "RunResult", "run_trajectory",
    "ENGINES", "GROUP_ENGINE", "MULTIHOST_ENGINE", "SINGLE_ENGINES", "Op",
    "Trajectory",
]
