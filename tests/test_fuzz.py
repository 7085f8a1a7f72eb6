"""Tests for the coverage-guided fault-injection fuzzer (the PR-7 tentpole).

Pure-unit halves (trajectory model, coverage DB, derived universe, mutator
determinism, minimizer logic) run without touching JAX; the integration
half drives real trajectories through the runner and checks the oracles,
coverage extraction and bit-for-bit replay on the live serving stack.
"""
import json

import numpy as np
import pytest

from repro.core.errors import ErrorCode
from repro.core.recovery import RecoveryPolicy
from repro.fuzz import (
    CoverageDB,
    FaultMutator,
    FuzzCampaign,
    Op,
    Trajectory,
    action_ladder,
    load_entry,
    minimize,
    reachable_cells,
    run_trajectory,
    write_entry,
)
from repro.fuzz.coverage import PAGED_ENGINES
from repro.fuzz.trajectory import (
    ENGINES,
    GROUP_ENGINE,
    MULTIHOST_ENGINE,
    SINGLE_ENGINES,
)

NAN = ErrorCode.NONFINITE_LOSS


# ------------------------------------------------------------- trajectory model
class TestTrajectory:
    def test_json_round_trip(self):
        t = Trajectory(seed=9, engine="overlap_paged", n_requests=4,
                       prompt_len=5, max_new=8, max_request_retries=1,
                       ops=[Op("word", cycle=2, slot=1, step=3,
                               code=int(NAN)),
                            Op("page_table", cycle=4, slot=0)],
                       note="test")
        assert Trajectory.loads(t.dumps()) == t
        assert Trajectory.from_json(json.loads(t.dumps())) == t

    def test_prompts_are_derived_not_stored(self):
        t = Trajectory(seed=0, engine="overlap", n_requests=2, prompt_len=3)
        assert t.prompts() == [(5, 6, 7), (6, 7, 8)]
        assert "prompt" not in json.dumps(t.to_json())[:-1].replace(
            '"prompt_len"', "")

    def test_kill_only_on_group_engine(self):
        with pytest.raises(ValueError, match="kill"):
            Trajectory(seed=0, engine="overlap",
                       ops=[Op("kill", cycle=1, slot=0)])
        with pytest.raises(ValueError, match="word"):
            Trajectory(seed=0, engine=GROUP_ENGINE,
                       ops=[Op("word", cycle=1, code=int(NAN))])

    def test_bad_ops_rejected(self):
        with pytest.raises(ValueError, match="nonzero"):
            Op("word", cycle=1, slot=0, code=0)
        with pytest.raises(ValueError, match="unknown op"):
            Op("wrod", cycle=1)
        with pytest.raises(ValueError, match="unknown engine"):
            Trajectory(seed=0, engine="stepwse")


# ------------------------------------------------------------------- coverage
class TestCoverage:
    def test_universe_is_derived_from_the_policy(self):
        cells = reachable_cells()
        assert len(cells) > 100
        # every soft code ladders skip→restore→rollback on every engine
        for engine in SINGLE_ENGINES:
            assert ("NONFINITE_LOSS", "skip_batch", engine) in cells
            assert ("NONFINITE_LOSS", "rollback", engine) in cells
        # engine-specific lanes
        for engine in SINGLE_ENGINES:
            assert (("PAGE_FAULT", "page_reclaim", engine) in cells) == (
                engine in PAGED_ENGINES)
        assert ("COMM_CORRUPTED", "shrink", GROUP_ENGINE) in cells
        assert ("RANK_FAILED", "reroute", GROUP_ENGINE) in cells
        # multihost (real OS process) lanes: heartbeat-detected eviction and
        # the SIGSTOP suspected-then-cleared false-positive guard
        assert ("RANK_FAILED", "evict", MULTIHOST_ENGINE) in cells
        assert ("STRAGGLER", "resume", MULTIHOST_ENGINE) in cells
        # hard/attribution-only lanes never appear as injectable cells
        assert not any(c[0] == "DRAFT_REJECT" for c in cells)
        assert not any(c[0] == "RANK_FAILED"
                       and c[2] not in (GROUP_ENGINE, MULTIHOST_ENGINE)
                       for c in cells)

    def test_action_ladder_replays_the_real_policy(self):
        ladder = action_ladder(NAN, depth=5)
        assert ladder == ["skip_batch", "restore_good", "restore_good",
                          "rollback", "rollback"]
        assert action_ladder(ErrorCode.DIVERGENCE)[0] == "reset_optimizer"

    def test_db_records_and_persists(self, tmp_path):
        path = str(tmp_path / "cov.json")
        db = CoverageDB(path)
        cell = ("NONFINITE_LOSS", "skip_batch", "overlap")
        assert db.record([cell]) == [cell]          # new
        assert db.record([cell]) == []              # already covered
        assert db.covered(cell)
        universe = [cell, ("USER", "skip_batch", "overlap")]
        assert db.fraction(universe) == 0.5
        assert db.uncovered(universe) == [("USER", "skip_batch", "overlap")]
        db.save()
        again = CoverageDB(path)
        assert again.cells() == {cell}
        rep = again.report(universe)
        assert rep["covered"] == 1 and rep["universe"] == 2

    def test_report_flags_cells_outside_the_universe(self):
        db = CoverageDB()
        db.record([("USER", "weird_action", "overlap")])
        rep = db.report([("USER", "skip_batch", "overlap")])
        assert rep["extra"] == ["USER|weird_action|overlap"]


# -------------------------------------------------------------------- mutator
class TestMutator:
    def test_proposals_replay_from_seed_and_index(self):
        a = FaultMutator(3, CoverageDB()).propose(7)
        b = FaultMutator(3, CoverageDB()).propose(7)
        assert a == b
        assert FaultMutator(4, CoverageDB()).propose(7) != a

    def test_targeted_mode_attacks_uncovered_cells(self):
        db = CoverageDB()
        mut = FaultMutator(0, db, engines=("overlap",), targeted_bias=1.0)
        traj = mut.propose(0)
        assert traj.engine == "overlap"
        assert traj.note.startswith("targeted:")
        assert traj.ops                      # ladder prefix scheduled
        # covering the whole universe flips the mutator to random/mutate mode
        db.record(mut.universe)
        assert not db.uncovered(mut.universe)
        assert mut.propose(1).note.startswith(("random", "mutant"))

    def test_group_trajectories_carry_exactly_one_kill(self):
        mut = FaultMutator(1, CoverageDB(), engines=(GROUP_ENGINE,))
        seen = set()
        for i in range(12):
            traj = mut.propose(i)
            assert traj.engine == GROUP_ENGINE
            kinds = [op.op for op in traj.ops]
            assert kinds.count("kill") == 1
            assert kinds.count("restart") <= 1
            assert kinds.count("rejoin") <= 1
            assert set(kinds) <= {"kill", "restart", "rejoin"}
            # a restart lands after the kill: the crash must catch the
            # shrunken fleet mid-replay of the re-routed backlog
            kill = next(o for o in traj.ops if o.op == "kill")
            for op in traj.ops:
                if op.op == "restart":
                    assert op.cycle >= kill.cycle + 3
            seen.update(kinds)
        # across a dozen seeded proposals every elastic lane gets exercised
        assert seen == {"kill", "restart", "rejoin"}

    def test_mutants_stay_valid(self):
        mut = FaultMutator(2, CoverageDB())
        rng = np.random.default_rng(0)
        parent = mut.propose(0)
        for _ in range(20):
            parent = mut.mutate(parent, rng)   # __post_init__ validates
        assert parent.engine == mut.propose(0).engine

    def test_unknown_engine_rejected(self):
        with pytest.raises(ValueError, match="unknown engines"):
            FaultMutator(0, CoverageDB(), engines=("warp",))


# ------------------------------------------------------------------ minimizer
class TestMinimizer:
    def test_greedy_drop_keeps_only_the_culprit(self, monkeypatch):
        culprit = Op("word", cycle=5, slot=1, step=2, code=int(NAN))
        noise = [Op("word", cycle=c, slot=0, step=0,
                    code=int(ErrorCode.USER)) for c in (1, 2, 3)]

        class FakeResult:
            def __init__(self, failed):
                self.failed = failed
                self.violations = ["boom"] if failed else []

        def fake_run(traj):
            return FakeResult(culprit in traj.ops)

        import repro.fuzz.campaign as campaign
        monkeypatch.setattr(campaign, "run_trajectory", fake_run)
        traj = Trajectory(seed=0, engine="overlap", n_requests=4,
                          prompt_len=7, max_new=12,
                          ops=noise[:2] + [culprit] + noise[2:])
        small, res = minimize(traj)
        assert small.ops == (culprit,)
        assert res.failed
        # load shrinking kicked in too
        assert small.n_requests == 2
        assert small.max_new == 5

    def test_passing_trajectory_comes_back_unchanged(self, monkeypatch):
        import repro.fuzz.campaign as campaign

        class Passing:
            failed = False
            violations = []

        monkeypatch.setattr(campaign, "run_trajectory",
                            lambda t: Passing())
        traj = Trajectory(seed=0, engine="overlap",
                          ops=[Op("poison", cycle=1)])
        small, res = minimize(traj)
        assert small == traj and not res.failed


# --------------------------------------------------------------------- corpus
class TestCorpusIO:
    def test_entry_round_trip(self, tmp_path):
        traj = Trajectory(seed=1, engine="spec", n_requests=2,
                          ops=[Op("word", cycle=2, code=int(NAN))])
        path = write_entry(str(tmp_path), "e1", traj, status="seed",
                           digest="abcd", cells=[("NONFINITE_LOSS",
                                                  "skip_batch", "spec")])
        entry = load_entry(path)
        assert entry["trajectory"] == traj
        assert entry["status"] == "seed"
        assert entry["digest"] == "abcd"
        assert entry["cells"] == ["NONFINITE_LOSS|skip_batch|spec"]


# -------------------------------------------------- integration (real stack)
class TestRunnerIntegration:
    def test_clean_run_passes_every_oracle(self):
        res = run_trajectory(Trajectory(seed=0, engine="overlap",
                                        n_requests=2, prompt_len=3,
                                        max_new=5))
        assert res.violations == []
        assert res.cells == set()

    def test_injected_ladder_covers_cells_and_stays_bit_exact(self):
        traj = Trajectory(
            seed=1, engine="overlap", n_requests=4, prompt_len=5, max_new=12,
            max_request_retries=6,
            ops=[Op("word", cycle=2 + k, slot=k % 2, step=1, code=int(NAN))
                 for k in range(4)])
        res = run_trajectory(traj)
        assert res.violations == []      # bit-exact + no drops despite 4 faults
        assert {("NONFINITE_LOSS", "skip_batch", "overlap"),
                ("NONFINITE_LOSS", "restore_good", "overlap"),
                ("NONFINITE_LOSS", "rollback", "overlap")} <= res.cells

    def test_replay_is_bit_for_bit(self):
        traj = Trajectory(seed=2, engine="overlap", n_requests=3,
                          prompt_len=5, max_new=8,
                          ops=[Op("word", cycle=2, slot=0, step=1,
                                  code=int(NAN)),
                               Op("preempt", cycle=3, slot=1)])
        a, b = run_trajectory(traj), run_trajectory(traj)
        assert a.digest() == b.digest()
        assert a.violations == b.violations == []
        assert a.cells == b.cells

    def test_non_injectable_word_is_rejected_by_the_replica(self):
        # the injector hook itself enforces the injectable mask: a trajectory
        # cannot even express this (Op validates at run), so go through a
        # hand-rolled injector to pin the replica-side guard
        from repro.fuzz.runner import get_kit
        from repro.serve.config import EngineConfig
        from repro.serve.queue import Request
        from repro.serve.replica import Replica

        kit = get_kit("overlap")
        rep = Replica(kit.cfg, params=kit.params,
                      config=EngineConfig(num_slots=2, max_len=32, window=4),
                      programs=kit.programs,
                      fault_injector=lambda i, shape: np.full(
                          shape, int(ErrorCode.DRAFT_REJECT), np.uint32))
        assert rep.submit(Request(id=0, prompt=(5, 6, 7),
                                  max_new_tokens=4)) is None
        with pytest.raises(ValueError, match="non-injectable"):
            rep.run()

    def test_campaign_smoke_covers_and_replays(self, tmp_path):
        db = CoverageDB(str(tmp_path / "cov.json"))
        camp = FuzzCampaign(seed=0, db=db, corpus_dir=str(tmp_path / "c"),
                            engines=("overlap",))
        rep = camp.run(3)
        assert rep.ran == 3
        assert not [c for c in rep.counterexamples if not c.get("flaky")]
        assert rep.coverage["covered"] > 0
        paths = camp.promote_seeds(2)
        for p in paths:
            entry = load_entry(p)
            res = run_trajectory(entry["trajectory"])
            assert res.violations == []
            assert res.digest() == entry["digest"]
