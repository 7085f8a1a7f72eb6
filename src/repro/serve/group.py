"""ServeGroup: N replicas on the thread-rank transport, ULFM fault handling.

Each rank thread owns one :class:`~repro.serve.replica.Replica` and serves its
share of the request ledger. Every round the ranks exchange health + remaining
load through a fault-aware ``Comm.all_reduce`` — the same choke point the
paper routes everything through: the wait either returns the reduction or
raises the unified exceptions.

Hard fault choreography (the acceptance scenario of ISSUE 1):

1. a replica dies (``Transport.kill`` / ``ctx.die`` — simulated node loss);
2. survivors' next health exchange fails; the ULFM protocol revokes, agrees,
   and every survivor raises ``CommCorruptedError`` — *no deadlock*: nobody
   waits on the dead rank;
3. survivors ``shrink_to_survivors`` and re-route: the ledger deterministically
   reassigns the dead rank's unanswered requests across survivors
   (``id % n_survivors`` over the sorted survivor list — no extra communication
   needed, in the spirit of non-collective communicator reparation
   [arXiv 2209.01849]), and serving continues without a global restart
   [arXiv 2212.08755];
4. re-routed requests are recomputed from their prompts on the new owner —
   accepted requests are *answered*, never dropped.

The elastic layer (ISSUE 8) extends the same machinery in both directions and
through time:

* **Epochs, one reconfiguration path.** Every membership change — fault
  shrink, replica join/rejoin, autoscale grow/shrink — is an *epoch*
  proposal on the shared :class:`~repro.serve.ledger.GroupLedger`. The
  per-round health exchange carries ``[remaining, epoch]`` under an
  elementwise max, so all active ranks observe the same highest epoch at the
  same collective and reconfigure together: nobody posts on a stale
  communicator while others moved on. A rank terminates only when the
  exchange agrees both that no work remains *and* that it sits on the newest
  epoch — so a pending joiner is always met on the widened communicator.
* **Non-blocking join** (Bouteiller et al., "Implicit Actions and
  Non-blocking Failure Recovery with MPI"): a joining rank warms up,
  receives weights + the page-pool layout snapshot as a background lane —
  survivors keep decoding throughout — then proposes a widened epoch; the
  ledger deterministically re-balances untaken work onto the widened group.
  Communicators for new epochs come from the *non-collective* reparation
  primitive ``Comm.repair`` [arXiv 2209.01849] — grow and shrink are the
  same operation.
* **Durable ledger.** With ``ledger_path`` every submit / route / retirement
  is a checksummed, fsync'd WAL record; ``serve_from_ledger`` restarts a
  fully crashed fleet from the log alone: answered requests come back
  bit-exact from their ``retire`` records, outstanding ones re-enter through
  the negative-sequence requeue lane with arrival times and trace ids
  preserved — zero drops across the crash.
* **Autoscaler.** The leader (lowest live rank) grows the group on sustained
  backlog / TTFT-p99 pressure and shrinks it on sustained idleness, with
  hysteresis + cooldown — by summoning a dormant spare or draining a victim
  through a *graceful* epoch, driving the very same membership path as a
  fault.

Soft faults stay replica-local (per-sequence LFLR inside ``Replica``); the
group only learns about them through metrics.
"""
from __future__ import annotations

import time
from collections import Counter
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax

from ..core import CommCorruptedError, PropagatedError, initialize, run_ranks
from ..core.faults import FaultSchedule
from ..core.transport import RankResult
from ..launch.steps import (
    make_cache_prefill,
    make_decode_window,
    make_prefill_decode_window,
    make_slot_decode_step,
    make_speculative_decode_window,
)
from ..models import build_model
from ..obs.trace import NULL_TRACER, Tracer, merge_traces
from .config import EngineConfig
from .ledger import GroupLedger, WriteAheadLog
from .ledger import replay as replay_ledger
from .metrics import ServeMetrics
from .queue import AdmissionPolicy, Request, RequestQueue, Response
from .replica import SERVE_PROBES, Replica

# chunking of the simulated join-time state transfer: enough chunks (with a
# short host pause each) that the join window spans several decode rounds —
# the survivor-throughput-during-join measurement needs a real window
_TRANSFER_CHUNKS = 6
_TRANSFER_PAUSE_S = 0.002


@dataclass(frozen=True)
class AutoscalePolicy:
    """Hysteresis-guarded elastic sizing policy for a :class:`ServeGroup`.

    The leader samples pressure every round: *hot* when the ledger backlog
    (accepted but unassigned requests) reaches ``queue_high`` or the leader's
    own TTFT p99 exceeds ``ttft_high``; *idle* when the backlog is empty.
    ``grow_sustain`` consecutive hot rounds summon a dormant spare;
    ``shrink_idle`` consecutive idle rounds drain the highest live rank out
    through a graceful epoch. ``cooldown`` rounds must separate consecutive
    membership changes — the hysteresis that stops grow/shrink flapping."""

    queue_high: int = 4
    ttft_high: Optional[float] = None      # seconds, None = queue-depth only
    grow_sustain: int = 3
    shrink_idle: int = 6
    cooldown: int = 8
    min_ranks: int = 2


@dataclass(frozen=True)
class AgreeDecision:
    """Outcome of one agreement round: what a member does with the folded
    ``[remaining, epoch]`` pair."""

    action: str      # "reconfigure" | "hold" | "close" | "continue"
    epoch: int       # the epoch to serve under after acting


def agree_round(rem: int, agreed: int, my_epoch: int, *,
                hold_close: bool = False) -> AgreeDecision:
    """The transport-neutral half of the §3.4 agreement: interpret the
    emax-folded ``[remaining, epoch]`` pair against this member's epoch.

    Both transports run the exact same ladder — the in-process
    ``comm.all_reduce`` group and the multihost socket workers (where the
    supervisor performs the fold in star topology) — so membership semantics
    cannot drift between fault domains:

    * a newer epoch wins over everything (**reconfigure**: enter it before
      serving another round);
    * ``rem == 0`` **close**s the group — unless ``hold_close`` (a pending
      join or a proposal that landed after this round's fold) asks to spin
      one more round;
    * otherwise **continue** serving.
    """
    if agreed > my_epoch:
        return AgreeDecision("reconfigure", agreed)
    if rem == 0:
        return AgreeDecision("hold" if hold_close else "close", my_epoch)
    return AgreeDecision("continue", my_epoch)


@dataclass
class RankReport:
    rank: int
    rounds: int = 0
    events: list = field(default_factory=list)   # ("shrink"|"propagated", round, info)
    metrics: Optional[ServeMetrics] = None
    device: Optional[str] = None                 # where the replica ran


@dataclass
class GroupResult:
    responses: dict[int, Response]
    reports: list[RankResult]                    # raw per-rank harness results
    rerouted: tuple[int, ...] = ()
    tracers: dict[int, Tracer] = field(default_factory=dict)
    rebalanced: tuple[int, ...] = ()             # moved by epoch re-balance
    joined: tuple[int, ...] = ()                 # ranks admitted via join
    autoscale: tuple[dict, ...] = ()             # leader grow/shrink decisions
    epoch: int = 0                               # final membership epoch
    crashed: bool = False                        # fleet stopped mid-serve
    replayed: tuple[int, ...] = ()               # ids re-admitted from a WAL

    @property
    def ok(self) -> dict[int, Response]:
        return {i: r for i, r in self.responses.items() if r.ok}

    def report(self, rank: int) -> Optional[RankReport]:
        rr = self.reports[rank]
        return rr.value if rr.exception is None and not rr.killed else None

    def merged_metrics(self) -> ServeMetrics:
        """Survivor replicas' metrics pooled into one accumulator (sums,
        max-of-peaks, pooled response populations for percentiles)."""
        parts = [rr.value.metrics for rr in self.reports
                 if rr.exception is None and not rr.killed
                 and rr.value is not None and rr.value.metrics is not None]
        return ServeMetrics.merged(parts)

    def summary(self) -> dict:
        """One fleet-level dict: the merged per-replica metrics plus the
        group's own story (replica count, survivors, re-routes)."""
        out = self.merged_metrics().summary()
        # a dormant spare that was never summoned returns None without
        # serving — it participated in nothing and counts as nothing
        out["replicas"] = sum(1 for rr in self.reports
                              if rr.killed or rr.exception is not None
                              or rr.value is not None)
        out["survivors"] = sum(1 for rr in self.reports
                               if rr.exception is None and not rr.killed
                               and rr.value is not None)
        out["rerouted"] = len(self.rerouted)
        if self.joined:
            out["joined"] = len(self.joined)
        if self.rebalanced:
            out["rebalanced"] = len(self.rebalanced)
        if self.autoscale:
            out["autoscale"] = len(self.autoscale)
        if self.crashed:
            out["crashed"] = True
        return out

    def trace(self) -> dict:
        """All ranks' tracers (dead ones included — their spans are the cause
        half of the kill → shrink → re-route chain) merged into one
        trace_event object."""
        return merge_traces(*(self.tracers[r] for r in sorted(self.tracers)))


class ServeGroup:
    """A fleet of serving replicas over the simulated multi-rank runtime."""

    def __init__(self, cfg, nranks: int, *,
                 config: Optional[EngineConfig] = None,
                 seed: int = 0, probe_cfg=SERVE_PROBES,
                 timeout: float = 30.0,
                 max_ranks: Optional[int] = None,
                 autoscale: Optional[AutoscalePolicy] = None,
                 transfer_chunks: int = _TRANSFER_CHUNKS,
                 transfer_pause_s: float = _TRANSFER_PAUSE_S):
        # engine shape comes in through one validated EngineConfig (the
        # historical group default was num_slots=2, preserved here); group
        # wiring (timeouts, elasticity, transfer shape) stays real keywords.
        config = config if config is not None else EngineConfig(num_slots=2)
        self.config = config
        if nranks < 2:
            raise ValueError("a ServeGroup needs >= 2 replicas")
        self.cfg = cfg
        self.nranks = nranks
        self.max_ranks = max(nranks, int(max_ranks or nranks))
        self.autoscale = autoscale
        # join-time state-transfer shape: benchmarks stretch it so the join
        # window spans many decode rounds (the survivor-throughput-during-join
        # cell needs a measurement window wider than one retire burst)
        self.transfer_chunks = int(transfer_chunks)
        self.transfer_pause_s = float(transfer_pause_s)
        self.num_slots = config.num_slots
        self.max_len = config.max_len
        self.timeout = timeout
        self.max_request_retries = config.max_request_retries
        self.eos_id = config.eos_id
        self.window = int(config.window)
        self.overlap = bool(self.window) and bool(config.overlap)
        self.prefill_budget = config.prefill_budget
        self.paged = bool(config.paged)
        self.page_size = config.page_size
        self.page_budget = config.page_budget
        self.page_watermark = config.page_watermark
        self.speculate = bool(config.speculate)
        self.draft_len = int(config.draft_len)
        self.draft_layers = int(config.draft_layers)
        self.tp = int(config.tp)
        self.trace = bool(config.trace)
        self.trace_sample = float(config.trace_sample)
        donate = config.donate
        self.params = build_model(cfg).init(jax.random.PRNGKey(seed))
        # one chip per rank where there are enough: rank r serves from
        # jax.devices()[r % n], with its own copy of the params (a tp>1 rank
        # spans the model mesh instead)
        devices = jax.devices()
        self._devices = [devices[r % len(devices)]
                         for r in range(self.max_ranks)]
        self._rank_params = ({} if self.tp > 1 else
                             {d: jax.device_put(self.params, d)
                              for d in dict.fromkeys(self._devices)})
        # compile once, share across rank threads (jit dispatch is thread-safe)
        # — each paged replica owns its own pool + table, but the layout (and
        # therefore every jitted program) is identical across the fleet
        if self.paged:
            from ..launch.paging import PagedLayout
            model = build_model(cfg)
            num_pages = (int(self.page_budget) if self.page_budget is not None
                         else self.num_slots * (self.max_len // self.page_size))
            self._layout = PagedLayout(model.init_cache(1, self.max_len),
                                       self.max_len,
                                       page_size=self.page_size,
                                       num_pages=num_pages)
        else:
            self._layout = None
        # tensor-parallel fleet: ONE TPContext (mesh + storage specs) shared
        # by the jitted window program below and by every rank's Replica —
        # jax.make_mesh with identical args yields equal Mesh objects, so the
        # per-rank replicas hit the same compilation cache
        self._tp_ctx = None
        if self.tp > 1:
            self._tp_ctx = self._make_tp_ctx()
        self._decode_fn = jax.jit(make_slot_decode_step(cfg, probe_cfg))
        self._prefill_fn = make_cache_prefill(cfg, probe_cfg,
                                              fused=bool(self.window),
                                              paged=self._layout,
                                              donate=bool(self.paged and donate))
        if not self.window:
            self._window_fn = None
        elif self.speculate:
            self._window_fn = make_speculative_decode_window(
                cfg, probe_cfg, window=self.window, draft_len=self.draft_len,
                draft_layers=self.draft_layers, donate=donate,
                paged=self._layout, tp=self._tp_ctx)
        elif self.overlap:
            self._window_fn = make_prefill_decode_window(
                cfg, probe_cfg, window=self.window, donate=donate,
                paged=self._layout, tp=self._tp_ctx)
        else:
            self._window_fn = make_decode_window(
                cfg, probe_cfg, window=self.window, donate=donate,
                paged=self._layout, tp=self._tp_ctx)

    def _make_tp_ctx(self):
        """The fleet-shared :class:`~repro.launch.steps.TPContext`: same mesh
        and storage specs every rank's Replica derives for itself, computed
        once here so the shared window program is sharded at build time.
        Cache specs come from shape templates only — nothing is materialised."""
        from ..launch.steps import TPContext
        from ..sharding.rules import param_specs, tp_storage_specs
        ndev = len(jax.devices())
        if ndev < self.tp:
            raise ValueError(
                f"tp={self.tp} requires {self.tp} devices, found {ndev} "
                "(on CPU, force host devices with XLA_FLAGS="
                f"--xla_force_host_platform_device_count={self.tp})")
        mesh = jax.make_mesh((self.tp,), ("model",))
        one = build_model(self.cfg).init_cache(1, self.max_len)
        if self.paged:
            hybrid = self._layout.init_hybrid(one, self.num_slots)
            cspecs = self._layout.tp_storage_specs(hybrid, mesh)
        else:
            stacked = jax.tree_util.tree_map(
                lambda v: jax.ShapeDtypeStruct((self.num_slots, *v.shape),
                                               v.dtype), one)
            cspecs = tp_storage_specs(stacked, mesh)
        return TPContext(mesh=mesh,
                         param_specs=param_specs(self.params, mesh),
                         cache_specs=cspecs)

    def device_of(self, rank: int):
        """The device rank ``rank``'s replica serves from (None when tp>1:
        the rank spans the model mesh)."""
        return None if self.tp > 1 else self._devices[rank]

    # ------------------------------------------------------------ entry points
    def serve(self, requests: Sequence[Request], *,
              faults: FaultSchedule | None = None,
              max_rounds: int = 10_000,
              ledger_path: Optional[str] = None,
              crash_at: Optional[int] = None,
              joins: Optional[Sequence[int]] = None) -> GroupResult:
        """Serve ``requests`` to completion across the group.

        ``faults`` uses :class:`FaultSpec` with ``step`` meaning the serving
        *round*: ``kind="kill"`` hard-kills a replica at the top of that round;
        ``kind="state_nan"`` flips a bit in one of its active sequences.
        Returns once every request has a terminal response on the survivors.

        The schedule is fully seeded: wildcard specs (``rank=None``) are
        resolved to concrete ranks up front via the schedule's own seed, and
        the slot a ``state_nan`` poisons is drawn from a per-(rank, round)
        generator derived from the same seed — so a fuzzer trajectory that
        kills "some" replica replays bit-for-bit from ``(specs, seed)``.

        Elastic extensions: ``ledger_path`` mirrors the ledger into a durable
        write-ahead log (see :meth:`serve_from_ledger` for the restart half);
        ``crash_at`` stops the *whole fleet* at the top of that round — the
        SIGKILL analogue, every rank dies, only the WAL survives; ``joins``
        lists rounds at which the leader summons a dormant spare rank into
        the group (``max_ranks`` > ``nranks`` provisions the spares).
        """
        wal = WriteAheadLog(ledger_path) if ledger_path else None
        ledger = GroupLedger(
            requests, range(self.nranks),
            spares=range(self.nranks, self.max_ranks), wal=wal)
        return self._run(ledger, actives=tuple(range(self.nranks)),
                         faults=faults, max_rounds=max_rounds,
                         crash_at=crash_at, joins=joins)

    def serve_from_ledger(self, ledger_path: str, *,
                          faults: FaultSchedule | None = None,
                          max_rounds: int = 10_000,
                          crash_at: Optional[int] = None,
                          joins: Optional[Sequence[int]] = None) -> GroupResult:
        """Restart a crashed fleet from its write-ahead log alone.

        :func:`~repro.serve.ledger.replay` reconstructs the ledger (answered
        requests return bit-exact from their ``retire`` records; a torn final
        record is discarded), the last logged epoch's members come back as
        the active set, every other rank up to ``max_ranks`` becomes a spare
        available for regrow, and the outstanding requests re-enter serving
        through the negative-sequence requeue lane with their original
        arrival times and trace ids — so latency accounting and the causal
        trace chain span the crash."""
        rep = replay_ledger(ledger_path)
        if not rep.members:
            raise ValueError(f"{ledger_path}: no epoch record to restart from")
        members = tuple(m for m in rep.members if m < self.max_ranks)
        if len(members) < 2:
            raise ValueError(
                f"{ledger_path}: epoch members {rep.members} leave fewer "
                f"than 2 restartable ranks (max_ranks={self.max_ranks})")
        outstanding = rep.outstanding()
        wal = WriteAheadLog(ledger_path)     # truncates any torn tail
        ledger = GroupLedger(
            outstanding, members,
            spares=[r for r in range(self.max_ranks) if r not in members],
            wal=wal, responses=rep.responses,
            replayed=[r.id for r in outstanding],
            stamped=[r.id for r in outstanding if r.arrival_t is not None],
            epoch0=rep.epoch, epoch_reason="replay", log_submits=False)
        return self._run(ledger, actives=members, faults=faults,
                         max_rounds=max_rounds, crash_at=crash_at,
                         joins=joins, replay_info=rep)

    # ------------------------------------------------------------- the machine
    def _run(self, ledger: GroupLedger, *, actives: tuple[int, ...],
             faults: FaultSchedule | None, max_rounds: int,
             crash_at: Optional[int], joins: Optional[Sequence[int]],
             replay_info=None) -> GroupResult:
        faults = (faults or FaultSchedule()).resolve(sorted(actives))
        policy = self.autoscale
        joins_at = Counter(int(r) for r in (joins or ()))
        launched = self.max_ranks if self.max_ranks > len(actives) else self.nranks
        # elastic mode throttles `take` to replica capacity so a widened
        # group finds untaken work to re-balance; the classic fixed group
        # keeps its drain-everything behavior bit-for-bit
        elastic = (launched > len(actives) or policy is not None
                   or ledger.wal is not None or crash_at is not None
                   or bool(joins_at))

        # a request that could never fit a replica's page pool must be
        # REJECTED at submit (same clamp Replica applies to its own queue)
        pool_cap = (self._layout.capacity_tokens
                    if self.paged and self._layout.has_paged_leaves
                    else self.max_len)

        leaves = jax.tree_util.tree_leaves(self.params)
        ledger.publish_state({
            "params_bytes": int(sum(l.size * l.dtype.itemsize
                                    for l in leaves)),
            "paged": self.paged,
            "num_pages": (self._layout.num_pages if self.paged else 0),
        })

        tracers: dict[int, Tracer] = {}
        epoch0 = ledger.epoch
        leader0 = min(actives)

        def make_tracer(rank: int) -> Tracer:
            if not self.trace:
                return NULL_TRACER
            tracer = Tracer(pid=rank, sample=self.trace_sample)
            # registered up front so a killed rank's spans survive it —
            # they are the *cause* half of the kill → shrink → re-route
            # chain the merged trace must show
            tracers[rank] = tracer
            return tracer

        def build_replica(rank: int, tracer: Tracer) -> Replica:
            queue = RequestQueue(AdmissionPolicy(
                max_queue=10_000, max_total_len=pool_cap), tracer=tracer)
            return Replica(
                self.cfg, params=(self.params if self.tp > 1 else
                                  self._rank_params[self._devices[rank]]),
                config=self.config,
                queue=queue, rank=rank,
                decode_fn=self._decode_fn, prefill_fn=self._prefill_fn,
                window_fn=self._window_fn, paged_layout=self._layout)

        def serve_rounds(ctx, comm, replica, tracer, report, my_epoch, *,
                         inject_faults=True):
            """The per-rank round loop — initial actives and joiners alike.

            ``round_i`` frames are aligned across the initial actives (every
            iteration is one collective exchange), so ``crash_at`` and the
            fault schedule fire coherently; a joiner counts its own rounds
            from 0 and therefore neither re-fires the schedule
            (``inject_faults=False`` — the specs describe the original
            incarnation) nor triggers ``crash_at`` itself — it learns of a
            fleet stop through the ledger flag."""
            for round_i in range(max_rounds):
                # ---- fleet stop (SIGKILL analogue): the WAL is all that
                # survives; every rank dies, joiners learn via the flag
                if (crash_at is not None and round_i == crash_at
                        and inject_faults) or ledger.crashed:
                    ledger.crash()
                    if tracer.enabled:
                        tracer.instant("fleet_stop", "group", rank=ctx.rank,
                                       round=round_i)
                    ctx.die()                           # never returns
                for spec in (faults.at(round_i, ctx.rank)
                             if inject_faults else ()):
                    if spec.kind == "kill":
                        if tracer.enabled:
                            tracer.instant("replica_kill", "group",
                                           rank=ctx.rank, round=round_i)
                        ctx.die()                       # never returns
                    elif spec.kind == "shard_kill":
                        # TP shard loss: one shard of this replica's model
                        # mesh dies. A TP replica is one SPMD program, so the
                        # shard loss is a hard fault of the whole rank — the
                        # survivors see the same RANK_FAILED → shrink →
                        # re-route path a full replica kill drives; the
                        # shard_loss instant records which shard was the cause
                        if tracer.enabled:
                            tracer.instant("shard_loss", "group",
                                           rank=ctx.rank, round=round_i,
                                           shard=spec.shard, tp=self.tp)
                            tracer.instant("replica_kill", "group",
                                           rank=ctx.rank, round=round_i)
                        ctx.die()                       # never returns
                    elif spec.kind == "state_nan":
                        slot = replica.inject_state_fault(
                            rng=faults.rng_for(ctx.rank, round_i))
                        if slot is not None:
                            report.events.append(("inject", round_i, slot))
                leader = min(ledger.members)
                if ctx.rank == leader and not ledger.stopped:
                    for _ in range(joins_at.get(round_i, 0)):
                        summoned = ledger.summon_next("scheduled")
                        if summoned is not None:
                            report.events.append(
                                ("summon", round_i, summoned))
                    if policy is not None:
                        self._autoscale_tick(ledger, policy, replica,
                                             round_i, tracer, report)
                # ---- graceful autoscale leave: drain, then propose the
                # epoch that excludes us and keep exchanging until agreed
                if ledger.leaving == ctx.rank and replica.idle():
                    left = ledger.depart(ctx.rank)
                    if tracer.enabled:
                        tracer.instant("autoscale", "group", action="depart",
                                       rank=ctx.rank, epoch=left,
                                       round=round_i)
                    report.events.append(("depart", round_i, left))
                if ledger.leaving != ctx.rank:
                    limit = (None if not elastic else
                             max(0, 2 * self.num_slots - replica.load()))
                    for req in ledger.take(ctx.rank, limit):
                        if (req.id in ledger.replayed
                                and req.arrival_t is not None):
                            rej = replica.readmit(req)
                        else:
                            rej = replica.submit(req)
                        if rej is None:
                            ledger.note_stamp(req)
                        else:
                            ledger.complete(rej)
                for resp in replica.step():
                    ledger.complete(resp)
                report.rounds = round_i + 1
                # fault-aware health/termination/epoch exchange: the one wait
                # that either agrees on progress or raises the paper's
                # exceptions. Elementwise max makes every rank of the epoch
                # see the same [remaining, newest-epoch] pair at the same
                # collective — the barrier at which reconfiguration happens.
                try:
                    rem, agreed = comm.all_reduce(
                        [ledger.remaining(), ledger.epoch], op="emax").wait()
                except PropagatedError as exc:
                    report.events.append(
                        ("propagated", round_i,
                         [e.rank for e in exc.errors]))
                    continue
                except CommCorruptedError:
                    prev = tuple(comm.context.members)
                    comm.shrink_to_survivors()
                    survivors = list(comm.context.members)
                    moved = ledger.on_death(set(prev) - set(survivors))
                    if tracer.enabled:
                        tracer.instant("ulfm_shrink", "group", rank=ctx.rank,
                                       round=round_i,
                                       survivors=sorted(survivors))
                        for rid, old, new in moved:
                            tracer.instant(
                                "reroute", "group",
                                trace_id=ledger.requests[rid].trace_id,
                                request=rid, from_rank=old, to_rank=new)
                    report.events.append(("shrink", round_i, len(survivors)))
                    if moved:
                        report.events.append(
                            ("reroute", round_i, [r for r, _, _ in moved]))
                    continue
                # hold the final close (serving never stalled — there is
                # simply nothing left to serve) while either (a) an
                # operator-scheduled joiner is still warming up /
                # mid-transfer, so a requested regrow cannot lose the race
                # against the drain, or (b) a membership proposal landed
                # *after* this round's exchange read the epoch — closing on
                # the stale agreement would strand the proposer on a
                # collective nobody posts
                decision = agree_round(
                    rem, agreed, my_epoch,
                    hold_close=(ledger.has_pending_joins()
                                or ledger.epoch > agreed))
                if decision.action == "reconfigure":
                    # first entrant re-balances untaken work over the new
                    # member list, everyone re-keys the comm
                    moved = ledger.enter_epoch(decision.epoch)
                    members = ledger.members_of(decision.epoch)
                    if tracer.enabled:
                        for rid, old, new in moved:
                            tracer.instant(
                                "reroute", "group",
                                trace_id=ledger.requests[rid].trace_id,
                                request=rid, from_rank=old, to_rank=new)
                    if moved:
                        report.events.append(
                            ("rebalance", round_i, [r for r, _, _ in moved]))
                    report.events.append(("epoch", round_i, decision.epoch))
                    if ctx.rank not in members:
                        return report       # our graceful leave is agreed
                    if tuple(sorted(comm.context.members)) != members:
                        comm = comm.repair(members,
                                           ("serve-epoch", decision.epoch))
                    my_epoch = decision.epoch
                    continue    # ≥1 exchange on the new epoch before exit
                if decision.action == "hold":
                    time.sleep(0.002)
                    continue
                if decision.action == "close":
                    ledger.close()
                    return report
            raise RuntimeError(
                f"rank {ctx.rank}: no global progress in {max_rounds} rounds "
                f"({ledger.remaining()} requests unanswered)")

        def join_rank(ctx, inst, tracer, replica, reason: str,
                      t_join0: float):
            """Warm spare → serving member, without stalling survivors:
            receive state as a background lane, propose the widened epoch,
            meet the group on the repaired communicator."""
            snap = ledger.state_snapshot or {}
            t_xfer0 = time.monotonic()
            for _ in range(self.transfer_chunks):
                if ledger.stopped:
                    ledger.abandon_join(ctx.rank)
                    return None             # fleet gone mid-transfer
                time.sleep(self.transfer_pause_s)
            if tracer.enabled:
                tracer.span("state_transfer", "group", t_xfer0,
                            time.monotonic(), rank=ctx.rank,
                            bytes=snap.get("params_bytes", 0),
                            num_pages=snap.get("num_pages", 0),
                            chunks=self.transfer_chunks, reason=reason,
                            complete=True)
            epoch = ledger.request_join(ctx.rank)
            if epoch is None:
                return None                 # group finished while we warmed
            # wait (off the collective path) until the actives entered an
            # epoch that includes us — guarantees somebody will meet our
            # first exchange. A concurrent fault may have pushed the agreed
            # epoch *past* our proposal; every later epoch still contains us
            # (only our own death could remove us), so we enter the newest.
            while ledger.agreed_epoch < epoch:
                if ledger.stopped:
                    ledger.abandon_join(ctx.rank)
                    return None
                time.sleep(0.001)
            epoch = ledger.agreed_epoch
            comm = inst.comm_world().repair(
                ledger.members_of(epoch), ("serve-epoch", epoch))
            if tracer.enabled:
                tracer.span("replica_join", "group", t_join0,
                            time.monotonic(), rank=ctx.rank, epoch=epoch,
                            reason=reason, complete=True)
            report = RankReport(rank=ctx.rank, metrics=replica.metrics,
                                device=str(replica.device))
            report.events.append(("join", epoch, reason))
            return serve_rounds(ctx, comm, replica, tracer, report, epoch,
                                inject_faults=False)

        def rank_fn(ctx):
            if ctx.rank in actives:
                inst = initialize(ctx, default_timeout=self.timeout)
                tracer = make_tracer(ctx.rank)
                if launched == len(actives):
                    comm = inst.comm_world()
                else:
                    comm = inst.comm_world().repair(
                        tuple(sorted(actives)), ("serve-epoch", epoch0))
                if replay_info is not None and ctx.rank == leader0 \
                        and tracer.enabled:
                    tracer.instant(
                        "ledger_replay", "group", rank=ctx.rank,
                        records=replay_info.records, torn=replay_info.torn,
                        epoch=epoch0, outstanding=len(ledger.replayed),
                        answered=len(replay_info.responses))
                replica = build_replica(ctx.rank, tracer)
                report = RankReport(rank=ctx.rank, metrics=replica.metrics,
                                    device=str(replica.device))
                return serve_rounds(ctx, comm, replica, tracer, report,
                                    epoch0)
            # dormant spare: pre-warm at spawn (replica build + jit warmup,
            # off the fleet's collective path — a warm standby pool), so a
            # later summons only pays the state-transfer lane; then wait
            # off-path for a summons (join schedule or autoscale grow) and
            # exit quietly if the group stops first
            if ledger.stopped:
                return None
            inst = initialize(ctx, default_timeout=self.timeout)
            tracer = make_tracer(ctx.rank)
            replica = build_replica(ctx.rank, tracer)
            replica.warmup()                # compiles; clears warmup spans
            deadline = time.monotonic() + self.timeout * 3
            while time.monotonic() < deadline:
                if ledger.stopped:
                    ledger.abandon_join(ctx.rank)
                    return None
                if all(m in ctx.t.dead for m in ledger.members):
                    ledger.abandon_join(ctx.rank)
                    return None             # nobody left to join
                reason = ledger.summoned(ctx.rank)
                if reason is not None:
                    return join_rank(ctx, inst, tracer, replica, reason,
                                     time.monotonic())
                time.sleep(0.002)
            ledger.abandon_join(ctx.rank)
            return None

        results = run_ranks(launched, rank_fn, ulfm=True,
                            join_timeout=self.timeout * 4)
        if ledger.wal is not None:
            ledger.wal.close()
        return GroupResult(
            responses=dict(ledger.responses), reports=results,
            rerouted=tuple(ledger.rerouted), tracers=tracers,
            rebalanced=tuple(ledger.rebalanced),
            joined=tuple(ledger.joined),
            autoscale=tuple(ledger.autoscale_events),
            epoch=ledger.epoch, crashed=ledger.crashed,
            replayed=tuple(sorted(ledger.replayed)))

    # -------------------------------------------------------------- autoscaler
    def _autoscale_tick(self, ledger: GroupLedger, policy: AutoscalePolicy,
                        replica: Replica, round_i: int, tracer: Tracer,
                        report: RankReport) -> None:
        """One leader-side policy sample. Grow and shrink both land on the
        ledger's epoch path — the same reconfiguration the fault handler
        drives — so elasticity adds no second membership mechanism."""
        st = ledger.scale_state
        members = ledger.members
        backlog = ledger.backlog()
        rem = ledger.remaining()
        hot = backlog >= policy.queue_high
        if not hot and policy.ttft_high is not None:
            p99 = replica.metrics.ttft_percentiles((99,)).get("p99")
            hot = p99 is not None and p99 > policy.ttft_high
        st["hot"] = st["hot"] + 1 if hot else 0
        st["idle"] = st["idle"] + 1 if (backlog == 0 and not hot) else 0
        since = round_i - st["last_change"]
        if (st["hot"] >= policy.grow_sustain and since >= policy.cooldown
                and len(members) < self.max_ranks):
            rank = ledger.summon_next("autoscale")
            if rank is not None:
                st["hot"] = 0
                st["last_change"] = round_i
                ledger.autoscale_events.append(
                    {"round": round_i, "action": "grow", "rank": rank})
                if tracer.enabled:
                    tracer.instant("autoscale", "group", action="grow",
                                   rank=rank, round=round_i)
                report.events.append(("autoscale", round_i, ("grow", rank)))
        elif (st["idle"] >= policy.shrink_idle and since >= policy.cooldown
                and len(members) > max(2, policy.min_ranks)
                and rem > 0 and ledger.leaving is None):
            victim = max(members)
            if victim != min(members) and ledger.request_leave(victim):
                st["idle"] = 0
                st["last_change"] = round_i
                ledger.autoscale_events.append(
                    {"round": round_i, "action": "shrink", "rank": victim})
                if tracer.enabled:
                    tracer.instant("autoscale", "group", action="shrink",
                                   rank=victim, round=round_i)
                report.events.append(
                    ("autoscale", round_i, ("shrink", victim)))
