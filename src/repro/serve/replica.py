"""Serving replica: the overlapped decode window, per-sequence LFLR.

One replica owns a fixed-slot continuous batch served by one engine: the
**overlapped decode window**
(:func:`~repro.launch.steps.make_prefill_decode_window`, built by
:func:`~repro.serve.engine.build_engine`). K greedy steps run fully on
device per dispatch; fault detection is deferred to the window boundary (the
paper's asynchrony contract — errors latch in-band, raise at the *wait*);
the per-slot ``(K, slots)`` error-word history runs through the paper's
enumeration algorithm, so the ``PropagatedError`` raised at the wait carries
exact ``(slot, code)`` pairs and the history names the exact step.

The commit loop is **double-buffered**: window N+1 is dispatched from window
N's device-resident outputs (next token + donated caches) *before* window
N's token block is read back, so the device never idles on a host round
trip. Host syncs scale with ``steps / K``. EOS / deadline / faulted slots
are handled at window boundaries: trailing tokens are discarded, freed lanes
are backfilled, and the already-in-flight window is patched — its stale
lanes are marked invalid and simply skipped at its own retirement.

Admission and LFLR recovery are **background prefill lanes** driven by the
scheduler: a joining or recovering slot's pending sequence is chunked into
the window scan, so the token stream of the healthy slots never stalls, and
the lane flips to decoding inside the window whose chunk consumes its last
pending token (the argmax after the last prompt token is the first
generated token). A fault latched during a chunk is attributed through the
same ``(K, slots)`` history and re-queues the lane (cache reset + chunk
from position 0) without a single host sync.

With ``speculate=True`` (full-attention archs) the window becomes a
**speculative decode window**
(:func:`~repro.launch.steps.make_speculative_decode_window`): every window
step drafts ``draft_len`` tokens with a shallow-exit self-draft and verifies
them in one batched full-model forward, emitting 1..D+1 tokens per step —
token-bit-exact vs the plain engine, since every emitted token is a
full-model argmax. The commit loop consumes a per-(step, slot) accepted-count
readback instead of assuming K tokens (EOS / deadline / fault boundaries cut
the flattened accepted stream), the position chain moves on device (advance
is data-dependent), and rejected drafts ride the same ``(K, slots)`` error
history as the attribution-only ``DRAFT_REJECT`` lane — visible to
``fault_codes()``, masked out of the fault-raising word, never recovered.

Recovery is the paper's use-case 1 applied to inference:

* ``STATE_FAULT`` (bit-flipped recurrent state) or non-finite logits on slot
  *i* → **LFLR re-prefill**: recompute slot *i*'s cache from its prompt +
  already-generated tokens (greedy decode is deterministic, so this recreates
  the pre-fault trajectory exactly) — the other slots commit their tokens and
  never notice;
* the :class:`~repro.core.recovery.RecoveryPolicy` escalates: repeated faults
  inside its window recompute *every* lane (the rollback analogue), and a
  request that re-faults past ``max_request_retries`` is answered ``FAILED``
  (the serving ABORT — one poisoned request must not wedge the replica).
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..core.detect import ProbeConfig
from ..core.device_channel import WORD_DTYPE, DeviceFuture
from ..core.errors import ErrorCode, PropagatedError
from ..core.faults import INJECTABLE_CODE_MASK as _INJECTABLE_MASK
from ..core.recovery import Action, RecoveryPolicy
from ..launch.steps import TPContext
from ..models import build_model
from ..obs.trace import NULL_TRACER, SHARD_TID, Tracer
from .config import EngineConfig
from .engine import SERVE_PROBES, EnginePrograms, build_engine
from .metrics import ServeMetrics
from .queue import EXPIRED, FAILED, AdmissionPolicy, Request, RequestQueue, Response
from .scheduler import ContinuousBatchingScheduler, PageAllocator, PagePoolExhausted


def _home_device(tree):
    """The one device every leaf of ``tree`` lives on, or None."""
    devices = {d for leaf in jax.tree_util.tree_leaves(tree)
               for d in leaf.devices()}
    return devices.pop() if len(devices) == 1 else None


@functools.lru_cache(maxsize=None)
def make_enum_fn(num_slots: int):
    """Jitted ``(words, mask) -> (combined, count, table)`` over the slot axis.

    Free slots are masked out (their caches may hold stale values from an
    evicted sequence), then the paper's enumeration attributes each remaining
    word to its slot. ``max_errors=num_slots`` so attribution never truncates.
    Cached per slot count, so a fleet of replicas compiles it once.
    """
    from ..core.device_channel import combine_words, enumerate_errors_ref

    @jax.jit
    def enum(words, mask):
        words = words.astype(WORD_DTYPE) * mask.astype(WORD_DTYPE)
        combined = combine_words(*(words[i] for i in range(num_slots)))
        count, table = enumerate_errors_ref(words, max_errors=num_slots)
        return combined, count, table

    return enum


@functools.lru_cache(maxsize=None)
def make_window_enum_fn(num_slots: int, ignore: int = 0):
    """Jitted ``(history (K, S), mask (S,)) -> (combined, count, table, hist)``.

    Free slots are masked out of the whole ``(K, slots)`` word history,
    per-slot words are OR-folded over the window (deferred detection — one
    check per K tokens), and the fold is handed to the per-slot enumeration
    of :func:`make_enum_fn`. The masked history rides along so :meth:`DeviceFuture.fault_steps` can attribute a
    fault to its exact ``(step, slot)`` on the (rare) fault path only.

    ``ignore`` strips attribution-only code bits (``DRAFT_REJECT``) from the
    fold that feeds the combined word and the enumeration table — those lanes
    stay in the returned history for exact (step, slot) attribution, but a
    window whose only events are speculation misses must wait() clean, never
    raise.
    """
    from ..core.errors import strip_codes

    slot_enum = make_enum_fn(num_slots)

    @jax.jit
    def enum(history, mask):
        hist = history.astype(WORD_DTYPE) * mask.astype(WORD_DTYPE)[None, :]
        words = jax.lax.reduce(strip_codes(hist, ignore), jnp.uint32(0),
                               jax.lax.bitwise_or, (0,))
        combined, count, table = slot_enum(words, jnp.ones_like(mask))
        return combined, count, table, hist

    return enum


@dataclass
class _WindowInFlight:
    """One dispatched decode window awaiting retirement.

    ``req_ids`` snapshots which request occupied each slot at dispatch (None =
    free lane); a lane's token block only commits if the same request still
    holds the slot at retirement. ``valid`` is cleared for a lane when the
    host patches its device state (LFLR re-prefill / backfill) while this
    window is already in flight — the lane's tokens *and its error words* are
    then stale and are skipped wholesale at retirement. ``start`` is the first
    committable step per lane: 0 for a decoding slot, ``rem - 1`` for a lane
    whose prompt chunk exhausts at step ``rem - 1`` (its argmax there is the
    first real token), K for a lane still mid-prefill (nothing committable).
    """

    fut: DeviceFuture
    req_ids: tuple
    valid: np.ndarray
    start: np.ndarray
    # speculative windows only. ``start_row``: first committable verify row
    # within the flip step ``start`` (prompt rows before it emit
    # non-committable prompt-position argmaxes). ``rem0``: prompt tokens fed
    # this window per lane (0 for decode lanes) — with the counts readback
    # this yields exact drafted/accepted counters. ``deferred``: lanes masked
    # out at dispatch (no valid state; their counts are garbage).
    start_row: Optional[np.ndarray] = None
    rem0: Optional[np.ndarray] = None
    deferred: Optional[np.ndarray] = None
    # MoE windows only. ``lanes``: lanes that ran a request (active and not
    # deferred at dispatch). ``routed``: the window's (K, S, H) readback of
    # rows routed to each held expert, set at retirement.
    lanes: Optional[np.ndarray] = None
    routed: Optional[np.ndarray] = None
    # tracing only: dispatch wall time + the window's index (_step_count at
    # dispatch), so the retire-side span covers the window's whole in-flight
    # life and fault events name the exact window they latched in.
    # ``trace_ids`` snapshots the lane owners' trace ids at dispatch (empty
    # when tracing is off): a fault must be attributed to the request whose
    # state the window actually computed with, even if that request finished
    # and left the slot before the deferred detection surfaced it.
    t_dispatch: float = 0.0
    index: int = 0
    trace_ids: tuple = ()


class Replica:
    """One continuous-batching serving replica (single host / rank)."""

    def __init__(self, cfg: ModelConfig, params: Any = None, *,
                 config: Optional[EngineConfig] = None,
                 queue: RequestQueue | None = None,
                 policy: RecoveryPolicy | None = None,
                 metrics: ServeMetrics | None = None,
                 probe_cfg: ProbeConfig = SERVE_PROBES,
                 rank: int = 0, seed: int = 0,
                 clock: Callable[[], float] = time.monotonic,
                 programs: Optional[EnginePrograms] = None,
                 tracer: Optional[Tracer] = None,
                 fault_injector: Optional[Callable] = None,
                 page_debug: Optional[bool] = None):
        # engine *shape* lives in one validated EngineConfig; runtime wiring
        # (queue, policy, shared programs, tracer, injector, clock) stays as
        # real keywords.
        config = config if config is not None else EngineConfig()
        self.config = config
        num_slots, max_len = config.num_slots, config.max_len
        self.cfg = cfg
        self.model = build_model(cfg)
        self.params = params if params is not None else self.model.init(
            jax.random.PRNGKey(seed))
        self.max_len = max_len
        self.rank = rank
        self.clock = clock
        self.policy = policy or RecoveryPolicy()
        self.metrics = metrics or ServeMetrics(clock=clock)
        # fault-causality tracing: explicit tracer > the provided queue's
        # tracer (a ServeGroup threads one per rank through both) > the free
        # NullTracer. Hot-path call sites guard on ``self.trace.enabled`` so
        # the disabled path never builds an event.
        if tracer is not None:
            self.trace = tracer
        elif queue is not None and queue.tracer.enabled:
            self.trace = queue.tracer
        else:
            self.trace = NULL_TRACER
        # slot -> open recovery lane (trace_id, t0, code, action, window):
        # begun at the recovery decision, closed by the first post-recovery
        # committed token (or swept as abandoned when the request leaves the
        # slot without one — its terminal response resolves the fault)
        self._recovering: dict[int, dict] = {}
        self.max_request_retries = config.max_request_retries
        # deterministic in-band fault-word injection (the fuzzer's device
        # mutation surface): called once per dispatch with the dispatch index
        # and the words shape — (K, slots), or (tp, K, slots) sharded — and
        # may return a uint32 array OR'd into the device error words *before*
        # enumeration, so injected codes ride the exact deferred-detection /
        # attribution path a probe-latched fault would. None = no injection.
        self._injector = fault_injector
        # debug-guarded page-ledger verification (fuzzing/tests): check the
        # allocator invariant at every preempt/requeue and LFLR page-reclaim
        # site so ledger corruption surfaces at the mutation site instead of
        # steps later. Defaults to __debug__ (off under python -O).
        self._page_debug = bool(__debug__ if page_debug is None else page_debug)
        self.window = int(config.window)
        # ---- speculative decode windows (speculate=True) ------------------
        # draft-and-verify inside the fused window: up to draft_len+1 tokens
        # per full-model step, token-bit-exact vs the plain engine; the
        # commit loop consumes a per-(step, slot) accepted-count readback
        # instead of assuming K tokens per window (DESIGN.md §3.4)
        self.speculate = bool(config.speculate)
        self.draft_len = int(config.draft_len)
        self.draft_layers = int(config.draft_layers)
        # the window program and the layout it was built for: handed in by a
        # ServeGroup / fuzz kit (compiled once for many replicas), else
        # built here
        if programs is None:
            programs = build_engine(cfg, self.params, config, probe_cfg)
        self._decode_window = programs.window_fn
        # ---- paged KV/state pool (paged=True) ------------------------------
        # full-attention caches become one shared page pool addressed through
        # a (slots, max_pages) table; the allocator owns the free list and
        # the per-slot ownership ledger (DESIGN.md §3.3)
        self.paged = bool(config.paged)
        self.layout = programs.layout
        # a single-device replica lives where its params live: its caches go
        # to that device too (a ServeGroup puts each rank on its own chip)
        self.device = _home_device(self.params) if config.tp == 1 else None
        one = self.model.init_cache(1, max_len)
        if self.device is not None:
            one = jax.device_put(one, self.device)
        if self.paged:
            self.alloc = PageAllocator(self.layout.num_pages,
                                       self.layout.page_size,
                                       watermark=config.page_watermark)
            self.page_table = self.layout.empty_table(num_slots)
            self._scrub = jax.jit(self.layout.scrub, donate_argnums=(0,))
        else:
            self.alloc = None
        if self.paged and self.layout.has_paged_leaves:
            # a request that could never fit in the pool must be REJECTED at
            # submit, not deferred forever by the watermark gate
            pool_cap = min(max_len,
                           self.layout.num_pages * self.layout.page_size)
        else:
            pool_cap = max_len
        self.queue = queue or RequestQueue(
            AdmissionPolicy(max_total_len=pool_cap), clock=clock,
            tracer=self.trace)
        self.sched = ContinuousBatchingScheduler(
            num_slots, self.queue, replica=rank, eos_id=config.eos_id,
            clock=clock, prefill_budget=config.prefill_budget,
            can_admit=(self._can_admit if self.paged else None),
            on_release=(self._release_pages if self.paged else None))
        # stacked per-sequence (batch=1) caches, leading slot axis — or, when
        # paged, the hybrid tree (page pools + dense per-slot stacks)
        if self.paged:
            self.caches = self.layout.init_hybrid(one, num_slots)
        else:
            self.caches = jax.tree_util.tree_map(
                lambda v: jnp.broadcast_to(v[None],
                                           (num_slots, *v.shape)).copy(),
                one)
        if self.device is not None:
            self.caches = jax.device_put(self.caches, self.device)
        # ---- tensor parallelism (tp > 1) ------------------------------------
        # one replica = tp shards of a "model" mesh: params and cache leaves
        # are STORED sharded (rules.param_specs / tp_storage_specs), compute
        # stays replicated inside the shard_mapped window, and per-shard
        # error words are OR-folded across the axis so a fault on any shard
        # latches identically on all shards (DESIGN §3.8)
        self.tp = int(config.tp)
        self._tp_ctx: Optional[TPContext] = programs.tp
        if self._tp_ctx is not None:
            from jax.sharding import NamedSharding
            ctx = self._tp_ctx
            ns = lambda s: NamedSharding(ctx.mesh, s)  # noqa: E731
            self.params = jax.device_put(
                self.params, jax.tree_util.tree_map(ns, ctx.param_specs))
            self.caches = jax.device_put(
                self.caches, jax.tree_util.tree_map(ns, ctx.cache_specs))
        self._step_count = 0
        self._cycles = 0        # step() calls: the serve.step phase's index
        # speculation misses (DRAFT_REJECT) are attribution-only: strip them
        # from the fault-raising fold so they never reach wait()
        self._ignore_codes = (int(ErrorCode.DRAFT_REJECT)
                              if self.speculate else 0)
        self._wenum = make_window_enum_fn(num_slots, self._ignore_codes)
        # fresh per-sequence cache template + fused one-dispatch reset of one
        # lane's slice of the stacked caches — the admission/LFLR restart
        # point (async, never a host sync). In paged mode the reset covers
        # the dense leaves only; the paged half of the restart is the page
        # scrub at re-allocation.
        self._fresh = one
        reset = (self.layout.reset_slot if self.paged else
                 lambda full, fresh, slot: jax.tree_util.tree_map(
                     lambda f, o: f.at[slot].set(o.astype(f.dtype)),
                     full, fresh))
        self._reset = jax.jit(reset, donate_argnums=(0,))
        self._pending: Optional[_WindowInFlight] = None
        # device-resident feed for the next window (token chain never leaves
        # the device) + host-tracked dispatch positions. With speculation the
        # per-window advance is data-dependent (1..K*(D+1) tokens), so the
        # position chain ALSO lives on device (`_dev_pos_dev`, fed from window
        # N's outputs into window N+1 without a host sync); `_dev_pos` then
        # tracks the *retired* truth — updated from each window's accepted-
        # count readback — and is only used for host planning (page growth).
        self._dev_tokens = jnp.zeros((num_slots, 1, 1), jnp.int32)
        self._dev_pos = np.zeros((num_slots,), np.int32)
        self._dev_pos_dev = jnp.zeros((num_slots,), jnp.int32)
        self._set_pos = jax.jit(lambda arr, slot, v: arr.at[slot].set(v))

    # ------------------------------------------------------------- page ledger
    def _check_pages(self) -> None:
        """Debug-guarded ledger invariant: every pool page free or owned
        exactly once, right now. Called at the mutation sites (preempt,
        requeue, LFLR reclaim) so a corrupted ledger fails at the op that
        corrupted it, not at whatever later step happens to trip over it."""
        if self._page_debug and self.alloc is not None:
            self.alloc.check()

    def _can_admit(self, req: Request) -> bool:
        """Watermark admission: a fresh sequence joins only if its prompt's
        pages (plus the first generated position) fit with the configured
        headroom left free for in-flight lanes to grow into."""
        if not self.layout.has_paged_leaves:
            return True
        return self.alloc.can_admit(len(req.prompt) + 1)

    def _release_pages(self, slot: int) -> None:
        """Free a slot's pages and unmap its table row. Host bookkeeping only
        — the device chain still orders every dispatched read of these pages
        before the scrub that their next owner's allocation queues, so
        reclamation never stalls or races the in-flight window."""
        if self.alloc.owns(slot):
            freed = self.alloc.free_slot(slot)
            self.page_table[slot, :] = self.layout.sentinel
            self.metrics.record_pages(freed=len(freed),
                                      in_use=self.alloc.pages_in_use)
            if self.trace.enabled:
                self.trace.instant("page_free", "page", tid=slot, slot=slot,
                                   pages=len(freed),
                                   in_use=self.alloc.pages_in_use)

    def _oldest_active(self, exclude: frozenset[int]) -> Optional[int]:
        """Eviction victim: the oldest-arrival active lane that owns pages."""
        best = None
        for s in self.sched.slots:
            if not s.active or s.idx in exclude or not self.alloc.owns(s.idx):
                continue
            key = (s.req.arrival_t if s.req.arrival_t is not None
                   else float("inf"), s.idx)
            if best is None or key < best[0]:
                best = (key, s.idx)
        return None if best is None else best[1]

    def _evict_for_pages(self, victim: int) -> None:
        """Memory-pressure preemption: pull the victim's request out of its
        slot and put it back in the queue (progress discarded — it recomputes
        from the prompt on its next slot, exactly the ledger re-route
        contract: zero dropped requests). The in-flight speculative window's
        lane is invalidated so its stale block is skipped at retirement."""
        req = self.sched.preempt(victim)          # on_release frees the pages
        if self.trace.enabled:
            self.trace.instant("page_evict", "page", tid=victim, slot=victim,
                               trace_id=req.trace_id)
        self.queue.requeue(req)
        self.metrics.record_page_eviction()
        if self._pending is not None:
            self._pending.valid[victim] = False
        self._check_pages()

    def _grow_slot(self, slot: int, target_tokens: int, *,
                   exclude_self: bool = False) -> Optional[list[int]]:
        """Ensure ``slot`` owns pages covering ``target_tokens`` positions,
        evicting oldest lanes under pressure. Returns the newly allocated
        (unscrubbed) page ids, or None if ``slot`` itself was evicted.

        The target is clamped to the pool's token capacity, not just
        ``max_len``: window over-decode can push ``pos + K`` past what any
        lane may hold, and demanding pages that cannot exist would evict the
        whole fleet and livelock (positions past the clamp drop their writes
        and are discarded at retirement anyway)."""
        target = min(int(target_tokens), self.layout.capacity_tokens)
        while True:
            need = (self.alloc.pages_for(target)
                    - len(self.alloc.owned(slot)))
            if need <= 0:
                return []
            try:
                got = self.alloc.alloc(slot, need)
                break
            except PagePoolExhausted:
                victim = self._oldest_active(
                    frozenset((slot,)) if exclude_self else frozenset())
                if victim is None:
                    raise      # unreachable under the admission-policy clamp
                self._evict_for_pages(victim)
                if victim == slot:
                    return None
        # append-only: write just the new tail entries, never rewrite the
        # whole row — the device table is the mapping of record, and a silent
        # full-row rewrite would paper over exactly the ledger/table
        # divergence the in-band PAGE_FAULT probe exists to surface
        n_owned = len(self.alloc.owned(slot))
        self.page_table[slot, n_owned - len(got):n_owned] = got
        self.metrics.record_pages(allocated=len(got),
                                  in_use=self.alloc.pages_in_use)
        if self.trace.enabled:
            self.trace.instant("page_alloc", "page", tid=slot, slot=slot,
                               pages=len(got), in_use=self.alloc.pages_in_use)
        return got

    def _paged_prepare(self, plan: dict) -> None:
        """Pre-dispatch page maintenance for one window.

        1. **Lane (re)starts** (fresh chunk plans — admission or LFLR): free
           the lane's old pages (the LFLR page *reclaim*, a pure host ledger
           op) and reset its dense state on the device chain; its new pages
           are (re-)acquired in step 2 — this is the non-blocking
           free-and-reacquire lane of DESIGN.md §3.3.
        2. **Growth**: every lane that writes during this window must have
           the pages holding positions ``[pos, pos+K)`` mapped; exhaustion
           preempts oldest lanes into the queue (never a drop).
        3. **Scrub**: newly allocated pages are zeroed in one fused dispatch
           riding the device chain before the window, so recycled pages can
           never leak a previous owner's (possibly poisoned) state.
        """
        sched, K = self.sched, self.window
        for slot, cp in plan.items():
            if cp.rem == 0 or not cp.fresh:
                continue
            self._release_pages(slot)
            self._check_pages()
            self.caches = self._reset(self.caches, self._fresh,
                                      jnp.int32(slot))
            self._set_dev_pos(slot, 0)
        if not self.layout.has_paged_leaves:
            return
        deferred = {slot for slot, cp in plan.items() if cp.rem == 0}
        # speculation: a window advances a data-dependent 1..K*(D+1) tokens,
        # and the in-flight window's advance is unknown until its counts come
        # back — grow to the worst case (retired truth + in-flight horizon +
        # this window's horizon). Conservative by design: demanding a page
        # that goes unwritten wastes headroom; missing one latches PAGE_FAULT.
        horizon = K * (self.draft_len + 1) if self.speculate else K
        slack = (horizon if self.speculate and self._pending is not None
                 else 0)
        new_ids: list[int] = []
        for s in list(sched.slots):
            if not s.active or s.idx in deferred:
                continue
            got = self._grow_slot(s.idx,
                                  int(self._dev_pos[s.idx]) + horizon + slack)
            if got:
                new_ids.extend(got)
        if new_ids:
            # dedupe: an eviction inside the growth loop recycles ids, so the
            # same physical page can be granted twice within one prepare —
            # unique ids always fit the fixed-size staging buffer
            new_ids = list(dict.fromkeys(new_ids))
            ids = np.full((self.layout.num_pages,), self.layout.sentinel,
                          np.int32)
            ids[:len(new_ids)] = new_ids
            self.caches = self._scrub(self.caches, jnp.asarray(ids))

    # ------------------------------------------------------------ dev position
    def _set_dev_pos(self, slot: int, val: int) -> None:
        """Patch one lane's dispatch position: the host mirror always; the
        device-resident position chain too when speculating (it is the value
        window N+1 actually consumes — the patch rides the device chain like
        the cache reset it accompanies, never a sync)."""
        self._dev_pos[slot] = val
        if self.speculate:
            self._dev_pos_dev = self._set_pos(self._dev_pos_dev,
                                              jnp.int32(slot), jnp.int32(val))

    # ---------------------------------------------------------------- warmup
    def warmup(self, *, max_new: int = 8) -> None:
        """Compile every hot-path program before real traffic: one throwaway
        request end-to-end covers prefill (the fused variant compiles once
        for *all* lengths), decode/window and commit. Swaps in fresh metrics
        afterwards so compile time never pollutes reported numbers."""
        assert self.idle(), "warmup must run before traffic is admitted"
        req = Request(id=-1, prompt=(1, 2, 3),
                      max_new_tokens=min(max_new, self.max_len - 4))
        assert self.submit(req) is None
        self.run()
        self.metrics = ServeMetrics(clock=self.clock)
        self.trace.clear()       # compile-time spans would pollute the trace

    # ------------------------------------------------------------- submission
    def submit(self, req: Request) -> Optional[Response]:
        """Admit a request; returns a ``REJECTED`` response or None (accepted).
        Every accepted request is eventually answered by ``step``/``run``."""
        resp = self.queue.submit(req)
        if resp is not None:
            self.metrics.record_response(resp)
        return resp

    def readmit(self, req: Request) -> Optional[Response]:
        """Idempotent re-admission after a ledger replay (crash-restart).

        A request the write-ahead log proves was already *accepted* re-enters
        through the negative-sequence requeue lane: admission checks are
        bypassed (it was admitted once, the zero-drop contract owes it a
        terminal answer), it sorts ahead of its deadline class, and its
        original ``arrival_t``/``trace_id`` are preserved so latency spans
        the whole crash-recovery window and the post-mortem sees one causal
        chain across both incarnations of the fleet. Requests the log shows
        as submitted but never accepted go through normal admission."""
        if req.arrival_t is None:
            return self.submit(req)
        self.queue.requeue(req)
        return None

    def load(self) -> int:
        """Queued + in-flight requests — the group take-limit / autoscale
        pressure signal."""
        return len(self.queue) + self.sched.in_flight()

    # ---------------------------------------------------------- fault surface
    def inject_state_fault(self, slot: Optional[int] = None, *,
                           rng: Optional[np.random.Generator] = None
                           ) -> Optional[int]:
        """Simulated SDC (paper §II-A): NaN one element of a slot's recurrent
        state on device — or, for attention-only architectures, of the K
        entry at position 0 of the slot's (paged or contiguous) KV cache,
        which the non-finite-logits probe then latches. ``slot=None`` picks
        the first active slot — or a seeded-random active slot when ``rng``
        is given (``FaultSchedule.rng_for`` hands one out per (rank, step),
        so any randomized injection replays bit-for-bit from the schedule
        seed alone). Returns the poisoned slot, or None if there was nothing
        to poison (e.g. a paged lane holding no mapped page)."""
        if slot is None:
            active = self.sched.active_slots()
            if not active:
                return None
            slot = int(rng.choice(active)) if rng is not None else active[0]
        hit = []

        def poison(path, leaf):
            keys = [getattr(k, "key", None) for k in path]
            if any(k in ("h", "ssm") for k in keys) and leaf.ndim >= 1:
                hit.append(True)
                return leaf.at[(slot,) + (0,) * (leaf.ndim - 1)].set(jnp.nan)
            return leaf

        poisoned = jax.tree_util.tree_map_with_path(poison, self.caches)
        if hit:
            self.caches = poisoned
            return slot
        # attention-only arch: poison K at position 0 (always a written
        # position once the lane holds state, so the NaN reaches the scores)
        if self.paged and self.layout.has_paged_leaves:
            pid = int(self.page_table[slot, 0])
            if pid >= self.layout.num_pages:
                return None              # lane owns no page yet — nothing real

            def poison_pool(path, leaf):
                if hit or not self.layout.is_paged_path(path):
                    return leaf
                hit.append(True)
                return leaf.at[(pid,) + (0,) * (leaf.ndim - 1)].set(jnp.nan)

            poisoned = jax.tree_util.tree_map_with_path(poison_pool,
                                                        self.caches)
        else:

            def poison_kv(path, leaf):
                keys = [getattr(k, "key", None) for k in path]
                if (hit or not keys or keys[-1] != "k" or leaf.ndim < 4
                        or leaf.shape[leaf.ndim - 3] != self.max_len):
                    return leaf          # full-attention K leaves only
                hit.append(True)
                return leaf.at[(slot,) + (0,) * (leaf.ndim - 1)].set(jnp.nan)

            poisoned = jax.tree_util.tree_map_with_path(poison_kv,
                                                        self.caches)
        if not hit:
            raise ValueError(
                f"{self.cfg.name}: no recurrent state or full-attention KV "
                "to poison")
        self.caches = poisoned
        return slot

    def corrupt_page_table(self, slot: int) -> bool:
        """Deterministic ledger-divergence injection (fuzzing/tests): unmap a
        lane's device page-table row behind the allocator's back. The host
        ledger still says the slot owns its pages; the device's mapping of
        record says it owns nothing — exactly the corruption the in-band
        ``PAGE_FAULT`` probe exists to latch at the next write. Returns True
        iff there was a mapped row to corrupt."""
        if not (self.paged and self.layout.has_paged_leaves):
            return False
        if int(self.page_table[slot, 0]) >= self.layout.num_pages:
            return False                  # nothing mapped — nothing to diverge
        self.page_table[slot, :] = self.layout.sentinel
        return True

    def preempt_slot(self, slot: int) -> bool:
        """Deterministic preemption injection (fuzzing / external rebalance):
        pull ``slot``'s request out mid-flight and requeue it ahead of its
        class — the same zero-drop contract as the paged memory-pressure
        eviction, exposed as an explicit hook. The in-flight window's lane is
        invalidated (its block computed with the departed request's state)
        and the page ledger, if any, is verified at the mutation site.
        Returns True iff the slot held a request."""
        s = self.sched.slots[slot]
        if not s.active:
            return False
        req = self.sched.preempt(slot)    # on_release reclaims any pages
        self.queue.requeue(req)
        if self._pending is not None:
            self._pending.valid[slot] = False
        self._check_pages()
        return True

    def _injection_for(self, shape: tuple) -> Optional[np.ndarray]:
        """The injector's validated fault word(s) for this dispatch, or None
        when nothing is scheduled. Shape is the engine's word surface:
        ``(K, slots)``, or ``(tp, K, slots)`` tensor-parallel (shard-targeted
        injection — the TP kit's device mutation surface)."""
        if self._injector is None:
            return None
        inj = self._injector(self._step_count, shape)
        if inj is None:
            return None
        inj = np.asarray(inj, np.uint32)
        if inj.shape != shape:
            raise ValueError(
                f"fault_injector returned shape {inj.shape}, expected {shape}")
        bad = int(np.bitwise_or.reduce(inj, axis=None)) & ~int(
            _INJECTABLE_MASK)
        if bad:
            raise ValueError(
                f"fault_injector word {bad:#x} carries non-injectable bits "
                "(attribution-only / hard / undefined)")
        return inj

    def _inject_words(self, words, shape: tuple):
        """OR the injector's scheduled fault word(s) for this dispatch into
        the device error words, *before* masking/enumeration — an injected
        code is indistinguishable from a probe-latched one from that point
        on (deferred detection, (step, slot) attribution, recovery routing
        all run for real). No-op (and zero extra dispatches) without an
        injector. The TP engine does not use this host-side path: its
        injection rides INTO the shard_mapped window as a per-shard operand
        so it is folded across shards like a probe-latched word."""
        inj = self._injection_for(shape)
        if inj is None:
            return words
        return jnp.bitwise_or(words, jnp.asarray(inj))

    # ------------------------------------------------------------- step cycle
    def step(self) -> list[Response]:
        """One scheduler cycle: expire → backfill/prefill → fused decode →
        commit. Returns every request answered during the cycle.

        Traced, the cycle is the ``serve.step`` phase; its parts are the
        ``serve.admit`` / ``dispatch`` / ``wait`` / ``commit`` / ``recover``
        phases (``repro.obs.trace``)."""
        self._cycles += 1
        with self.trace.phase("serve.step") as ph:
            if self.trace.enabled:
                ph.note(step=self._cycles)
            with self.trace.phase("serve.admit") as adm:
                if self.trace.enabled:
                    adm.note(step=self._cycles)
                out = self._admit()
            self.metrics.record_active_slots(self.sched.in_flight())
            if self.sched.has_active() or self._pending is not None:
                out.extend(self._window_cycle())
            for resp in out:
                self.metrics.record_response(resp)
            if self.trace.enabled:
                t_done = self.clock()
                for resp in out:
                    self.trace.end_request(resp, t_done)
                self._sweep_recoveries(t_done)
        return out

    def _admit(self) -> list[Response]:
        """Expire what passed its deadline and backfill free slots; returns
        the answers. Admission is a background lane: the scheduler chunks
        each new prompt into the following windows — no blocking prefill."""
        now = self.clock()
        out: list[Response] = []
        for req in self.queue.drain_expired(now):
            out.append(Response(id=req.id, status=EXPIRED,
                                latency_s=now - req.arrival_t,
                                replica=self.rank,
                                detail="deadline passed in queue",
                                trace_id=req.trace_id))
        out.extend(self.sched.expire_active(now))
        for slot, _req in self.sched.backfill(now):
            if self.trace.enabled and _req.trace_id is not None:
                self.trace.instant("slot_assign", "sched", ts=now, tid=slot,
                                   trace_id=_req.trace_id, slot=slot)
            self.sched.begin_prefill(slot)
        return out

    def run(self, *, max_steps: int = 100_000) -> list[Response]:
        """Serve until the queue and all slots drain; returns all responses.

        Raises instead of returning if ``max_steps`` is exhausted with work
        still pending — an accepted request is never silently dropped.
        """
        out: list[Response] = []
        for _ in range(max_steps):
            if self.idle():
                return out
            out.extend(self.step())
        if not self.idle():
            raise RuntimeError(
                f"replica {self.rank}: {len(self.queue)} queued + "
                f"{self.sched.in_flight()} in-flight requests unanswered "
                f"after {max_steps} steps")
        return out

    def idle(self) -> bool:
        return (not len(self.queue) and not self.sched.has_active()
                and self._pending is None)

    # ------------------------------------------------------ recovery lanes (obs)
    def _trace_recovery_begin(self, slot: int, trace_id: Optional[int],
                              code: int, action: str, window: int,
                              now: float) -> None:
        """Open a recovery lane for ``slot`` (closing, as re-faulted, any lane
        the same slot already had open — its recompute never produced a
        healthy token before faulting again)."""
        old = self._recovering.pop(slot, None)
        if old is not None:
            self.trace.span("recovery", "recovery", old["t0"], now, tid=slot,
                            trace_id=old["trace_id"], slot=slot,
                            window=old["window"], action=old["action"],
                            code=old["code"], outcome="refaulted")
        if trace_id is None:
            return
        self._recovering[slot] = {"trace_id": trace_id, "t0": now,
                                  "code": code, "action": action,
                                  "window": window}

    def _trace_recovery_end(self, slot: int, trace_id: Optional[int],
                            now: float, outcome: str) -> None:
        """Close ``slot``'s recovery lane: the span runs from the recovery
        decision to the first healthy post-recovery token (outcome
        ``recovered``)."""
        ctx = self._recovering.get(slot)
        if ctx is None or ctx["trace_id"] != trace_id:
            return
        del self._recovering[slot]
        self.trace.span("recovery", "recovery", ctx["t0"], now, tid=slot,
                        trace_id=trace_id, slot=slot, window=ctx["window"],
                        action=ctx["action"], code=ctx["code"],
                        outcome=outcome)

    def _sweep_recoveries(self, now: float) -> None:
        """Close recovery lanes whose request left the slot without committing
        a post-recovery token (FAILED / EXPIRED / preempted): the request's
        terminal response is what resolves the fault; the abandoned lane span
        records that the recompute never finished."""
        for slot, ctx in list(self._recovering.items()):
            s = self.sched.slots[slot]
            if s.active and s.req.trace_id == ctx["trace_id"]:
                continue
            del self._recovering[slot]
            self.trace.span("recovery", "recovery", ctx["t0"], now, tid=slot,
                            trace_id=ctx["trace_id"], slot=slot,
                            window=ctx["window"], action=ctx["action"],
                            code=ctx["code"], outcome="abandoned")

    # --------------------------------------------------------- window engine
    def _window_cycle(self) -> list[Response]:
        """Double-buffered commit loop: dispatch window N+1 from window N's
        device-resident outputs *before* reading back window N's tokens."""
        prev = self._pending
        if self.sched.has_active():
            # window N (``prev``) stays pending while N+1 is planned: page
            # pressure may still invalidate its lanes
            with self.trace.phase("serve.dispatch") as ph:
                self._pending = self._dispatch_window(ph)
        else:
            self._pending = None
        return self._retire_window(prev) if prev is not None else []

    def _dispatch_window(self, ph) -> _WindowInFlight:
        """Plan, build and enqueue the next window; ``ph`` is the open
        ``serve.dispatch`` phase, which gets the window's lane counts."""
        self._step_count += 1
        sched = self.sched
        K = self.window
        t_disp = self.clock() if self.trace.enabled else 0.0
        # speculation: prompt feed rides the verify width, so one window can
        # consume up to K*(D+1) prompt tokens per lane
        chunk_width = (self.draft_len + 1) if self.speculate else 1
        plan = sched.plan_prefill(K * chunk_width)
        if self.paged:
            # page maintenance first: lane restarts recycle their pages, every
            # writing lane gets growth pages, eviction preempts under pressure
            # — all of it host bookkeeping + chained device ops, zero syncs
            self._paged_prepare(plan)
        mask = sched.active_mask()
        start = np.zeros(sched.num_slots, np.int64)
        start_row = np.zeros(sched.num_slots, np.int64)
        rem0 = np.zeros(sched.num_slots, np.int64)
        deferred = np.zeros(sched.num_slots, bool)
        # jnp.array copies: on the CPU, jnp.asarray may alias a numpy buffer,
        # and the host edits the table in place while this window is in
        # flight (page reclaim, growth)
        extra = ((jnp.array(self.page_table),) if self.paged else ())
        if self.tp > 1:
            # per-shard injection rides into the shard_mapped window as its
            # trailing (tp, K, S) operand: each shard ORs its slice into its
            # local words BEFORE the cross-shard fold, so an injected word —
            # like a probe-latched one — latches identically on every shard
            inj = self._injection_for((self.tp, K, sched.num_slots))
            if inj is None:
                inj = np.zeros((self.tp, K, sched.num_slots), np.uint32)
            extra = extra + (jnp.asarray(inj),)
        chunk = np.zeros((K, chunk_width, sched.num_slots), np.int32)
        rem = np.zeros((sched.num_slots,), np.int32)
        for slot, cp in plan.items():
            if not sched.slots[slot].active:
                continue            # preempted by the page-pressure loop
            if cp.rem == 0:
                # deferred fresh lane: no valid state yet — fully masked
                mask[slot] = 0
                start[slot] = K
                deferred[slot] = True
                continue
            if cp.fresh and not self.paged:
                # lane (re)start: fresh cache slice + position 0, both
                # queued on the device chain — never a host sync (the
                # paged engine did this in _paged_prepare, plus the page
                # free/re-acquire/scrub that replaces the slab reset)
                self.caches = self._reset(self.caches, self._fresh,
                                          jnp.int32(slot))
                self._set_dev_pos(slot, 0)
            chunk.reshape(K * chunk_width,
                          sched.num_slots)[:cp.rem, slot] = cp.tokens
            rem[slot] = cp.rem
            rem0[slot] = cp.rem
            if cp.exhausts:
                # flip point: the argmax after the last prompt token is
                # the first committable token — step kf, verify row rf
                kf = (cp.rem - 1) // chunk_width
                start[slot] = kf
                start_row[slot] = (cp.rem - 1) - kf * chunk_width
            else:
                start[slot] = K
            self.metrics.record_chunk(cp.rem)
            if self.trace.enabled:
                tr = sched.slots[slot].req.trace_id
                if tr is not None:
                    self.trace.instant(
                        "chunk", "prefill", ts=t_disp, tid=slot,
                        trace_id=tr, slot=slot, tokens=cp.rem,
                        fresh=cp.fresh, exhausts=cp.exhausts,
                        window=self._step_count)
        if self.speculate:
            # device-resident position chain: the per-window advance is
            # data-dependent, so window N+1 reads window N's next_pos
            # without the host ever seeing it
            toks, counts, words, next_tok, next_pos, caches = (
                self._decode_window(
                    self.params, self.caches, self._dev_tokens,
                    self._dev_pos_dev, jnp.asarray(chunk),
                    jnp.asarray(rem), *extra))
            self._dev_pos_dev = next_pos
            outputs = (toks, counts)
        else:
            # the plain window feeds one prompt token per step
            toks, words, *routed, next_tok, caches = self._decode_window(
                self.params, self.caches, self._dev_tokens,
                jnp.asarray(self._dev_pos), jnp.asarray(chunk[:, 0, :]),
                jnp.asarray(rem), *extra)
            outputs = (toks, *routed) if routed else toks
        # the device-side chain advances: window N+1 consumes these directly
        self.caches = caches
        self._dev_tokens = next_tok
        if not self.speculate:
            self._dev_pos = self._dev_pos + K
        if self.tp <= 1:
            # TP injection already rode the window (pre-fold, device-side)
            words = self._inject_words(words, (K, sched.num_slots))
        combined, count, table, hist = self._wenum(words, jnp.asarray(mask))
        fut = DeviceFuture(outputs=outputs, word=combined, count=count,
                           table=table, history=hist)
        if self.trace.enabled:
            ph.note(window=self._step_count, slots=sched.num_slots,
                    lanes=sched.in_flight(),
                    prefill_lanes=int(np.count_nonzero(rem0)),
                    prompt_tokens=int(rem0.sum()))
        return _WindowInFlight(
            fut=fut,
            req_ids=tuple(s.req.id if s.active else None for s in sched.slots),
            valid=np.ones(sched.num_slots, bool),
            start=start,
            start_row=start_row if self.speculate else None,
            rem0=rem0 if self.speculate else None,
            deferred=deferred if self.speculate else None,
            lanes=mask.astype(bool) if self.cfg.is_moe else None,
            t_dispatch=t_disp, index=self._step_count,
            trace_ids=(tuple(s.req.trace_id if s.active else None
                             for s in sched.slots)
                       if self.trace.enabled else ()))

    def _retire_window(self, win: _WindowInFlight) -> list[Response]:
        with self.trace.phase("serve.wait") as ph:
            ready = win.fut.done()
            if not ready:
                # the device is still computing this window at its retirement
                # — the pipeline, not the host, is the bottleneck right now
                self.metrics.record_window_wait()
            if self.trace.enabled:
                ph.note(window=win.index, ready=ready)
            exc = None
            try:
                win.fut.wait()
            except PropagatedError as e:
                exc = e
            if self.trace.enabled:
                self.trace.span("window", "window", win.t_dispatch,
                                self.clock(), window=win.index,
                                faulted=exc is not None)
            # a faulted window's tokens are read too: its clean prefix commits
            block = jax.device_get(win.fut.outputs)
        counts = None
        if self.speculate:
            toks, counts = (np.asarray(x) for x in block)
        elif self.cfg.is_moe:
            toks, win.routed = (np.asarray(x) for x in block)
        else:
            toks = np.asarray(block)
        if exc is not None:
            return self._recover_window(win, exc, toks, counts)
        if self.speculate:
            self._note_advance(win, counts)
        return self._commit_window(win, toks, counts=counts)

    def _note_advance(self, win: _WindowInFlight, counts: np.ndarray,
                      metric_limits: Optional[np.ndarray] = None) -> None:
        """Fold a retired speculative window's accepted counts into the host
        position mirror — the only place the host learns how far the device
        chain actually advanced. Lanes that were patched mid-flight or have
        changed owner are skipped: their device position was (or will be)
        reset on the chain, and the mirror was reset with it. Also derives
        the drafted/accepted speculation counters from the counts block:
        step k of a lane with ``rem0`` prompt tokens force-feeds
        ``f_k = max(clip(rem0 - k·(D+1), 0, D+1), 1)`` rows, drafts the
        remaining ``D+1 - f_k``, and accepted drafts are whatever the counts
        show beyond the forced rows. ``metric_limits`` (per-slot first
        faulting step, from the fault path) caps the *counters* — steps at
        and past a real fault ran on corrupted state, so their
        accepts/rejects are noise that must not skew acceptance rates — while
        the position mirror always folds the full window (the device chain
        advanced through every step regardless)."""
        D1 = self.draft_len + 1
        K = self.window
        drafted = accepted = 0
        per_slot: dict[int, tuple[int, int]] = {}
        for slot, rid in enumerate(win.req_ids):
            if rid is None or not win.valid[slot] or win.deferred[slot]:
                continue
            s = self.sched.slots[slot]
            if s.active and s.req.id == rid:
                self._dev_pos[slot] += int(counts[:, slot].sum())
            lim = K if metric_limits is None else int(metric_limits[slot])
            rem = int(win.rem0[slot])
            forced = np.maximum(np.clip(rem - np.arange(lim) * D1, 0, D1), 1)
            d = int((D1 - forced).sum())
            a = int(counts[:lim, slot].sum() - forced.sum())
            if d > 0:
                drafted += d
                accepted += a
                per_slot[slot] = (d, a)
        if drafted:
            self.metrics.record_spec(drafted, accepted, per_slot)
            if self.trace.enabled:
                self.trace.instant("speculate", "spec", window=win.index,
                                   drafted=drafted, accepted=accepted)

    def _flat_block(self, win: _WindowInFlight, toks: np.ndarray,
                    counts: np.ndarray, slot: int, lo: int,
                    hi: int) -> list[int]:
        """Flatten a speculative lane's committable tokens: window steps
        ``lo .. hi-1``, each contributing its accepted rows — starting at the
        lane's flip row in its flip step (earlier rows are prompt-position
        argmaxes, fed not generated)."""
        out = []
        for k in range(lo, hi):
            j0 = int(win.start_row[slot]) if k == lo else 0
            out.extend(int(toks[k, slot, j])
                       for j in range(j0, int(counts[k, slot])))
        return out

    def _commit_window(self, win: _WindowInFlight, toks: np.ndarray,
                       limits: Optional[np.ndarray] = None,
                       counts: Optional[np.ndarray] = None) -> list[Response]:
        """Commit each lane's token block from its first real step
        (``win.start`` — past any prompt-chunk feed) up to EOS / token budget /
        its fault boundary (``limits``, in window steps); trailing tokens are
        discarded. Lanes whose request left the slot since dispatch (finished,
        expired, re-routed) or whose state was patched mid-flight (``valid``
        cleared) are skipped. With speculation (``counts`` given) a window
        step contributes its variable accepted prefix instead of one token —
        the variable-commit contract of DESIGN.md §3.4."""
        with self.trace.phase("serve.commit") as ph:
            now = self.clock()
            K = self.window
            out: list[Response] = []
            committed = discarded = 0
            for slot, rid in enumerate(win.req_ids):
                if rid is None:
                    continue                     # lane was free at dispatch
                lo = int(win.start[slot])        # prompt-feed steps emit no
                s = self.sched.slots[slot]       # committable tokens
                if counts is None:
                    emitted = K - lo
                else:
                    # the flip step's leading prompt rows are fed, not
                    # generated
                    emitted = max(int(counts[lo:, slot].sum())
                                  - int(win.start_row[slot]), 0)
                if not s.active or s.req.id != rid or not win.valid[slot]:
                    discarded += emitted
                    continue
                limit = K if limits is None else int(limits[slot])
                if limit <= lo:
                    block = []
                elif counts is None:
                    block = toks[lo:limit, slot]
                else:
                    block = self._flat_block(win, toks, counts, slot, lo,
                                             limit)
                if self.trace.enabled:
                    # capture before commit: a finishing lane clears its slot
                    tr = s.req.trace_id
                    first_before = s.t_first
                k, done = (self.sched.commit_block(slot, block, now)
                           if len(block) else (0, None))
                committed += k
                discarded += emitted - k
                if self.trace.enabled and tr is not None:
                    self.trace.span("decode", "window", win.t_dispatch, now,
                                    tid=slot, trace_id=tr, window=win.index,
                                    committed=k, discarded=emitted - k)
                    if k and first_before is None:
                        self.trace.instant("first_token", "request", ts=now,
                                           tid=slot, trace_id=tr)
                    if k:
                        self._trace_recovery_end(slot, tr, now, "recovered")
                if done is not None:
                    out.append(done)
            self.metrics.record_window(committed, discarded, K)
            if self.trace.enabled:
                ph.note(window=win.index, committed=committed,
                        discarded=discarded)
                if win.routed is not None:
                    # rows routed to each held expert by the lanes that ran
                    # a request, over the window's steps and the layers
                    per = win.routed[:, win.lanes].sum(axis=(0, 1))
                    ph.note(moe_pairs=int(per.sum()),
                            moe_pairs_max=int(per.max()))
        return out

    def _recover_window(self, win: _WindowInFlight, exc: PropagatedError,
                        toks: np.ndarray,
                        counts: Optional[np.ndarray]) -> list[Response]:
        """Deferred-detection recovery: the ``(K, slots)`` history attributes
        the fault to its exact ``(step, slot)``; the clean prefix before the
        fault step commits (it is part of the deterministic greedy trajectory)
        and only the faulted suffix is recomputed via LFLR re-prefill.

        Traced, the attribution and the LFLR lanes are two ``serve.recover``
        phases on either side of the prefix's ``serve.commit``."""
        with self.trace.phase("serve.recover") as ph:
            if self.trace.enabled:
                ph.note(window=win.index)
            plan = self._attribute_fault(win, exc, counts)
        if plan is None:
            return self._commit_window(win, toks, counts=counts)
        limits, *lanes = plan
        out = self._commit_window(win, toks, limits=limits, counts=counts)
        with self.trace.phase("serve.recover") as ph:
            if self.trace.enabled:
                ph.note(window=win.index)
            out.extend(self._restart_lanes(win, *lanes))
        return out

    def _attribute_fault(self, win: _WindowInFlight, exc: PropagatedError,
                         counts: Optional[np.ndarray]) -> Optional[tuple]:
        """Attribute a retired window's fault and decide the recovery:
        ``(limits, faulted, decision, codes)``, with ``limits`` each lane's
        committable steps, or None when every attributed lane was already
        patched (a stale fault: the whole window commits)."""
        num_slots = self.sched.num_slots
        K = self.window
        faulted = sorted({e.rank for e in exc.errors if 0 <= e.rank < num_slots})
        if not faulted:                      # unattributed word: assume all
            faulted = list(self.sched.active_slots())
        # a lane patched while this window was in flight re-reports its old
        # fault (the window *computed* with the poisoned state even though the
        # state has since been repaired) — stale, already recovered: drop it
        faulted = [s for s in faulted if win.valid[s]]
        if not faulted:
            if self.speculate:
                self._note_advance(win, counts)
            return None
        # first *faulting* step per slot: attribution-only lanes (speculation
        # misses) are masked out, so a rejected draft never truncates the
        # clean committable prefix — and a real fault mid-speculation drops
        # every token from its step on (no stale draft tokens commit)
        steps = win.fut.fault_steps(ignore=self._ignore_codes)
        limits = np.full(num_slots, K, np.int64)
        for slot in faulted:
            limits[slot] = steps[slot] if steps is not None and steps[slot] >= 0 else 0
        if self.speculate:
            # counters capped at each lane's fault boundary: post-fault steps
            # ran on corrupted state and must not skew acceptance rates
            self._note_advance(win, counts, metric_limits=limits)
        decision = self.policy.decide(exc, self._step_count)
        self.metrics.record_fault(self._step_count, int(exc.combined_code),
                                  decision.action.value, tuple(faulted))
        # per-slot exact error words from the (K, slots) history OR-fold:
        # unlike the enumeration table it never truncates, so both the paged
        # ledger repair and the fault spans can attribute every slot even
        # under an enumeration-saturating burst
        codes = (win.fut.fault_codes()
                 if (self.paged or self.trace.enabled) else None)
        if self.paged:
            # page-ownership faults get their own ledger record: the LFLR
            # re-queue repairs them too (free + re-acquire rebuilds the
            # mapping), but a PAGE_FAULT means the host ledger and device
            # table diverged — worth counting separately from soft faults
            page_slots = tuple(
                s for s in faulted if codes is not None
                and int(codes[s]) & int(ErrorCode.PAGE_FAULT))
            if page_slots:
                self.metrics.record_fault(self._step_count,
                                          int(ErrorCode.PAGE_FAULT),
                                          "page_reclaim", page_slots)
        if self.trace.enabled:
            # one fault event per attributed slot, carrying the slot's exact
            # error word (bit-for-bit what fault_codes() read back) and the
            # (window, step) the history latched it at — the detection edge
            # of the causal chain
            t_fault = self.clock()
            for slot in faulted:
                tr = win.trace_ids[slot] if win.trace_ids else None
                word = (int(codes[slot]) if codes is not None
                        else int(exc.combined_code))
                step_i = (int(steps[slot])
                          if steps is not None and steps[slot] >= 0 else None)
                self.trace.instant(
                    "fault", "fault", ts=t_fault, tid=slot, trace_id=tr,
                    slot=slot, window=win.index, step=step_i, code=word,
                    code_names=[c.name for c in ErrorCode(word).classes()],
                    action=decision.action.value)
            if self.tp > 1:
                # reconciliation fan-out: the OR-folded word latched on EVERY
                # shard of the model mesh — one instant per shard, so the
                # post-mortem can check that no shard missed (or diverged
                # from) the fault its peers recovered from
                for shard in range(self.tp):
                    self.trace.instant(
                        "shard_fanout", "shard", ts=t_fault,
                        tid=SHARD_TID + shard, shard=shard, tp=self.tp,
                        window=win.index, code=int(exc.combined_code))
        return limits, faulted, decision, codes

    def _restart_lanes(self, win: _WindowInFlight, faulted: list,
                       decision, codes) -> list[Response]:
        """After the clean prefix committed: fail the lanes past their retry
        budget and send the rest through LFLR (every active lane on a
        ROLLBACK)."""
        if decision.action is Action.ROLLBACK:
            targets, fail_now = list(self.sched.active_slots()), False
        elif decision.action is Action.ABORT:
            targets, fail_now = faulted, True
        else:   # SKIP_BATCH / RESTORE_GOOD / CONTINUE / ... → per-sequence LFLR
            targets, fail_now = faulted, False
        out: list[Response] = []
        faulted_set = set(faulted)
        for slot in targets:
            s = self.sched.slots[slot]
            if not s.active or s.req.id != win.req_ids[slot]:
                continue                     # finished/evicted inside its prefix
            if slot in faulted_set:
                retries = self.sched.note_retry(slot)
            else:
                retries = self.sched.request(slot).retries
            if fail_now or retries > self.max_request_retries:
                out.append(self.sched.evict(
                    slot, FAILED,
                    detail=f"{decision.reason} (retries={retries})"))
                if self._pending is not None:
                    # the in-flight speculative window computed with the same
                    # poisoned state; without a prefill patch clearing it, its
                    # lane would re-raise this fault as a new one at retire
                    self._pending.valid[slot] = False
                continue
            if self.trace.enabled:
                word = (int(codes[slot]) if codes is not None
                        and slot in faulted_set else 0)
                self._trace_recovery_begin(
                    slot, s.req.trace_id, word, decision.action.value,
                    win.index, self.clock())
            self._lflr_slot(slot)            # LFLR: recompute, don't restart
        return out

    def _lflr_slot(self, slot: int) -> None:
        """LFLR recompute for one lane: re-queue it — the scheduler chunks
        prompt + committed tokens back into the cache through subsequent
        fused windows (the cache reset rides the next dispatch), and the
        in-flight window's stale lane is invalidated. The host never
        blocks."""
        self.sched.begin_prefill(slot)
        if self._pending is not None:
            self._pending.valid[slot] = False
