"""Serving metrics: per-request latency, throughput, fault counters.

Feeds the same :class:`~repro.core.resilient.EventLog` record the training
executor uses, so one post-mortem tool reads both training and serving runs.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Optional

import numpy as np

from ..core.errors import ErrorCode
from ..core.resilient import Event, EventLog
from .queue import OK, Response


@dataclass
class FaultRecord:
    step: int
    code: int
    action: str
    slots: tuple[int, ...] = ()
    t: float = 0.0               # wall clock (metrics clock) of detection


class ServeMetrics:
    """Thread-safe accumulator for one replica (or a whole group)."""

    def __init__(self, clock=time.monotonic):
        self._lock = threading.Lock()
        self.clock = clock
        self.responses: list[Response] = []
        self._resp_t: list[float] = []       # completion wall time per response
                                             # (Response is frozen and carries
                                             # only the latency, so the stamp
                                             # lives here, index-aligned)
        self.faults: list[FaultRecord] = []
        self.decode_steps = 0
        self.decode_tokens = 0               # all committed tokens
        self._t0: Optional[float] = None
        self._t_last: Optional[float] = None
        self.windows = 0                     # decode windows retired
        self.discarded_tokens = 0            # trailing tokens dropped at window
                                             # boundaries (EOS/budget/fault)
        self.prefill_chunks = 0              # prompt chunks fused into windows
        self.prefill_chunk_tokens = 0        # prompt tokens fed via chunks
        self.window_waits = 0                # windows not yet done at retire
                                             # (device-bound, host keeping up)
        self.pages_allocated = 0             # paged KV: pool pages granted
        self.pages_freed = 0                 # paged KV: pool pages reclaimed
        self.page_evictions = 0              # paged KV: lanes preempted +
                                             # requeued under memory pressure
        self.peak_pages_in_use = 0           # paged KV: high-water pool usage
        self.peak_active_slots = 0           # most lanes concurrently serving
                                             # (the paged capacity headline)
        self.draft_tokens = 0                # speculation: tokens proposed by
                                             # the shallow-exit drafter
        self.accepted_draft_tokens = 0       # ... accepted by the full-model
                                             # verify (DRAFT_REJECT lane
                                             # carries the misses in-band)
        self._spec_per_slot: dict[int, list] = {}   # slot -> [drafted, accepted]

    # ------------------------------------------------------------- recording
    def record_window(self, committed_tokens: int, discarded_tokens: int,
                      window: int) -> None:
        """One retired decode window: K deferred device steps, one host sync."""
        with self._lock:
            self._tick()
            self.windows += 1
            self.decode_steps += window
            self.decode_tokens += committed_tokens
            self.discarded_tokens += discarded_tokens

    def record_chunk(self, tokens_fed: int) -> None:
        """A prompt chunk fused into a decode window (overlapped prefill)."""
        with self._lock:
            self._tick()
            self.prefill_chunks += 1
            self.prefill_chunk_tokens += tokens_fed

    def record_window_wait(self) -> None:
        """A window that was still computing when the host came to retire it."""
        with self._lock:
            self.window_waits += 1

    def record_pages(self, *, allocated: int = 0, freed: int = 0,
                     in_use: int = 0) -> None:
        """Paged-KV ledger movement (allocation / reclamation + high-water)."""
        with self._lock:
            self.pages_allocated += allocated
            self.pages_freed += freed
            self.peak_pages_in_use = max(self.peak_pages_in_use, in_use)

    def record_spec(self, drafted: int, accepted: int,
                    per_slot: Optional[dict] = None) -> None:
        """One retired speculative window's draft/verify outcome. ``per_slot``
        maps slot -> (drafted, accepted) so acceptance is attributable per
        lane (a single always-rejecting sequence shows up here, not just as a
        diluted global average)."""
        with self._lock:
            self.draft_tokens += drafted
            self.accepted_draft_tokens += accepted
            for slot, (d, a) in (per_slot or {}).items():
                cell = self._spec_per_slot.setdefault(slot, [0, 0])
                cell[0] += d
                cell[1] += a

    def record_page_eviction(self) -> None:
        """A lane preempted (and requeued) to free pages under pressure."""
        with self._lock:
            self.page_evictions += 1

    def record_active_slots(self, n: int) -> None:
        """Concurrent-lane gauge; the peak is the paged capacity headline."""
        with self._lock:
            self.peak_active_slots = max(self.peak_active_slots, n)

    def _tick(self) -> None:
        now = self.clock()
        if self._t0 is None:
            self._t0 = now
        self._t_last = now

    def record_response(self, resp: Response) -> None:
        with self._lock:
            self.responses.append(resp)
            self._resp_t.append(self.clock())

    def record_fault(self, step: int, code: int | ErrorCode, action: str,
                     slots: tuple[int, ...] = ()) -> None:
        with self._lock:
            self.faults.append(FaultRecord(step, int(code), action, slots,
                                           t=self.clock()))

    # --------------------------------------------------------------- queries
    def by_status(self) -> dict[str, int]:
        with self._lock:
            out: dict[str, int] = {}
            for r in self.responses:
                out[r.status] = out.get(r.status, 0) + 1
            return out

    def fault_counts(self) -> dict[str, int]:
        """Faults keyed by ErrorCode class name (a combined word may count
        several classes)."""
        with self._lock:
            out: dict[str, int] = {}
            for f in self.faults:
                for cls in ErrorCode(f.code).classes() or [ErrorCode.OK]:
                    out[cls.name] = out.get(cls.name, 0) + 1
            return out

    def tokens_per_s(self) -> float:
        """Committed tokens per wall second. Already speculation-adjusted:
        only tokens the verify accepted and the scheduler committed count —
        drafted-but-rejected work never inflates throughput."""
        with self._lock:
            if self._t0 is None or self._t_last is None or self._t_last <= self._t0:
                return 0.0
            return self.decode_tokens / (self._t_last - self._t0)

    def tokens_per_step(self) -> float:
        """Committed tokens per *dispatched* decode step (a window counts K
        steps; each step serves every slot, so multi-slot batching alone
        yields up to ``num_slots``). The speculation headline is therefore a
        same-slot-count comparison: draft-and-verify lifts this ratio above
        the plain engine's on identical traffic."""
        with self._lock:
            if not self.decode_steps:
                return 0.0
            return self.decode_tokens / self.decode_steps

    def acceptance_rate(self) -> float:
        """Fraction of drafted tokens the full-model verify accepted."""
        with self._lock:
            if not self.draft_tokens:
                return 0.0
            return self.accepted_draft_tokens / self.draft_tokens

    def acceptance_rate_per_slot(self) -> dict[int, float]:
        with self._lock:
            return {slot: (a / d if d else 0.0)
                    for slot, (d, a) in sorted(self._spec_per_slot.items())}

    def latency_percentiles(self, ps=(50, 99)) -> dict[str, float]:
        with self._lock:
            lats = [r.latency_s for r in self.responses if r.status == OK]
        if not lats:
            return {f"p{p}": float("nan") for p in ps}
        arr = np.asarray(lats)
        return {f"p{p}": float(np.percentile(arr, p)) for p in ps}

    def ttft_percentiles(self, ps=(50, 99)) -> dict[str, float]:
        """Time-to-first-token percentiles over answered requests (the number
        overlapped admission optimises: the first token of a late-admitted
        request must not wait for a blocking full-prompt prefill)."""
        with self._lock:
            tt = [r.ttft_s for r in self.responses
                  if r.status == OK and r.ttft_s is not None]
        if not tt:
            return {f"p{p}": float("nan") for p in ps}
        arr = np.asarray(tt)
        return {f"p{p}": float(np.percentile(arr, p)) for p in ps}

    def summary(self) -> dict:
        out = {
            "requests": len(self.responses),
            "statuses": self.by_status(),
            "decode_steps": self.decode_steps,
            "decode_tokens": self.decode_tokens,
            "windows": self.windows,
            "discarded_tokens": self.discarded_tokens,
            "prefill_chunks": self.prefill_chunks,
            "prefill_chunk_tokens": self.prefill_chunk_tokens,
            "window_waits": self.window_waits,
            "pages_allocated": self.pages_allocated,
            "pages_freed": self.pages_freed,
            "page_evictions": self.page_evictions,
            "peak_pages_in_use": self.peak_pages_in_use,
            "peak_active_slots": self.peak_active_slots,
            "draft_tokens": self.draft_tokens,
            "accepted_draft_tokens": self.accepted_draft_tokens,
            "rejected_draft_tokens": (self.draft_tokens
                                      - self.accepted_draft_tokens),
            "acceptance_rate": self.acceptance_rate(),
            "acceptance_rate_per_slot": self.acceptance_rate_per_slot(),
            "tokens_per_step": self.tokens_per_step(),
            "tokens_per_s": self.tokens_per_s(),
            "faults": self.fault_counts(),
            "retries": sum(r.retries for r in self.responses),
        }
        out.update({f"latency_{k}_s": v
                    for k, v in self.latency_percentiles().items()})
        out.update({f"ttft_{k}_s": v
                    for k, v in self.ttft_percentiles().items()})
        return out

    # --------------------------------------------------------------- export
    def to_event_log(self) -> EventLog:
        """EventLog-style record: requests as ok/fault events, faults with the
        recovery action taken — same shape the training executor emits.

        Every event carries its real wall-clock stamp ``t`` (the metrics
        clock): a fault's detection time, a response's completion time (its
        span starts ``latency_s`` earlier, at the request's arrival). The
        merged log is emitted in wall order so interleaving several logs —
        training + serving, or one per replica — sorts causally; a request's
        ``step`` is its dispatch position, the engine step a fault names."""
        log = EventLog()
        with self._lock:
            entries = [(f.t, Event(step=f.step, kind="fault", code=f.code,
                                   action=f.action,
                                   detail=f"slots={list(f.slots)}", t=f.t))
                       for f in self.faults]
            resp_order = sorted(zip(self._resp_t, self.responses),
                                key=lambda p: p[0])
            entries += [(t, Event(step=i,
                                  kind="ok" if r.status == OK else "fault",
                                  detail=f"request {r.id}: {r.status}",
                                  duration_s=r.latency_s, t=t))
                        for i, (t, r) in enumerate(resp_order)]
        for _, ev in sorted(entries, key=lambda p: p[0]):
            log.add(ev)
        return log

    # ---------------------------------------------------------------- merging
    @classmethod
    def merged(cls, parts: "list[ServeMetrics]") -> "ServeMetrics":
        """One accumulator equivalent to the union of ``parts`` (e.g. a
        ServeGroup's per-replica metrics): counters sum, peaks take the max,
        responses and faults pool (so percentiles are computed over the whole
        fleet's population, not averaged per replica), and the wall window
        spans min ``t0`` to max ``t_last`` — fleet tokens/s is total tokens
        over the fleet's wall span, replicas being concurrent."""
        out = cls()
        for m in parts:
            with m._lock:
                out.responses.extend(m.responses)
                out._resp_t.extend(m._resp_t)
                out.faults.extend(m.faults)
                out.decode_steps += m.decode_steps
                out.decode_tokens += m.decode_tokens
                out.windows += m.windows
                out.discarded_tokens += m.discarded_tokens
                out.prefill_chunks += m.prefill_chunks
                out.prefill_chunk_tokens += m.prefill_chunk_tokens
                out.window_waits += m.window_waits
                out.pages_allocated += m.pages_allocated
                out.pages_freed += m.pages_freed
                out.page_evictions += m.page_evictions
                out.peak_pages_in_use = max(out.peak_pages_in_use,
                                            m.peak_pages_in_use)
                out.peak_active_slots = max(out.peak_active_slots,
                                            m.peak_active_slots)
                out.draft_tokens += m.draft_tokens
                out.accepted_draft_tokens += m.accepted_draft_tokens
                for slot, (d, a) in m._spec_per_slot.items():
                    cell = out._spec_per_slot.setdefault(slot, [0, 0])
                    cell[0] += d
                    cell[1] += a
                if m._t0 is not None:
                    out._t0 = (m._t0 if out._t0 is None
                               else min(out._t0, m._t0))
                if m._t_last is not None:
                    out._t_last = (m._t_last if out._t_last is None
                                   else max(out._t_last, m._t_last))
        return out
