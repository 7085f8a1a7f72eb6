"""Drive a ``Replica`` through a traffic plan on the host clock.

The loop offers the plan's requests when they are due (open loop) or when
their client is free (closed loop), calls ``Replica.step`` while there is
work, injects the plan's soft state faults, and after the window has closed
waits up to ``drain_s`` for every answer still owed. It touches the replica
through ``submit``, ``step``, ``idle``, ``inject_state_fault``, the
``ServeMetrics`` counters and, for faults only, the slot table that
``inject_state_fault`` itself chooses from.

Times, all on the benchmark's clock:

- ``t_due``: when a request was due (open: its schedule; closed: when its
  client was answered);
- ``t_submit``: when ``submit`` was called;
- ``t_done``: when the ``step`` that answered it returned.

A fault's recovery runs from its injection to the end of the first ``step``
after its detection in which the request it hit committed a further token
(or was answered).
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

import numpy as np

from repro.serve import Request


@dataclass
class Record:
    rid: int
    prompt_len: int
    t_due: float
    t_submit: float = float("nan")
    t_done: float = float("nan")
    status: str = "unanswered"
    ttft_s: Optional[float] = None     # submit → first token (the engine's)
    tokens: tuple = ()


@dataclass
class Fault:
    t_inj: float
    slot: int
    rid: int
    t_det: Optional[float] = None
    n_at_det: int = 0
    t_heal: Optional[float] = None


@dataclass
class Run:
    records: dict = field(default_factory=dict)     # rid -> Record
    faults: list = field(default_factory=list)
    t_open: float = 0.0
    t_close: float = 0.0
    # counter snapshots at the first step boundary at/after open and close
    snap_open: Optional[tuple] = None
    snap_close: Optional[tuple] = None
    steps: int = 0
    step_s: float = 0.0          # wall seconds inside Replica.step
    windows: int = 0             # retired inside the window
    windows_total: int = 0       # retired in the whole run
    late_s: list = field(default_factory=list)      # submit − due (open)
    prompts: dict = field(default_factory=dict)     # rid -> prompt


def _counters(m, clock) -> tuple:
    return (clock(), m.decode_tokens, m.prefill_chunk_tokens, m.windows,
            len(m.faults))


def _eligible(replica, K: int) -> list:
    """Slots whose state the next window consumes, with room for the fault
    to surface before the request could finish: active, past their first
    chunk, and (when decoding) at least two windows of budget left."""
    out = []
    for s in replica.sched.slots:
        if not s.active:
            continue
        if s.pending is not None:
            if s.prefill_pos == 0:
                continue          # its next chunk resets the lane anyway
        elif len(s.generated) + 2 * K + 1 > s.req.max_new_tokens:
            continue
        out.append(s.idx)
    return out


def drive(replica, plan, *, seconds: float, drain_s: float,
          rng: np.random.Generator,
          clock: Callable[[], float] = time.monotonic,
          annotate: Optional[Callable[[str], object]] = None,
          on_tick: Optional[Callable[[float], None]] = None) -> Run:
    """Run the plan. ``annotate(name)`` returns a context manager marking a
    host span (the traced run's profiler annotations); ``on_tick(now)`` is
    called between steps (the traced run starts and stops the profiler)."""
    ann = annotate or (lambda name: contextlib.nullcontext())
    K = replica.window
    run = Run()
    t_begin = clock()
    run.t_open = t_begin + plan.warm_s
    run.t_close = run.t_open + seconds
    deadline = run.t_close + drain_s
    open_loop = plan.loop == "open"
    pending = list(plan.items) if open_loop else []
    queues: dict[int, list] = {}
    free_at: dict[int, float] = {}
    if not open_loop:
        for it in plan.items:
            queues.setdefault(it.client, []).append(it)
        # clients start spread over the warm-up, so their turns interleave
        for c in range(plan.clients):
            free_at[c] = (t_begin + plan.warm_s * c / plan.clients
                          if plan.warm_s else run.t_open)
    busy: dict[int, int] = {}            # client -> rid in flight
    client_of: dict[int, int] = {}
    fault_times = [run.t_open + t for t in plan.faults]
    owed: set[int] = set()               # rids due in the window, unanswered
    active: list[Fault] = []
    seen_faults = 0

    def submit(it, t_due: float) -> None:
        now = clock()
        rec = Record(it.rid, len(it.prompt), t_due, t_submit=now)
        run.records[it.rid] = rec
        run.prompts[it.rid] = it.prompt
        if run.t_open <= t_due < run.t_close:
            owed.add(it.rid)
            if open_loop:
                run.late_s.append(now - t_due)
        resp = replica.submit(Request(id=it.rid, prompt=it.prompt,
                                      max_new_tokens=it.max_new))
        if resp is not None:                  # refused at admission
            answer(resp, now)

    def answer(resp, now: float) -> None:
        rec = run.records[resp.id]
        rec.t_done = now
        rec.status = resp.status
        rec.ttft_s = resp.ttft_s
        rec.tokens = tuple(resp.tokens)
        owed.discard(resp.id)
        c = client_of.pop(resp.id, None)
        if c is not None:
            busy.pop(c, None)
            free_at[c] = now

    while True:
        now = clock()
        if on_tick is not None:
            on_tick(now)
        closed = now >= run.t_close
        with ann("bench.offer"):
            if open_loop:
                while pending and run.t_open + pending[0].due <= now:
                    it = pending.pop(0)
                    if run.t_open + it.due >= run.t_close:
                        pending.clear()
                        break
                    submit(it, run.t_open + it.due)
            else:
                for c, q in queues.items():
                    if (c not in busy and q and free_at[c] <= now
                            and free_at[c] < run.t_close):
                        it = q.pop(0)
                        busy[c] = it.rid
                        client_of[it.rid] = c
                        submit(it, free_at[c])
        if fault_times and fault_times[0] <= now and not closed:
            fault_times.pop(0)
            with ann("bench.inject"):
                slots = _eligible(replica, K)
                if slots:
                    slot = int(rng.choice(slots))
                    s = replica.sched.slots[slot]
                    if replica.inject_state_fault(slot) == slot:
                        active.append(Fault(clock(), slot, s.req.id))
                        run.faults.append(active[-1])
        if closed and run.snap_close is None and run.snap_open is not None:
            run.snap_close = _counters(replica.metrics, clock)
        if closed and not owed:
            break
        if now >= deadline:
            break
        if replica.idle():
            nxt = [deadline]
            if open_loop and pending:
                nxt.append(run.t_open + pending[0].due)
            if not open_loop:
                nxt += [free_at[c] for c, q in queues.items()
                        if c not in busy and q]
            if fault_times and not closed:
                nxt.append(fault_times[0])
            with ann("bench.wait"):
                time.sleep(max(0.0, min(min(nxt) - clock(), 0.005)))
            if run.snap_open is None and clock() >= run.t_open:
                run.snap_open = _counters(replica.metrics, clock)
            continue
        t0 = clock()
        with ann("replica.step"):
            out = replica.step()
        t1 = clock()
        run.steps += 1
        run.step_s += t1 - t0
        for resp in out:
            answer(resp, t1)
        if run.snap_open is None and t1 >= run.t_open:
            run.snap_open = _counters(replica.metrics, clock)
        if active:
            _track_faults(replica, active, t1, seen_faults, run)
        seen_faults = len(replica.metrics.faults)
    if run.snap_close is None:
        run.snap_close = _counters(replica.metrics, clock)
    run.windows = run.snap_close[3] - run.snap_open[3]
    run.windows_total = replica.metrics.windows
    return run


def _track_faults(replica, active: list, now: float, seen: int,
                  run: Run) -> None:
    """Advance each open fault: detection (a new fault record names its
    slot), then the first further token of the request it hit."""
    new = replica.metrics.faults[seen:]
    for f in list(active):
        rec = run.records.get(f.rid)
        s = replica.sched.slots[f.slot]
        holds = s.active and s.req.id == f.rid
        n = len(s.generated) if holds else None
        if f.t_det is None:
            if any(f.slot in r.slots for r in new):
                f.t_det = now
                f.n_at_det = n if n is not None else 0
            elif rec is not None and rec.status != "unanswered":
                active.remove(f)            # answered before it surfaced
            continue
        if (rec is not None and rec.status != "unanswered") or (
                n is not None and n > f.n_at_det):
            f.t_heal = now
            active.remove(f)
