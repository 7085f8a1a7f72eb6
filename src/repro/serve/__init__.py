"""repro.serve — fault-tolerant continuous-batching inference.

The serving layer of the stack (DESIGN.md §2/§3): it applies the paper's
contract — local errors, asynchrony and hard faults become *catchable
exceptions* at a wait, never deadlocks or aborts — to inference traffic.

* :class:`RequestQueue` / :class:`AdmissionPolicy` — deadline-aware (EDF)
  admission; every accepted request gets a terminal :class:`Response`.
* :class:`ContinuousBatchingScheduler` — fixed decode slots, evict +
  backfill at window boundaries, prompt chunks planned into each window.
* :class:`Replica` — one engine, the overlapped decode window: K greedy steps
  per dispatch behind a ``DeviceFuture``; per-slot error words + the paper's
  enumeration give ``(step, slot, code)`` attribution, so ``STATE_FAULT``
  triggers per-sequence LFLR re-prefill (recompute, don't restart) and a
  :class:`~repro.core.recovery.RecoveryPolicy` escalates. Admission and LFLR
  ride the windows as background chunked-prefill lanes — the token stream
  never stalls on a prefill (DESIGN §3.2).
* :func:`build_engine` — the one place the window program (plain, paged,
  tensor-parallel or speculative) is built from an :class:`EngineConfig`.
* :class:`ServeGroup` — N replicas over the thread-rank transport; a killed
  replica raises on the survivors via the ULFM protocol, the group shrinks and
  re-routes its in-flight requests.
* :class:`MultiHostSupervisor` — the same fault contract across real OS
  processes: localhost subprocess workers (one replica each) under a
  phi-accrual heartbeat failure detector; a SIGKILL'd worker is detected,
  mapped to ``RANK_FAILED`` on the survivors, and repaired through the same
  :func:`agree_round` epoch ladder over a length-prefixed socket transport
  (DESIGN §3.9).
* :class:`ServeMetrics` — latency percentiles, tokens/s, fault counters, and
  an ``EventLog`` export matching the training executor's records.
* Tracing (``repro.obs``) — pass ``tracer=Tracer(...)`` to a replica (or
  ``trace=True`` to a :class:`ServeGroup`) and every request's life becomes a
  causal span chain: submit → slot → prefill chunks → decode windows →
  (faults → recovery lanes →) terminal response, exported as Perfetto
  ``trace_event`` JSON (DESIGN §3.5).
"""
from .config import EngineConfig  # noqa: F401
from .engine import EnginePrograms, build_engine  # noqa: F401
from .group import (  # noqa: F401
    AgreeDecision,
    GroupResult,
    RankReport,
    ServeGroup,
    agree_round,
)
from .metrics import FaultRecord, ServeMetrics  # noqa: F401
from .multihost import (  # noqa: F401
    MultiHostResult,
    MultiHostSupervisor,
    PhiAccrualDetector,
    sim_tokens,
)
from .queue import (  # noqa: F401
    EXPIRED,
    FAILED,
    OK,
    REJECTED,
    AdmissionPolicy,
    Request,
    RequestQueue,
    Response,
)
from .replica import Replica  # noqa: F401
from .scheduler import (  # noqa: F401
    ChunkPlan,
    ContinuousBatchingScheduler,
    PageAllocator,
    PagePoolExhausted,
    Slot,
)
