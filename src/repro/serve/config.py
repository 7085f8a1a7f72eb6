"""EngineConfig — the one validated construction surface for the serving engine.

There is one plain serving engine: the overlapped decode window
(:func:`~repro.launch.steps.make_prefill_decode_window`) — K greedy steps
per dispatch, with admission and LFLR recovery fed as prompt chunks inside
the same scan. ``paged``, ``speculate`` and ``tp`` are variants of that
engine, built by :func:`~repro.serve.engine.build_engine`.

* every *engine-shape* knob lives here, validated once in ``__post_init__``
  (a bad value fails at construction, in one place, with one message);
* :meth:`from_flags` parses ``"win=8,spec=1,dlen=3"``-style strings for the
  CLI and benchmark entry points;
* ``Replica(...)``/``ServeGroup(...)`` take ``config=EngineConfig(...)`` —
  the sole construction path.

Runtime *wiring* (queues, tracers, shared programs, clocks, injectors)
deliberately stays out: those are per-instance objects, not engine shape, and
an EngineConfig must stay hashable/serialisable so benchmark cells and fuzz
engine kits can be declared as data.

Model-dependent checks (``speculate`` requires
``Model.supports_speculation()``; ``tp`` requires enough devices for the
"model" mesh axis) are made by ``build_engine``, the first place that has
the model and devices in hand.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional


@dataclass(frozen=True)
class EngineConfig:
    """Shape of one serving engine. Frozen, hashable, validated.

    ``tp`` is the tensor-parallel width: ``tp > 1`` shards the decode /
    verify / prefill windows over a ``tp``-way "model" mesh axis (storage
    sharded by :mod:`repro.sharding.rules`, compute replicated after an
    in-program all-gather — DESIGN §3.8), with per-shard error words
    OR-folded across the axis so a fault on any shard latches identically on
    all shards. Requires ``tp`` visible devices at construction.

    ``overlap`` accepts only ``True``: the blocking-prefill engine was
    removed, and the field stays only because benchmark configurations
    still name it.
    """

    num_slots: int = 4
    max_len: int = 64
    eos_id: Optional[int] = None
    max_request_retries: int = 2
    # ---- decode windows ------------------------------------------------
    window: int = 4
    overlap: bool = True
    prefill_budget: Optional[int] = None
    # ---- paged KV pool (PR 4) -----------------------------------------
    paged: bool = False
    page_size: int = 8
    page_budget: Optional[int] = None
    page_watermark: int = 0
    # ---- speculative windows (PR 5) -----------------------------------
    speculate: bool = False
    draft_len: int = 3
    draft_layers: int = 1
    # ---- tensor parallelism (PR 9) ------------------------------------
    tp: int = 1
    # ---- tracing (PR 6; consumed by ServeGroup — a Replica takes a
    # Tracer object directly) -------------------------------------------
    trace: bool = False
    trace_sample: float = 1.0

    def __post_init__(self):
        if self.num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {self.num_slots}")
        if self.max_len < 1:
            raise ValueError(f"max_len must be >= 1, got {self.max_len}")
        if self.max_request_retries < 0:
            raise ValueError("max_request_retries must be >= 0, got "
                             f"{self.max_request_retries}")
        if self.window < 1:
            raise ValueError(
                f"window must be >= 1, got {self.window} (the stepwise "
                "engine was removed: every engine decodes in windows)")
        if not self.overlap:
            raise ValueError(
                "overlap must be True: the blocking-prefill window engine "
                "was removed (admission and recovery ride the window)")
        if self.prefill_budget is not None and self.prefill_budget < 1:
            raise ValueError("prefill_budget must be >= 1 (or None), got "
                             f"{self.prefill_budget}")
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {self.page_size}")
        if self.page_budget is not None and self.page_budget < 1:
            raise ValueError("page_budget must be >= 1 (or None), got "
                             f"{self.page_budget}")
        if self.page_watermark < 0:
            raise ValueError("page_watermark must be >= 0, got "
                             f"{self.page_watermark}")
        if self.draft_len < 1:
            raise ValueError(f"draft_len must be >= 1, got {self.draft_len}")
        if self.draft_layers < 1:
            raise ValueError("draft_layers must be >= 1, got "
                             f"{self.draft_layers}")
        if not 0.0 <= self.trace_sample <= 1.0:
            raise ValueError("trace_sample must be in [0, 1], got "
                             f"{self.trace_sample}")
        if self.tp < 1:
            raise ValueError(f"tp must be >= 1, got {self.tp}")

    # ------------------------------------------------------------ construction
    @classmethod
    def from_flags(cls, spec: str, **overrides) -> "EngineConfig":
        """Parse ``"win=8,spec=1,dlen=3,tp=2,paged=1,page=16"`` → EngineConfig.

        One parser for every CLI/benchmark entry point. Bare keys are
        boolean shorthand (``"paged,spec"`` ≡ ``"paged=1,spec=1"``);
        ``overrides`` are applied on top (a tool's fixed ``num_slots`` beats
        the flag string). Unknown keys raise — a typo must not silently
        configure the default engine.
        """
        bool_fields = {"overlap", "paged", "speculate", "trace"}
        alias = {
            "win": "window", "window": "window",
            "slots": "num_slots", "num_slots": "num_slots",
            "max_len": "max_len", "eos": "eos_id", "eos_id": "eos_id",
            "retries": "max_request_retries",
            "max_request_retries": "max_request_retries",
            "overlap": "overlap",
            "budget": "prefill_budget", "prefill_budget": "prefill_budget",
            "page": "page_size", "page_size": "page_size",
            "paged": "paged", "pages": "page_budget",
            "page_budget": "page_budget",
            "watermark": "page_watermark", "page_watermark": "page_watermark",
            "spec": "speculate", "speculate": "speculate",
            "dlen": "draft_len", "draft_len": "draft_len",
            "dlayers": "draft_layers", "draft_layers": "draft_layers",
            "tp": "tp", "trace": "trace", "trace_sample": "trace_sample",
        }
        kw: dict = {}
        for part in (spec or "").split(","):
            part = part.strip()
            if not part:
                continue
            k, _, v = part.partition("=")
            if k not in alias:
                raise ValueError(
                    f"unknown engine flag {k!r} (known: "
                    f"{sorted(set(alias))})")
            field = alias[k]
            if not v:
                if field not in bool_fields:
                    raise ValueError(f"engine flag {k!r} needs a value")
                kw[field] = True
                continue
            if field in bool_fields:
                kw[field] = bool(int(v))
            elif field == "trace_sample":
                kw[field] = float(v)
            else:
                kw[field] = int(v)
            # legacy ``page=16`` meant "paged pool with 16-token pages"
            if k == "page" and int(v) > 0:
                kw["paged"] = True
        kw.update(overrides)
        return cls(**kw)
