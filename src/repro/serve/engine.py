"""The one place a serving engine's device programs are built.

:func:`build_engine` turns a model, its params and an
:class:`~repro.serve.config.EngineConfig` into the window program the
replica dispatches, plus the two pieces of layout that program was built
for: the :class:`~repro.launch.paging.PagedLayout` of a paged KV pool and
the :class:`~repro.launch.steps.TPContext` of a tensor-parallel replica.

A :class:`~repro.serve.replica.Replica` calls it when it is not handed
programs; a :class:`~repro.serve.group.ServeGroup` and the fuzz engine kits
call it once and hand the result to every replica they build, so N replicas
compile one program. Only shapes are traced here — no cache is allocated.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax

from ..configs.base import ModelConfig
from ..core.detect import ProbeConfig
from ..launch.paging import PagedLayout
from ..launch.steps import (
    TP_AXIS,
    TPContext,
    make_prefill_decode_window,
    make_speculative_decode_window,
)
from ..models import build_model
from .config import EngineConfig

# CPU/interpret backends fall back to the fused-by-XLA probe oracle anyway;
# forcing it keeps the vmapped step portable (see kernels/fault_probe/ops.py).
SERVE_PROBES = ProbeConfig(use_kernel=False)


@dataclass(frozen=True)
class EnginePrograms:
    """What :func:`build_engine` returns: the jitted window program, the
    paged layout it addresses (None for contiguous caches) and the
    tensor-parallel context it is sharded over (None when ``tp == 1``)."""

    window_fn: Callable
    layout: Optional[PagedLayout]
    tp: Optional[TPContext]


def build_engine(cfg: ModelConfig, params: Any, config: EngineConfig,
                 probe_cfg: ProbeConfig = SERVE_PROBES) -> EnginePrograms:
    """Build the window program for ``config`` (overlapped, speculative when
    ``config.speculate``), over a paged pool when ``config.paged`` and
    shard_mapped over a ``config.tp``-way "model" mesh when ``tp > 1``."""
    model = build_model(cfg)
    if config.speculate and not model.supports_speculation():
        raise ValueError(
            f"{cfg.name}: speculation requires a pure full-attention"
            ", non-MoE architecture")
    num_slots, max_len = config.num_slots, config.max_len
    one = jax.eval_shape(lambda: model.init_cache(1, max_len))
    layout = None
    if config.paged:
        num_pages = (int(config.page_budget)
                     if config.page_budget is not None
                     else num_slots * (max_len // config.page_size))
        layout = PagedLayout(one, max_len, page_size=config.page_size,
                             num_pages=num_pages)
    tp = _tp_context(params, config, one, layout) if config.tp > 1 else None
    if config.speculate:
        window_fn = make_speculative_decode_window(
            cfg, probe_cfg, window=config.window, draft_len=config.draft_len,
            draft_layers=config.draft_layers, donate=True, paged=layout,
            tp=tp)
    else:
        window_fn = make_prefill_decode_window(
            cfg, probe_cfg, window=config.window, donate=True, paged=layout,
            tp=tp)
    return EnginePrograms(window_fn=window_fn, layout=layout, tp=tp)


def _tp_context(params, config: EngineConfig, one,
                layout: Optional[PagedLayout]) -> TPContext:
    """Mesh + storage specs of a ``config.tp``-way replica, from shapes only
    (``one`` is the per-slot cache's shape tree): params and cache leaves
    are stored sharded, compute stays replicated inside the window
    (DESIGN §3.8)."""
    from ..sharding.rules import param_specs, tp_storage_specs
    ndev = len(jax.devices())
    if ndev < config.tp:
        raise ValueError(
            f"tp={config.tp} requires {config.tp} devices, found {ndev} "
            "(on CPU, force host devices with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={config.tp})")
    mesh = jax.make_mesh((config.tp,), (TP_AXIS,))
    S = config.num_slots
    if layout is not None:
        hybrid = jax.eval_shape(lambda c: layout.init_hybrid(c, S), one)
        cspecs = layout.tp_storage_specs(hybrid, mesh)
    else:
        stacked = jax.tree_util.tree_map(
            lambda v: jax.ShapeDtypeStruct((S, *v.shape), v.dtype), one)
        cspecs = tp_storage_specs(stacked, mesh)
    return TPContext(mesh=mesh, param_specs=param_specs(params, mesh),
                     cache_specs=cspecs)
