"""Mixture-of-Experts FFN: top-k routing, capacity buffers, expert-parallel layout.

TPU-native dispatch (GShard/Switch lineage): tokens are scattered into per-expert
capacity buffers so the expert matmuls are dense einsums that shard cleanly over the
expert axis (EP on the ``model`` mesh axis).

Dispatch is *grouped per batch row* (vmap over B): the position-in-expert cumsum
runs along the sequence axis inside each row, so it never crosses data-parallel
shards — no cross-device cumsum chains in the SPMD partitioning. Capacity is
therefore per (row, expert): C = ceil(cf · S · K / E).

Tokens beyond capacity are dropped and the dropped fraction is returned — it feeds
the paper's ``ROUTER_OVERFLOW`` soft-fault probe (``repro.core.detect.router_probe``),
making router pathologies a first-class propagated error instead of a silent
quality regression. That is the training path. Serving uses
:func:`apply_moe_dropless`: a served token may not depend on its neighbours, so
every routed pair is computed and nothing is dropped.

Expert parallelism: a model told ``expert_shards`` / ``expert_shard`` holds one
contiguous block of each layer's experts. The router keeps its full width and
top-k; the layer returns the held experts' part of the result, and pairs routed
to experts held elsewhere add nothing here.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .layers import _dense_init

# Optional PartitionSpec for the (B, E, C, d) dispatch buffers, set by the launch
# layer (§Perf lever "ep"): constraining E over "model" makes GSPMD move tokens
# to their experts with an all-to-all-shaped scatter instead of all-gathering
# the full capacity buffers onto every device.
EXPERT_SPEC = None


def _constrain_e(x):
    if EXPERT_SPEC is not None:
        import jax as _jax

        return _jax.lax.with_sharding_constraint(x, EXPERT_SPEC)
    return x


def init_moe(key, cfg, dtype=jnp.float32):
    """Router over all ``num_experts``; expert weights for the held block."""
    ks = jax.random.split(key, 4)
    E, H, d, f = cfg.num_experts, cfg.experts_held, cfg.d_model, cfg.d_ff
    p = {
        "router": _dense_init(ks[0], (d, E), dtype=jnp.float32),  # fp32 routing
        "wo": _dense_init(ks[3], (H, f, d), dtype=dtype),
    }
    if cfg.mlp_kind in ("swiglu", "geglu"):
        p["wi"] = _dense_init(ks[1], (H, d, f), dtype=dtype)
        p["wg"] = _dense_init(ks[2], (H, d, f), dtype=dtype)
    else:
        p["wi"] = _dense_init(ks[1], (H, d, f), dtype=dtype)
    return p


def _capacity(tokens_per_group: int, cfg) -> int:
    c = int(cfg.expert_capacity_factor * tokens_per_group
            * cfg.num_experts_per_tok / cfg.num_experts)
    return max(8, -(-c // 8) * 8)     # lane-friendly multiple of 8


def _dispatch_row(xt, expert_idx, gate_vals, E: int, C: int, held=None):
    """One batch row. xt:(S,d), expert_idx/gate_vals:(S,K) → (E,C,d) buffers plus
    combine metadata. ``held`` (S,K) marks pairs routed to this model's
    experts (local ids in ``expert_idx``); the rest get no buffer row."""
    S, d = xt.shape
    K = expert_idx.shape[1]
    flat_idx = expert_idx.reshape(-1)                        # (S*K,)
    onehot = jax.nn.one_hot(flat_idx, E, dtype=jnp.int32)
    pos = (jnp.cumsum(onehot, axis=0) - 1)
    at = flat_idx if held is None else jnp.where(held.reshape(-1), flat_idx, 0)
    pos = jnp.take_along_axis(pos, at[:, None], axis=1)[:, 0]
    keep = pos < C
    if held is not None:
        keep = keep & held.reshape(-1)
    buf_idx = jnp.where(keep, flat_idx * C + pos, E * C)     # trash row at E*C
    token_of = jnp.repeat(jnp.arange(S), K)
    buffers = jnp.zeros((E * C + 1, d), xt.dtype)
    buffers = buffers.at[buf_idx].set(xt[token_of], mode="drop")
    return buffers[: E * C].reshape(E, C, d), (buf_idx, token_of, keep)


def _combine_row(out_e, meta, gate_vals, S: int):
    buf_idx, token_of, keep = meta
    E_C, d = out_e.reshape(-1, out_e.shape[-1]).shape
    flat_out = out_e.reshape(E_C, d)
    safe_idx = jnp.where(keep, buf_idx, 0)
    gathered = flat_out[safe_idx] * keep[:, None].astype(flat_out.dtype)
    weighted = gathered * gate_vals.reshape(-1)[:, None].astype(flat_out.dtype)
    return jax.ops.segment_sum(weighted, token_of, num_segments=S)


def _route(p, x, cfg, precision=None):
    """Softmax over all ``num_experts`` in float32, top-k, renormalised over
    the k: ``(gates, expert ids)``, each (..., K)."""
    logits = jnp.matmul(x.astype(jnp.float32), p["router"],
                        precision=precision)                 # (..., E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    return gate_vals, expert_idx


def apply_moe(p, x, cfg):
    """x: (B, S, d) → (B, S, d), plus aux dict (dropped fraction, load).
    Capacity-bounded (the training path); pairs past capacity are dropped."""
    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    C = _capacity(S, cfg)

    gate_vals, expert_idx = _route(p, x, cfg)                # (B, S, K)
    held = None
    if cfg.expert_shards > 1:
        E = cfg.experts_held
        expert_idx = expert_idx - cfg.expert_shard * E
        held = (expert_idx >= 0) & (expert_idx < E)
        expert_idx = jnp.where(held, expert_idx, E)

    buffers, meta = jax.vmap(
        lambda xt, ei, gv, hd: _dispatch_row(xt, ei, gv, E, C, hd)
    )(x, expert_idx, gate_vals, held)                        # (B, E, C, d)
    buffers = _constrain_e(buffers)

    h = jnp.einsum("becd,edf->becf", buffers, p["wi"])
    if cfg.mlp_kind == "swiglu":
        h = jax.nn.silu(jnp.einsum("becd,edf->becf", buffers, p["wg"])) * h
    elif cfg.mlp_kind == "geglu":
        h = jax.nn.gelu(jnp.einsum("becd,edf->becf", buffers, p["wg"]),
                        approximate=True) * h
    else:
        h = jax.nn.gelu(h, approximate=True)
    out_e = _constrain_e(jnp.einsum("becf,efd->becd", h, p["wo"]))  # (B,E,C,d)

    combined = jax.vmap(lambda oe, m, gv: _combine_row(oe, m, gv, S))(
        out_e, meta, gate_vals)

    keep = meta[2]
    if held is None:
        dropped_fraction = 1.0 - jnp.mean(keep.astype(jnp.float32))
    else:
        dropped_fraction = 1.0 - (jnp.sum(keep.astype(jnp.float32))
                                  / jnp.maximum(jnp.sum(held), 1))
    load = jnp.mean(jax.nn.one_hot(expert_idx, E, dtype=jnp.float32),
                    axis=(0, 1, 2)) * E
    aux = {"dropped_fraction": dropped_fraction, "load_max": jnp.max(load)}
    return combined.reshape(B, S, d), aux


def apply_moe_dropless(p, x, cfg):
    """The serving expert layer: every (token, held expert) pair the router
    chose is computed, with no capacity, so a row's result never depends on
    the other rows. Each held expert runs over all the rows, weighted by a
    gate that is zero where it was not chosen: one read of each held
    expert's weights per call.

    x: (..., d) → ``(out (..., d), pairs (H,) int32)``, ``pairs`` the rows
    routed to each held expert. The router runs at full float32 precision,
    so the top-k choice does not depend on how the chip rounds a float32
    product."""
    H = cfg.experts_held
    gate, idx = _route(p, x, cfg, precision=jax.lax.Precision.HIGHEST)
    chosen = (idx - cfg.expert_shard * H)[..., None] == jnp.arange(H)
    w = jnp.sum(jnp.where(chosen, gate[..., None], 0.0), axis=-2)  # (..., H)
    lead = x.shape[:-1]
    xh = jnp.broadcast_to(x.reshape(1, -1, x.shape[-1]),
                          (H, math.prod(lead), x.shape[-1]))       # (H, N, d)
    h = jnp.einsum("hnd,hdf->hnf", xh, p["wi"])
    if cfg.mlp_kind == "swiglu":
        h = jax.nn.silu(jnp.einsum("hnd,hdf->hnf", xh, p["wg"])) * h
    elif cfg.mlp_kind == "geglu":
        h = jax.nn.gelu(jnp.einsum("hnd,hdf->hnf", xh, p["wg"]),
                        approximate=True) * h
    else:
        h = jax.nn.gelu(h, approximate=True)
    y = jnp.einsum("hnf,hfd->hnd", h, p["wo"])                     # (H, N, d)
    out = jnp.einsum("hnd,nh->nd", y.astype(jnp.float32),
                     w.reshape(-1, H))
    pairs = jnp.sum(chosen, axis=tuple(range(chosen.ndim - 1)),
                    dtype=jnp.int32)
    return out.reshape(x.shape).astype(x.dtype), pairs
