"""Block assembly + depth stacking.

Heterogeneous stacks are expressed as a repeating ``block_pattern``; the stack scans
over pattern *periods* (``lax.scan`` with the per-position blocks unrolled inside the
body), so HLO size scales with the period length, not the depth — essential for
compile times at 48–64 layers. Remainder layers (depth not divisible by the period)
are applied unrolled after the scan.

Block types:
  attn     — self-attention (full)   + MLP/MoE
  sliding  — self-attention (window) + MLP/MoE
  cross    — cross-attention to image embeddings + MLP (VLM layers, gated)
  ssd      — Mamba-2 mixer (no MLP: the mixer is the block)
  rglru    — Griffin recurrent block + MLP
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from .attention import (
    attention_decode,
    attention_train,
    attention_verify,
    cross_attention_decode,
    init_attention,
    init_kv_cache,
    precompute_cross_kv,
)
from .layers import apply_mlp, apply_norm, init_mlp, init_norm
from .moe import apply_moe, apply_moe_dropless, init_moe
from .rglru import init_rglru, init_rglru_cache, rglru_decode, rglru_mixer
from .ssm import init_mamba2, init_mamba2_cache, mamba2_decode, mamba2_mixer

ATTN_KINDS = ("attn", "sliding", "cross")

# Optional PartitionSpec for the residual stream between blocks, set by the
# launch layer (sequence parallelism: P(dp_axes, "model", None) makes GSPMD
# lower the Megatron-TP activation all-reduces into reduce-scatter + all-gather
# pairs and shards the norm/probe elementwise work over the model axis).
ACTIVATION_SPEC = None


def _constrain(x):
    if ACTIVATION_SPEC is not None:
        return jax.lax.with_sharding_constraint(x, ACTIVATION_SPEC)
    return x


# ------------------------------------------------------------------------- init
def init_block(key, cfg, btype: str, dtype):
    ks = jax.random.split(key, 4)
    p: dict[str, Any] = {"norm1": init_norm(cfg, jnp.float32)}
    if btype in ATTN_KINDS:
        p["attn"] = init_attention(ks[0], cfg, dtype)
        p["norm2"] = init_norm(cfg, jnp.float32)
        if btype == "cross":
            p["gate_attn"] = jnp.zeros((), jnp.float32)
            p["gate_mlp"] = jnp.zeros((), jnp.float32)
        if cfg.is_moe:
            p["moe"] = init_moe(ks[1], cfg, dtype)
        elif cfg.d_ff:
            p["mlp"] = init_mlp(ks[1], cfg, dtype=dtype)
    elif btype == "ssd":
        p["ssd"] = init_mamba2(ks[0], cfg, dtype)
    elif btype == "rglru":
        p["rglru"] = init_rglru(ks[0], cfg, dtype)
        p["norm2"] = init_norm(cfg, jnp.float32)
        if cfg.d_ff:
            p["mlp"] = init_mlp(ks[1], cfg, dtype=dtype)
    else:
        raise ValueError(f"unknown block type {btype}")
    return p


# ------------------------------------------------------------------ train paths
def _ffn(p, h, cfg):
    """MLP or MoE sub-block; returns (out, dropped_fraction)."""
    if cfg.is_moe:
        out, aux = apply_moe(p["moe"], h, cfg)
        return out, aux["dropped_fraction"]
    if cfg.d_ff:
        return apply_mlp(p["mlp"], h, cfg.mlp_kind), jnp.float32(0)
    return jnp.zeros_like(h), jnp.float32(0)


def _ffn_decode(p, h, cfg):
    """The serving MLP or dropless MoE sub-block; returns (out, routed):
    the rows routed to each held expert (MoE), else a float zero."""
    if cfg.is_moe:
        return apply_moe_dropless(p["moe"], h, cfg)
    return _ffn(p, h, cfg)


def _routed0(cfg):
    """The zero that the decode stack's routed counts accumulate from."""
    if cfg.is_moe:
        return jnp.zeros((cfg.experts_held,), jnp.int32)
    return jnp.float32(0)


def apply_block_train(p, x, positions, cfg, btype: str, *,
                      img_embeds=None, impl: str = "auto"):
    """Pre-norm residual block. Returns (x, dropped_fraction)."""
    drop = jnp.float32(0)
    if btype in ATTN_KINDS:
        h = apply_norm(p["norm1"], x, cfg.norm)
        window = cfg.sliding_window if btype == "sliding" else 0
        kv_src = img_embeds if btype == "cross" else None
        a = attention_train(p["attn"], h, positions, cfg, window=window,
                            kv_src=kv_src, impl=impl)
        if btype == "cross":
            a = a * jnp.tanh(p["gate_attn"]).astype(a.dtype)
        x = x + a
        h = apply_norm(p["norm2"], x, cfg.norm)
        f, drop = _ffn(p, h, cfg)
        if btype == "cross":
            f = f * jnp.tanh(p["gate_mlp"]).astype(f.dtype)
        x = x + f
    elif btype == "ssd":
        h = apply_norm(p["norm1"], x, cfg.norm)
        x = x + mamba2_mixer(p["ssd"], h, cfg, impl=impl)
    elif btype == "rglru":
        h = apply_norm(p["norm1"], x, cfg.norm)
        x = x + rglru_mixer(p["rglru"], h, cfg, impl=impl)
        h = apply_norm(p["norm2"], x, cfg.norm)
        f, drop = _ffn(p, h, cfg)
        x = x + f
    return x, drop


def init_stack(key, cfg, dtype):
    """Period-stacked parameters: ``periods[f"b{pos}"]`` has leading dim
    num_periods; ``rest`` holds the remainder layers unrolled."""
    n_per = cfg.num_periods
    keys = jax.random.split(key, cfg.num_layers + 1)
    periods = {}
    for pos, btype in enumerate(cfg.block_pattern):
        layer_params = [init_block(keys[c * cfg.period + pos], cfg, btype, dtype)
                        for c in range(n_per)]
        periods[f"b{pos}"] = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *layer_params)
    rest = [init_block(keys[n_per * cfg.period + i], cfg, btype, dtype)
            for i, btype in enumerate(cfg.remainder_layers)]
    return {"periods": periods, "rest": rest}


def _remat_policy(cfg):
    if cfg.remat == "none":
        return None
    if cfg.remat == "dots_saveable":
        return jax.checkpoint_policies.dots_saveable
    return jax.checkpoint_policies.nothing_saveable


def apply_stack_train(stack, x, positions, cfg, *, img_embeds=None,
                      impl: str = "auto"):
    """Scan over periods; returns (x, mean dropped_fraction)."""

    def period_body(x, period_params):
        drop_acc = jnp.float32(0)
        for pos, btype in enumerate(cfg.block_pattern):
            x = _constrain(x)
            x, d = apply_block_train(period_params[f"b{pos}"], x, positions, cfg,
                                     btype, img_embeds=img_embeds, impl=impl)
            drop_acc = drop_acc + d
        return x, drop_acc

    policy = _remat_policy(cfg)
    body = period_body if policy is None else jax.checkpoint(
        period_body, policy=policy)

    if cfg.num_periods > 0:
        if cfg.scan_layers:
            x, drops = jax.lax.scan(lambda c, p: body(c, p), x, stack["periods"])
            drop_total = jnp.sum(drops)
        else:
            drop_total = jnp.float32(0)
            for i in range(cfg.num_periods):
                pp = jax.tree_util.tree_map(lambda a: a[i], stack["periods"])
                x, d = body(x, pp)
                drop_total = drop_total + d
    else:
        drop_total = jnp.float32(0)
    for i, btype in enumerate(cfg.remainder_layers):
        x, d = apply_block_train(stack["rest"][i], x, positions, cfg, btype,
                                 img_embeds=img_embeds, impl=impl)
        drop_total = drop_total + d
    n_ffn = max(sum(1 for b in cfg.pattern_layers if b != "ssd"), 1)
    return x, drop_total / n_ffn


# ----------------------------------------------------------------------- caches
def init_block_cache(batch, cfg, btype: str, max_len: int, dtype):
    hd = cfg.resolved_head_dim
    if btype == "attn":
        return init_kv_cache(batch, max_len, cfg.num_kv_heads, hd, dtype)
    if btype == "sliding":
        cap = min(cfg.sliding_window, max_len)
        return init_kv_cache(batch, cap, cfg.num_kv_heads, hd, dtype)
    if btype == "cross":
        return init_kv_cache(batch, cfg.img_tokens, cfg.num_kv_heads, hd, dtype)
    if btype == "ssd":
        return init_mamba2_cache(batch, cfg, dtype)
    if btype == "rglru":
        return init_rglru_cache(batch, cfg, dtype)
    raise ValueError(btype)


def init_stack_cache(batch, cfg, max_len: int, dtype):
    n_per = cfg.num_periods
    periods = {}
    for pos, btype in enumerate(cfg.block_pattern):
        one = init_block_cache(batch, cfg, btype, max_len, dtype)
        periods[f"b{pos}"] = jax.tree_util.tree_map(
            lambda v: jnp.broadcast_to(v[None], (n_per, *v.shape)).copy(), one)
    rest = [init_block_cache(batch, cfg, btype, max_len, dtype)
            for btype in cfg.remainder_layers]
    return {"periods": periods, "rest": rest}


def _layer_of(cache, layer):
    """This block's layer of a period stack of caches, read by index."""
    if layer is None:
        return cache
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False),
        cache)


def _put_layer(cache, new, layer):
    """Write one layer's new cache into the period stack, in place."""
    if layer is None:
        return new
    return jax.tree_util.tree_map(
        lambda a, n: jax.lax.dynamic_update_index_in_dim(a, n, layer, 0),
        cache, new)


def apply_block_decode(p, x, cache, pos, cfg, btype: str, layer=None):
    """One-token block against its cache; returns (x, new_cache, routed).

    With ``layer``, ``cache`` is the period stack of this block's caches
    (leading axis the period) and ``layer`` the block's index in it: the
    block reads its layer by index and writes back only what it changed
    (attention a K/V row, a recurrent block its state, cross attention
    nothing), and returns the whole stack."""
    routed = _routed0(cfg)
    if btype in ATTN_KINDS:
        h = apply_norm(p["norm1"], x, cfg.norm)
        if btype == "cross":
            a = cross_attention_decode(p["attn"], h, _layer_of(cache, layer),
                                       cfg)
            a = a * jnp.tanh(p["gate_attn"]).astype(a.dtype)
            new_cache = cache  # static image K/V
        else:
            window = cfg.sliding_window if btype == "sliding" else 0
            a, new_cache = attention_decode(p["attn"], h, cache, pos, cfg,
                                            window=window, layer=layer)
        x = x + a
        h = apply_norm(p["norm2"], x, cfg.norm)
        f, routed = _ffn_decode(p, h, cfg)
        if btype == "cross":
            f = f * jnp.tanh(p["gate_mlp"]).astype(f.dtype)
        x = x + f
    elif btype == "ssd":
        h = apply_norm(p["norm1"], x, cfg.norm)
        y, new = mamba2_decode(p["ssd"], h, _layer_of(cache, layer), cfg)
        new_cache = _put_layer(cache, new, layer)
        x = x + y
    elif btype == "rglru":
        h = apply_norm(p["norm1"], x, cfg.norm)
        y, new = rglru_decode(p["rglru"], h, _layer_of(cache, layer), cfg)
        new_cache = _put_layer(cache, new, layer)
        x = x + y
        h = apply_norm(p["norm2"], x, cfg.norm)
        f, routed = _ffn_decode(p, h, cfg)
        x = x + f
    else:
        raise ValueError(btype)
    return x, new_cache, routed


def apply_block_verify(p, x, cache, pos, cfg, btype: str):
    """Multi-token decode block (speculative verify). Full attention only:
    ring buffers and recurrent states advance destructively, so they cannot
    absorb the over-writes a rejected draft leaves behind."""
    if btype != "attn":
        raise ValueError(
            f"speculative verify supports full-attention blocks only, "
            f"got {btype!r}")
    h = apply_norm(p["norm1"], x, cfg.norm)
    a, new_cache = attention_verify(p["attn"], h, cache, pos, cfg)
    x = x + a
    h = apply_norm(p["norm2"], x, cfg.norm)
    f, routed = _ffn_decode(p, h, cfg)
    x = x + f
    return x, new_cache, routed


def apply_stack_verify(stack, x, caches, pos, cfg):
    """T-token verify through the whole stack; returns (x, new_caches).

    Structure mirrors :func:`apply_stack_decode` (same layer order, same
    remainder unroll) with the multi-token verify block, so each token row
    computes the single-token decode arithmetic at its own position. It still
    scans the period caches as ``xs`` / ``ys``.
    """

    def period_body(x, inputs):
        pp, pc = inputs
        new_pc = {}
        for i, btype in enumerate(cfg.block_pattern):
            x, c, _ = apply_block_verify(pp[f"b{i}"], x, pc[f"b{i}"], pos,
                                         cfg, btype)
            new_pc[f"b{i}"] = c
        return x, new_pc

    if cfg.num_periods > 0:
        if cfg.scan_layers:
            x, new_periods = jax.lax.scan(
                period_body, x, (stack["periods"], caches["periods"]))
        else:
            outs = []
            for i in range(cfg.num_periods):
                pp = jax.tree_util.tree_map(lambda a: a[i], stack["periods"])
                pc = jax.tree_util.tree_map(lambda a: a[i], caches["periods"])
                x, npc = period_body(x, (pp, pc))
                outs.append(npc)
            new_periods = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *outs)
    else:
        new_periods = caches["periods"]
    new_rest = []
    for i, btype in enumerate(cfg.remainder_layers):
        x, c, _ = apply_block_verify(stack["rest"][i], x, caches["rest"][i],
                                     pos, cfg, btype)
        new_rest.append(c)
    return x, {"periods": new_periods, "rest": new_rest}


def _draft_layer_slices(stack, caches, cfg, num_layers: int):
    """(params, cache, writeback) triple per drafted layer.

    Period-stacked layers are sliced out once; ``writeback(caches, new)``
    re-inserts the advanced per-layer caches in one ``.at[c].set`` per layer
    — the draft *chain* slices and writes back once around all D steps, so
    the stacked-leaf copies don't scale with draft depth.
    """
    n_scan = cfg.num_periods * cfg.period
    if not 0 < num_layers <= cfg.num_layers:
        raise ValueError(
            f"draft layers must be in [1, {cfg.num_layers}], got {num_layers}")
    layers = []
    for l in range(num_layers):
        if l < n_scan:
            c, pat = l // cfg.period, l % cfg.period
            key = f"b{pat}"
            pp = jax.tree_util.tree_map(lambda a: a[c], stack["periods"][key])
            pc = jax.tree_util.tree_map(lambda a: a[c], caches["periods"][key])

            def wb(caches, nc, c=c, key=key):
                caches["periods"][key] = jax.tree_util.tree_map(
                    lambda full, one: full.at[c].set(one),
                    caches["periods"][key], nc)

            layers.append((pp, pc, cfg.block_pattern[pat], wb))
        else:
            i = l - n_scan

            def wb(caches, nc, i=i):
                caches["rest"][i] = nc

            layers.append((stack["rest"][i], caches["rest"][i],
                           cfg.remainder_layers[i], wb))
    return layers


def apply_stack_decode(stack, x, caches, pos, cfg):
    """One-token decode through the whole stack; returns (x, new_caches,
    routed): for MoE the rows routed to each held expert, summed over the
    layers, else a float zero.

    The period stacks of caches ride in the scan's carry, not its ``xs`` /
    ``ys``: each block reads its layer by index and writes one row (or its
    state) back in place. Scanned caches would be sliced into the body and
    re-stacked out of it every step, and under the serving engine's vmap over
    slot-major caches, leaves (S, L, ...), also transposed whole to
    layer-major and back."""

    def period_body(carry, inputs):
        x, acc, pc = carry
        pp, c = inputs
        for i, btype in enumerate(cfg.block_pattern):
            key = f"b{i}"
            x, new, d = apply_block_decode(pp[key], x, pc[key], pos, cfg,
                                           btype, layer=c)
            pc = {**pc, key: new}
            acc = acc + d
        return (x, acc, pc), None

    routed = _routed0(cfg)
    periods = caches["periods"]
    if cfg.num_periods > 0:
        if cfg.scan_layers:
            (x, routed, periods), _ = jax.lax.scan(
                period_body, (x, routed, periods),
                (stack["periods"], jnp.arange(cfg.num_periods)))
        else:
            for c in range(cfg.num_periods):
                pp = jax.tree_util.tree_map(lambda a: a[c], stack["periods"])
                (x, routed, periods), _ = period_body((x, routed, periods),
                                                      (pp, c))
    new_rest = []
    for i, btype in enumerate(cfg.remainder_layers):
        x, c, d = apply_block_decode(stack["rest"][i], x, caches["rest"][i],
                                     pos, cfg, btype)
        new_rest.append(c)
        routed = routed + d
    return x, {"periods": periods, "rest": new_rest}, routed
