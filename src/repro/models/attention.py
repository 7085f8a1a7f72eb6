"""Attention: GQA/MQA/MHA, full / sliding-window / cross, train + decode paths.

Three interchangeable SDPA implementations:

* ``sdpa_ref`` — naive full-materialisation oracle (tests, tiny shapes only);
* ``sdpa_chunked`` — online-softmax over KV chunks inside a scan: O(S·C) live
  memory, the flash algorithm expressed in pure jnp. This is the default lowering
  path (CPU dry-runs and the XLA-TPU fallback);
* Pallas flash kernel (``repro.kernels.flash_attention``) — the TPU hot path,
  numerically validated against ``sdpa_ref`` in interpret mode.

All take q:(B,S,Hq,D), k/v:(B,T,Hkv,D) and broadcast KV heads by GQA grouping.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .layers import _dense_init, apply_rope, rms_norm_vec

NEG_INF = -1e30

# Dry-run cost-variant compiles set this to fully unroll the inner KV scan so
# ``cost_analysis`` (which counts a while-loop body once) sees exact FLOPs.
INNER_UNROLL = False


# ------------------------------------------------------------------------- init
def init_attention(key, cfg, dtype=jnp.float32):
    """Projection weights are kept 3D — (d, heads, head_dim) — so tensor-parallel
    sharding lands on the head dimension directly (a fused (d, H·hd) layout forces
    GSPMD to reshard through the reshape whenever kv_heads doesn't divide the
    model axis, which is the common GQA case)."""
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 6)
    import math
    sc = 1.0 / math.sqrt(cfg.d_model)
    p = {
        "wq": _dense_init(ks[0], (cfg.d_model, cfg.num_heads, hd), scale=sc,
                          dtype=dtype),
        "wk": _dense_init(ks[1], (cfg.d_model, cfg.num_kv_heads, hd), scale=sc,
                          dtype=dtype),
        "wv": _dense_init(ks[2], (cfg.d_model, cfg.num_kv_heads, hd), scale=sc,
                          dtype=dtype),
        "wo": _dense_init(ks[3], (cfg.num_heads, hd, cfg.d_model),
                          scale=1.0 / math.sqrt(cfg.num_heads * hd), dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _project_qkv(p, xq, xkv, cfg):
    q = jnp.einsum("bsd,dhe->bshe", xq, p["wq"])
    k = jnp.einsum("bsd,dhe->bshe", xkv, p["wk"])
    v = jnp.einsum("bsd,dhe->bshe", xkv, p["wv"])
    if cfg.qk_norm:
        q = rms_norm_vec(q, p["q_norm"])
        k = rms_norm_vec(k, p["k_norm"])
    return q, k, v


# ----------------------------------------------------------------------- oracle
def sdpa_ref(q, k, v, *, causal: bool, window: int = 0,
             q_offset: int = 0) -> jax.Array:
    """Naive SDPA oracle. window>0 ⇒ sliding (keys within `window` of the query)."""
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    kr = jnp.repeat(k, group, axis=2)
    vr = jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32),
                        kr.astype(jnp.float32)) / jnp.sqrt(float(D))
    qpos = jnp.arange(S) + q_offset
    kpos = jnp.arange(T)
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window:
        mask &= kpos[None, :] > qpos[:, None] - window
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", probs, vr.astype(jnp.float32))
    return out.astype(q.dtype)


# ----------------------------------------------------------- chunked (jnp flash)
#
# Flash-structured attention in pure jnp with a CUSTOM VJP: the backward
# recomputes per-chunk probabilities from saved (q, k, v, out, lse) instead of
# letting autodiff save the O(S·T) probability tensors — without this, each
# layer's backward writes/reads ~4 GiB of residuals per 2048² chunk pair and the
# memory roofline term is fiction. GQA is expressed with grouped einsums
# (B,S,Kv,g,D vs B,T,Kv,D) so KV heads are never materialised ``repeat``-ed.
def _chunk_ranges(nq, nk, q_chunk, kv_chunk, q_offset, causal, window):
    """Static per-q-chunk KV ranges (and the transpose for the backward)."""
    q_ranges = []
    for qi in range(nq):
        q_lo = qi * q_chunk + q_offset
        q_hi = (qi + 1) * q_chunk - 1 + q_offset
        k_first, k_last = 0, nk - 1
        if causal:
            k_last = min(k_last, q_hi // kv_chunk)
        if window:
            k_first = max(0, (q_lo - window + 1) // kv_chunk)
        q_ranges.append((k_first, max(k_last - k_first + 1, 1)))
    kv_ranges = []
    for kj in range(nk):
        k_lo, k_hi = kj * kv_chunk, (kj + 1) * kv_chunk - 1
        q_first, q_last = 0, nq - 1
        if causal:
            q_first = max(0, (k_lo - q_offset) // q_chunk)
        if window:
            q_last = min(q_last, (k_hi + window - 1 - q_offset) // q_chunk)
        kv_ranges.append((q_first, max(q_last - q_first + 1, 1)))
    return q_ranges, kv_ranges


def _mask_for(qpos, kpos, causal, window, T):
    mask = kpos[None, :] < T
    if causal:
        mask = mask & (kpos[None, :] <= qpos[:, None])
    if window:
        mask = mask & (kpos[None, :] > qpos[:, None] - window)
    return mask


def _flash_fwd_impl(q, k, v, causal, window, q_offset, q_chunk, kv_chunk):
    B, S, Hq, D = q.shape
    T, Kv = k.shape[1], k.shape[2]
    g = Hq // Kv
    q_chunk = min(q_chunk, S)
    kv_chunk = min(kv_chunk, T)
    nq, nk = -(-S // q_chunk), -(-T // kv_chunk)
    Sp, Tp = nq * q_chunk, nk * kv_chunk
    qp = jnp.pad(q, ((0, 0), (0, Sp - S), (0, 0), (0, 0))).reshape(
        B, Sp, Kv, g, D)
    kp = jnp.pad(k, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    scale = 1.0 / jnp.sqrt(float(D))
    q_ranges, _ = _chunk_ranges(nq, nk, q_chunk, kv_chunk, q_offset, causal,
                                window)

    outs, lses = [], []
    for qi in range(nq):
        k_first, n_steps = q_ranges[qi]
        qs = jax.lax.slice_in_dim(qp, qi * q_chunk, (qi + 1) * q_chunk,
                                  axis=1).astype(jnp.float32)
        qpos = qi * q_chunk + jnp.arange(q_chunk) + q_offset

        def body(carry, kj, qs=qs, qpos=qpos):
            acc, m, l = carry
            ks = jax.lax.dynamic_slice_in_dim(
                kp, kj * kv_chunk, kv_chunk, 1).astype(jnp.float32)
            vs = jax.lax.dynamic_slice_in_dim(
                vp, kj * kv_chunk, kv_chunk, 1).astype(jnp.float32)
            s = jnp.einsum("bskgd,btkd->bkgst", qs, ks) * scale
            kpos = kj * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.where(_mask_for(qpos, kpos, causal, window, T)[None, None,
                                                                   None],
                          s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))      # (B,Kv,g,qc)
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = (acc * corr[..., None]
                   + jnp.einsum("bkgst,btkd->bkgsd", p, vs))
            return (acc, m_new, l), None

        acc0 = jnp.zeros((B, Kv, g, q_chunk, D), jnp.float32)
        m0 = jnp.full((B, Kv, g, q_chunk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, Kv, g, q_chunk), jnp.float32)
        ks_idx = jnp.arange(k_first, k_first + n_steps, dtype=jnp.int32)
        (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), ks_idx,
                                      unroll=True if INNER_UNROLL else 1)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))             # (B,Kv,g,qc)
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        outs.append(out.transpose(0, 3, 1, 2, 4))            # (B,qc,Kv,g,D)
        lses.append(lse)
    out = jnp.concatenate(outs, axis=1)[:, :S]
    lse = jnp.concatenate(lses, axis=3)[..., :S]             # (B,Kv,g,S)
    return out.reshape(B, S, Hq, D).astype(q.dtype), lse


def _flash(q, k, v, causal, window, q_offset, q_chunk, kv_chunk):
    out, _ = _flash_fwd_impl(q, k, v, causal, window, q_offset, q_chunk,
                             kv_chunk)
    return out


def _flash_fwd(q, k, v, causal, window, q_offset, q_chunk, kv_chunk):
    out, lse = _flash_fwd_impl(q, k, v, causal, window, q_offset, q_chunk,
                               kv_chunk)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, window, q_offset, q_chunk, kv_chunk, res, do):
    q, k, v, out, lse = res
    B, S, Hq, D = q.shape
    T, Kv = k.shape[1], k.shape[2]
    g = Hq // Kv
    q_chunk = min(q_chunk, S)
    kv_chunk = min(kv_chunk, T)
    nq, nk = -(-S // q_chunk), -(-T // kv_chunk)
    Sp, Tp = nq * q_chunk, nk * kv_chunk
    scale = 1.0 / jnp.sqrt(float(D))
    qp = jnp.pad(q, ((0, 0), (0, Sp - S), (0, 0), (0, 0))).reshape(
        B, Sp, Kv, g, D)
    kp = jnp.pad(k, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    dop = jnp.pad(do.astype(jnp.float32),
                  ((0, 0), (0, Sp - S), (0, 0), (0, 0))).reshape(
        B, Sp, Kv, g, D)
    lsep = jnp.pad(lse, ((0, 0), (0, 0), (0, 0), (0, Sp - S)))
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.pad(delta, ((0, 0), (0, Sp - S), (0, 0))).reshape(
        B, Sp, Kv, g).transpose(0, 2, 3, 1)                   # (B,Kv,g,Sp)
    q_ranges, kv_ranges = _chunk_ranges(nq, nk, q_chunk, kv_chunk, q_offset,
                                        causal, window)

    def recompute(qs, qpos, kj):
        ks = jax.lax.dynamic_slice_in_dim(
            kp, kj * kv_chunk, kv_chunk, 1).astype(jnp.float32)
        vs = jax.lax.dynamic_slice_in_dim(
            vp, kj * kv_chunk, kv_chunk, 1).astype(jnp.float32)
        s = jnp.einsum("bskgd,btkd->bkgst", qs, ks) * scale
        kpos = kj * kv_chunk + jnp.arange(kv_chunk)
        s = jnp.where(_mask_for(qpos, kpos, causal, window, T)[None, None,
                                                               None],
                      s, NEG_INF)
        return s, ks, vs

    # ---- dq: loop q chunks, scan kv chunks ----
    dqs = []
    for qi in range(nq):
        k_first, n_steps = q_ranges[qi]
        sl = lambda a: jax.lax.slice_in_dim(a, qi * q_chunk,
                                            (qi + 1) * q_chunk, axis=1)
        qs = sl(qp).astype(jnp.float32)
        dos = sl(dop)
        lse_q = jax.lax.slice_in_dim(lsep, qi * q_chunk, (qi + 1) * q_chunk,
                                     axis=3)
        delta_q = jax.lax.slice_in_dim(delta, qi * q_chunk,
                                       (qi + 1) * q_chunk, axis=3)
        qpos = qi * q_chunk + jnp.arange(q_chunk) + q_offset

        def body(dq_acc, kj, qs=qs, dos=dos, lse_q=lse_q, delta_q=delta_q,
                 qpos=qpos):
            s, ks, vs = recompute(qs, qpos, kj)
            p = jnp.exp(s - lse_q[..., None])
            dp = jnp.einsum("bskgd,btkd->bkgst", dos, vs)
            ds = p * (dp - delta_q[..., None]) * scale
            dq_acc = dq_acc + jnp.einsum("bkgst,btkd->bskgd", ds, ks)
            return dq_acc, None

        dq0 = jnp.zeros((B, q_chunk, Kv, g, D), jnp.float32)
        ks_idx = jnp.arange(k_first, k_first + n_steps, dtype=jnp.int32)
        dq_qi, _ = jax.lax.scan(body, dq0, ks_idx,
                                unroll=True if INNER_UNROLL else 1)
        dqs.append(dq_qi)
    dq = jnp.concatenate(dqs, axis=1)[:, :S].reshape(B, S, Hq, D)

    # ---- dk, dv: loop kv chunks, scan q chunks ----
    dks, dvs = [], []
    for kj in range(nk):
        q_first, n_steps = kv_ranges[kj]
        kpos = kj * kv_chunk + jnp.arange(kv_chunk)

        def body(carry, qi, kpos=kpos, kj=kj):
            dk_acc, dv_acc = carry
            qs = jax.lax.dynamic_slice_in_dim(
                qp, qi * q_chunk, q_chunk, 1).astype(jnp.float32)
            dos = jax.lax.dynamic_slice_in_dim(dop, qi * q_chunk, q_chunk, 1)
            lse_q = jax.lax.dynamic_slice_in_dim(lsep, qi * q_chunk, q_chunk, 3)
            delta_q = jax.lax.dynamic_slice_in_dim(delta, qi * q_chunk,
                                                   q_chunk, 3)
            qpos = qi * q_chunk + jnp.arange(q_chunk) + q_offset
            ks = jax.lax.dynamic_slice_in_dim(
                kp, kj * kv_chunk, kv_chunk, 1).astype(jnp.float32)
            vs = jax.lax.dynamic_slice_in_dim(
                vp, kj * kv_chunk, kv_chunk, 1).astype(jnp.float32)
            s = jnp.einsum("bskgd,btkd->bkgst", qs, ks) * scale
            s = jnp.where(_mask_for(qpos, kpos, causal, window, T)[None, None,
                                                                   None],
                          s, NEG_INF)
            p = jnp.exp(s - lse_q[..., None])
            dv_acc = dv_acc + jnp.einsum("bkgst,bskgd->btkd", p, dos)
            dp = jnp.einsum("bskgd,btkd->bkgst", dos, vs)
            ds = p * (dp - delta_q[..., None]) * scale
            dk_acc = dk_acc + jnp.einsum("bkgst,bskgd->btkd", ds, qs)
            return (dk_acc, dv_acc), None

        dk0 = jnp.zeros((B, kv_chunk, Kv, D), jnp.float32)
        dv0 = jnp.zeros((B, kv_chunk, Kv, D), jnp.float32)
        qs_idx = jnp.arange(q_first, q_first + n_steps, dtype=jnp.int32)
        (dk_kj, dv_kj), _ = jax.lax.scan(body, (dk0, dv0), qs_idx,
                                         unroll=True if INNER_UNROLL else 1)
        dks.append(dk_kj)
        dvs.append(dv_kj)
    dk = jnp.concatenate(dks, axis=1)[:, :T]
    dv = jnp.concatenate(dvs, axis=1)[:, :T]
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


_flash_vjp = jax.custom_vjp(_flash, nondiff_argnums=(3, 4, 5, 6, 7))
_flash_vjp.defvjp(_flash_fwd, _flash_bwd)


def sdpa_chunked(q, k, v, *, causal: bool, window: int = 0, q_offset: int = 0,
                 q_chunk: int = 2048, kv_chunk: int = 2048) -> jax.Array:
    """Flash attention in pure jnp (custom-VJP recompute backward)."""
    return _flash_vjp(q, k, v, causal, window, q_offset, q_chunk, kv_chunk)


def sdpa(q, k, v, *, causal: bool, window: int = 0, q_offset: int = 0,
         impl: str = "auto") -> jax.Array:
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "chunked"
    if impl == "pallas":
        from ..kernels.flash_attention import flash_attention
        return flash_attention(q, k, v, causal=causal, window=window,
                               q_offset=q_offset)
    if impl == "chunked":
        return sdpa_chunked(q, k, v, causal=causal, window=window,
                            q_offset=q_offset)
    return sdpa_ref(q, k, v, causal=causal, window=window, q_offset=q_offset)


# ------------------------------------------------------------------ train paths
def attention_train(p, x, positions, cfg, *, window: int = 0,
                    kv_src: Optional[jax.Array] = None,
                    impl: str = "auto") -> jax.Array:
    """Self- or cross-attention over a full sequence."""
    cross = kv_src is not None
    xkv = kv_src if cross else x
    q, k, v = _project_qkv(p, x, xkv, cfg)
    if not cross and cfg.rope_style != "none":
        q = apply_rope(q, positions, theta=cfg.rope_theta, style=cfg.rope_style,
                       fraction=cfg.rope_fraction)
        k = apply_rope(k, positions, theta=cfg.rope_theta, style=cfg.rope_style,
                       fraction=cfg.rope_fraction)
    causal = cfg.causal and not cross
    out = sdpa(q, k, v, causal=causal, window=0 if cross else window, impl=impl)
    return jnp.einsum("bshe,hed->bsd", out, p["wo"])


# ----------------------------------------------------------------- decode paths
#
# Decode attention contracts each GQA group of query heads against its KV head
# in the cache as stored: q (B,S,H,D) is viewed as (B,S,Kv,g,D), so no
# ``repeat``-ed (…,T,H,D) copy and no float32 copy of the cache is made. A bf16
# product is exact in float32, so the scores equal the upcast form's; scores,
# softmax, probs and both accumulations stay float32.
def _gqa_scores(q, k):
    """q (B,S,H,D) · k (B,T,Kv,D) -> float32 scores (B,Kv,g,S,T)."""
    B, S, H, D = q.shape
    Kv = k.shape[2]
    qg = q.reshape(B, S, Kv, H // Kv, D)
    return jnp.einsum("bskgd,btkd->bkgst", qg, k,
                      preferred_element_type=jnp.float32)


def _gqa_values(probs, v):
    """float32 probs (B,Kv,g,S,T) · v (B,T,Kv,D) -> float32 (B,S,H,D).

    ``HIGHEST`` keeps the probs float32: at the default precision the TPU
    rounds a float32 operand to bf16."""
    B, Kv, g, S, _ = probs.shape
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v,
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, S, Kv * g, v.shape[-1])


def init_kv_cache(batch: int, length: int, n_kv: int, head_dim: int, dtype):
    return {
        "k": jnp.zeros((batch, length, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, length, n_kv, head_dim), dtype),
    }


def attention_decode(p, x, cache, pos, cfg, *, window: int = 0,
                     layer=None, impl: str = "ref"):
    """One-token decode. ``cache`` holds (k, v) of capacity T (full) or W (ring).

    pos: scalar int32 — global position of the new token. Sliding-window layers
    use a ring buffer of capacity ``window``: slot = pos % window; masking is done
    via reconstructed slot positions, so the cache stays O(window) regardless of
    sequence length (this is what makes long_500k decode sub-quadratic AND
    sub-linear in memory for local layers).

    layer: where ``cache`` is a stack of layers (leaves (L, B, cap, Kv, D)),
    this layer's index in it. The new row is written into the stack at
    ``(layer, slot)`` and the layer is then read by index, so attention sees
    the same values as on one layer's cache; the whole stack is returned. A
    layer scan that carries the stack thus writes one row a layer in place.
    """
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    q, k_new, v_new = _project_qkv(p, x, x, cfg)
    posv = jnp.full((B, 1), pos, jnp.int32)
    if cfg.rope_style != "none":
        q = apply_rope(q, posv, theta=cfg.rope_theta, style=cfg.rope_style,
                       fraction=cfg.rope_fraction)
        k_new = apply_rope(k_new, posv, theta=cfg.rope_theta,
                           style=cfg.rope_style, fraction=cfg.rope_fraction)
    cap = cache["k"].shape[-3]
    slot = pos % cap if window else jnp.minimum(pos, cap - 1)
    if layer is None:
        k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, slot, 1)
        v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, slot, 1)
        new_cache = {"k": k, "v": v}
    else:
        at = (layer, 0, slot, 0, 0)
        new_cache = {
            "k": jax.lax.dynamic_update_slice(cache["k"], k_new[None], at),
            "v": jax.lax.dynamic_update_slice(cache["v"], v_new[None], at)}
        k, v = (jax.lax.dynamic_index_in_dim(new_cache[n], layer, 0,
                                             keepdims=False) for n in "kv")

    # reconstruct the global position of every slot for masking
    slots = jnp.arange(cap)
    if window:
        # ring: slot s holds position p with p ≡ s (mod cap), the largest p ≤ pos
        delta = (slot - slots) % cap
        slot_pos = pos - delta
        valid = (slot_pos >= 0) & (slot_pos > pos - window)
    else:
        slot_pos = slots
        valid = slots <= pos

    scores = _gqa_scores(q, k) / jnp.sqrt(float(hd))
    scores = jnp.where(valid[None, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _gqa_values(probs, v)
    out = jnp.einsum("bshe,hed->bsd", out.astype(x.dtype), p["wo"])
    return out, new_cache


def attention_verify(p, x, cache, pos, cfg):
    """Multi-token decode ("verify"): T new tokens at positions
    ``pos .. pos+T-1`` against an existing full-attention cache.

    The speculative decode window's verification pass: all T new K/V entries
    are written first (out-of-capacity positions are *dropped*, never clamped
    — a clamp would clobber the last in-range entry before an in-range query
    reads it), then every query attends over the full capacity with its own
    per-position causal mask. Each query row performs exactly the arithmetic
    of :func:`attention_decode` at that position (same projections, same rope,
    same full-capacity scores + masked softmax), so the verified logits — and
    the K/V entries left in the cache — are bit-equal to T sequential decode
    steps over the same tokens. Full (non-windowed) attention only: ring
    buffers can not absorb speculative over-writes (a rejected draft's write
    would destroy the ring entry a later real step still attends).
    """
    B, T = x.shape[:2]
    hd = cfg.resolved_head_dim
    q, k_new, v_new = _project_qkv(p, x, x, cfg)
    qpos = pos + jnp.arange(T, dtype=jnp.int32)
    posv = jnp.broadcast_to(qpos[None, :], (B, T))
    if cfg.rope_style != "none":
        q = apply_rope(q, posv, theta=cfg.rope_theta, style=cfg.rope_style,
                       fraction=cfg.rope_fraction)
        k_new = apply_rope(k_new, posv, theta=cfg.rope_theta,
                           style=cfg.rope_style, fraction=cfg.rope_fraction)
    cap = cache["k"].shape[1]
    k = cache["k"].at[:, qpos].set(k_new, mode="drop")
    v = cache["v"].at[:, qpos].set(v_new, mode="drop")

    slots = jnp.arange(cap)
    valid = slots[None, :] <= qpos[:, None]          # (T, cap) per-query mask

    scores = _gqa_scores(q, k) / jnp.sqrt(float(hd))
    scores = jnp.where(valid[None, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    # the probs·V contraction and the output projection are computed per
    # query row: XLA-CPU's tiling (hence accumulation order) for these two
    # ops depends on the number of query rows, so batched forms diverge from
    # the decode step in low-order bits; a T=1 slice has the decode step's
    # exact shapes and lowers identically at the capacities the serving
    # engines run and are fenced at (caps up to a few hundred — see
    # tests/test_serve_spec.py). At very large capacities the backend may
    # partition big contractions across threads, where bit-equality between
    # any two programs stops being guaranteeable; emitted tokens remain
    # full-model argmaxes (a self-consistent greedy stream), they may just
    # differ from the single-token engine near exact logit ties.
    rows = []
    for t in range(T):
        o_t = _gqa_values(probs[:, :, :, t:t + 1, :], v)
        rows.append(jnp.einsum("bshe,hed->bsd", o_t.astype(x.dtype),
                               p["wo"]))
    out = jnp.concatenate(rows, axis=1)
    return out, {"k": k, "v": v}


def cross_attention_decode(p, x, img_kv, cfg):
    """Decode-time cross attention against static (precomputed) image K/V."""
    B = x.shape[0]
    q, _, _ = _project_qkv(p, x, x, cfg)
    k, v = img_kv["k"], img_kv["v"]
    group = cfg.num_heads // cfg.num_kv_heads
    kr = jnp.repeat(k, group, axis=2)
    vr = jnp.repeat(v, group, axis=2)
    hd = cfg.resolved_head_dim
    scores = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32),
                        kr.astype(jnp.float32)) / jnp.sqrt(float(hd))
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", probs, vr.astype(jnp.float32))
    out = jnp.einsum("bshe,hed->bsd", out.astype(x.dtype), p["wo"])
    return out


def precompute_cross_kv(p, img_embeds, cfg):
    """Prefill-time K/V projection of the (stubbed) image embeddings."""
    k = jnp.einsum("bsd,dhe->bshe", img_embeds, p["wk"])
    v = jnp.einsum("bsd,dhe->bshe", img_embeds, p["wv"])
    if cfg.qk_norm:
        k = rms_norm_vec(k, p["k_norm"])
    return {"k": k, "v": v}
