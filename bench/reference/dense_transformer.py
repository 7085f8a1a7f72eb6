"""Plain reference of a dense pre-norm decoder: Qwen3 and StarCoder2.

Written from the published descriptions, with nothing taken from the program
under test but the arrays the benchmark itself drew (``bench/weights.py``),
read by their place in the served layout:

    embed.embedding (V, d)            unembed.kernel (d, V), when untied
    stack.periods.b0.<leaf> (L, ...)  one slice per layer
      norm1, norm2: scale (d,), bias (d,) for LayerNorm
      attn: wq (d, H, hd), wk/wv (d, KV, hd), wo (H, hd, d), q_norm/k_norm (hd,)
      mlp:  wi (d, f), wg (d, f) for SwiGLU, wo (f, d)
    final_norm: scale (d,), bias (d,) for LayerNorm

Each layer: ``x += attn(norm1(x)); x += mlp(norm2(x))``. Attention is causal
with rotary embeddings (half-split, base ``rope_theta``) on queries and keys,
grouped queries (head ``h`` reads key/value head ``h // (H / KV)``), an
optional RMS norm of each query and key head (Qwen3), and an optional sliding
window (StarCoder2: key positions ``> q - window``). The MLP is SwiGLU
(``silu(x wg) * (x wi)``) or GeLU with the tanh form. Logits are
``norm(x) @ embedding.T`` when tied.

``precision="float32"`` computes every product in float32 at ``highest``
matmul precision. ``precision="fp8"`` is the control: the same arithmetic,
with both operands of every product rounded to float8 e4m3 under a scale per
tensor (its largest magnitude mapped to 448), accumulated in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

E4M3_MAX = 448.0


def _q8(x):
    """Round ``x`` to float8 e4m3 under a per-tensor scale; back in f32."""
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _dot(spec, a, b, fp8: bool):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _norm(x, p, cfg):
    eps = cfg["norm_eps"]
    scale = p["scale"].astype(jnp.float32)
    if cfg["norm"] == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return ((x - mu) / jnp.sqrt(var + eps) * scale
                + p["bias"].astype(jnp.float32))
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * scale


def _head_rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * scale.astype(jnp.float32)


def _rope(x, theta):
    """x: (B, S, heads, hd) at positions 0..S-1."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, p, cfg, fp8: bool):
    B, S, _ = x.shape
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    a = p["attn"]
    h = _norm(x, p["norm1"], cfg)
    q = _dot("bsd,dhe->bshe", h, a["wq"], fp8)
    k = _dot("bsd,dhe->bshe", h, a["wk"], fp8)
    v = _dot("bsd,dhe->bshe", h, a["wv"], fp8)
    if cfg["qk_norm"]:
        q = _head_rms(q, a["q_norm"], cfg["norm_eps"])
        k = _head_rms(k, a["k_norm"], cfg["norm_eps"])
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    q = q.reshape(B, S, KV, H // KV, hd)
    scores = _dot("bskgd,btkd->bkgst", q, k, fp8) / jnp.sqrt(float(hd))
    qpos, kpos = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    mask = kpos <= qpos
    if cfg.get("sliding_window"):
        mask &= kpos > qpos - cfg["sliding_window"]
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = _dot("bkgst,btkd->bskgd", probs, v, fp8).reshape(B, S, H, hd)
    x = x + _dot("bshe,hed->bsd", o, a["wo"], fp8)
    m = p["mlp"]
    h = _norm(x, p["norm2"], cfg)
    up = _dot("bsd,df->bsf", h, m["wi"], fp8)
    if cfg["hidden_act"] == "silu":
        act = jax.nn.silu(_dot("bsd,df->bsf", h, m["wg"], fp8)) * up
    else:
        c = jnp.sqrt(2.0 / jnp.pi)
        act = 0.5 * up * (1.0 + jnp.tanh(c * (up + 0.044715 * up ** 3)))
    return x + _dot("bsf,fd->bsd", act, m["wo"], fp8)


@functools.partial(jax.jit, static_argnames=("cfg_items", "fp8"))
def _hidden(params, tokens, cfg_items, fp8):
    cfg = dict(cfg_items)
    x = jnp.take(params["embed"]["embedding"], tokens, axis=0).astype(
        jnp.float32)
    layers = params["stack"]["periods"]["b0"]

    def body(x, p):
        return _layer(x, p, cfg, fp8), None

    x, _ = jax.lax.scan(body, x, layers)
    return _norm(x, params["final_norm"], cfg)


@functools.partial(jax.jit, static_argnames=("tied", "fp8"))
def _logits(params, rows, tied, fp8):
    w = (params["embed"]["embedding"].T if tied
         else params["unembed"]["kernel"])
    return _dot("nd,dv->nv", rows, w, fp8)


@jax.jit
def _pick(h, rows):
    return h[rows[:, 0], rows[:, 1]]


def _cfg_key(cfg: dict) -> tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "norm", "norm_eps", "qk_norm", "hidden_act", "rope_theta",
            "sliding_window")
    return tuple((k, cfg.get(k)) for k in keys)


def position_logits(params, cfg: dict, tokens, rows, *,
                    precision: str = "float32", chunk: int = 256):
    """Yield ``(row_index, logits (n, V) f32)`` blocks.

    ``tokens``: (B, S) int32, each sequence right-padded (causal, so padding
    changes no real position). ``rows``: (N, 2) int array of ``(b, s)``
    positions whose next-token logits are wanted. Hidden states are computed
    for the whole batch once, then the unembedding runs ``chunk`` rows at a
    time (the last block padded, so every block has one shape) so that a
    150k vocabulary fits.
    """
    fp8 = precision == "fp8"
    n = rows.shape[0]
    padded = np.zeros((-(-n // chunk) * chunk, 2), np.int32)
    padded[:n] = rows
    with jax.default_matmul_precision("highest"):
        h = _hidden(params, jnp.asarray(tokens, jnp.int32), _cfg_key(cfg),
                    fp8)
        picked = _pick(h, jnp.asarray(padded))
        for i in range(0, n, chunk):
            lg = _logits(params,
                         jax.lax.dynamic_slice_in_dim(picked, i, chunk),
                         bool(cfg["tie_word_embeddings"]), fp8)
            yield i, np.asarray(lg)[:min(chunk, n - i)]
