"""Plain reference of a sparse-expert pre-norm decoder: Qwen3-MoE
(Qwen3-30B-A3B), one chip's share of an expert-parallel deployment.

Written from the published description, with nothing taken from the program
under test but the arrays the benchmark itself drew (``bench/weights.py``),
read by their place in the served layout:

    embed.embedding (V, d)            unembed.kernel (d, V), when untied
    stack.periods.b0.<leaf> (L, ...)  one slice per layer
      norm1, norm2: scale (d,)
      attn: wq (d, H, hd), wk/wv (d, KV, hd), wo (H, hd, d), q_norm/k_norm (hd,)
      moe:  router (d, E) float32; wi, wg (Eh, d, f), wo (Eh, f, d) for the
            Eh experts held here
    final_norm: scale (d,)

Each layer: ``x += attn(norm1(x)); x += moe(norm2(x))``. Attention is that
of Qwen3 (``dense_transformer.py``, whose helpers this file uses): causal,
rotary (half-split, base ``rope_theta``), grouped queries, an RMS norm of
each query and key head. The expert layer routes each token over all ``E``
experts: softmax of ``x @ router``, the top ``num_experts_per_tok``, their
probabilities renormalised to sum to one (``norm_topk_prob``); it returns
``sum over the chosen experts e of gate_e * (silu(x wg_e) * (x wi_e)) wo_e``.
No shared expert. Every layer is sparse (``decoder_sparse_step`` 1,
``mlp_only_layers`` empty).

One departure from the published model: the experts held by the other chips
of the deployment (``expert_shards`` chips, this one holding the contiguous
block ``expert_shard`` of ``num_experts_held``) are left out, so a chosen
expert that is not held here adds nothing, exactly as the program under test
computes its share without the exchange.

``precision="float32"`` computes every product in float32 at ``highest``
matmul precision, the router included. ``precision="fp8"`` is the control:
both operands of every product rounded to float8 e4m3 under a scale per
tensor (``dense_transformer._q8``), accumulated in float32.
"""
from __future__ import annotations

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np


def _load_dense():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "dense_transformer.py")
    spec = importlib.util.spec_from_file_location(
        "bench_ref_dense_transformer_helpers", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_dense = _load_dense()
_dot, _norm, _head_rms, _rope = (_dense._dot, _dense._norm, _dense._head_rms,
                                 _dense._rope)


def _attention(x, p, cfg, fp8: bool):
    B, S, _ = x.shape
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    a = p["attn"]
    h = _norm(x, p["norm1"], cfg)
    q = _dot("bsd,dhe->bshe", h, a["wq"], fp8)
    k = _dot("bsd,dhe->bshe", h, a["wk"], fp8)
    v = _dot("bsd,dhe->bshe", h, a["wv"], fp8)
    q = _head_rms(q, a["q_norm"], cfg["norm_eps"])
    k = _head_rms(k, a["k_norm"], cfg["norm_eps"])
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    q = q.reshape(B, S, KV, H // KV, hd)
    scores = _dot("bskgd,btkd->bkgst", q, k, fp8) / jnp.sqrt(float(hd))
    mask = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    o = _dot("bkgst,btkd->bskgd", probs, v, fp8).reshape(B, S, H, hd)
    return x + _dot("bshe,hed->bsd", o, a["wo"], fp8)


def _experts(x, p, cfg, fp8: bool):
    m = p["moe"]
    held = cfg["num_experts_held"]
    h = _norm(x, p["norm2"], cfg)
    probs = jax.nn.softmax(_dot("bsd,de->bse", h, m["router"], fp8), axis=-1)
    gate, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    mine = cfg["expert_shard"] * held + jnp.arange(held)
    w = jnp.sum(jnp.where(idx[..., None] == mine, gate[..., None], 0.0),
                axis=-2)                                      # (B, S, held)
    up = _dot("bsd,edf->bsef", h, m["wi"], fp8)
    act = jax.nn.silu(_dot("bsd,edf->bsef", h, m["wg"], fp8)) * up
    y = _dot("bsef,efd->bsed", act, m["wo"], fp8)
    return x + jnp.einsum("bsed,bse->bsd", y, w,
                          precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("cfg_items", "fp8"))
def _hidden(params, tokens, cfg_items, fp8):
    cfg = dict(cfg_items)
    x = jnp.take(params["embed"]["embedding"], tokens, axis=0).astype(
        jnp.float32)

    def body(x, p):
        return _experts(_attention(x, p, cfg, fp8), p, cfg, fp8), None

    x, _ = jax.lax.scan(body, x, params["stack"]["periods"]["b0"])
    return _norm(x, params["final_norm"], cfg)


def _cfg_key(cfg: dict) -> tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim", "norm",
            "norm_eps", "rope_theta", "num_experts_per_tok",
            "num_experts_held", "expert_shard")
    return tuple((k, cfg[k]) for k in keys)


def position_logits(params, cfg: dict, tokens, rows, *,
                    precision: str = "float32", chunk: int = 256):
    """Yield ``(row_index, logits (n, V) f32)`` blocks; the interface of
    ``dense_transformer.position_logits``: ``tokens`` (B, S) int32, each
    sequence right-padded; ``rows`` (N, 2) ``(b, s)`` positions whose
    next-token logits are wanted, unembedded ``chunk`` rows at a time."""
    fp8 = precision == "fp8"
    n = rows.shape[0]
    padded = np.zeros((-(-n // chunk) * chunk, 2), np.int32)
    padded[:n] = rows
    with jax.default_matmul_precision("highest"):
        h = _hidden(params, jnp.asarray(tokens, jnp.int32), _cfg_key(cfg),
                    fp8)
        picked = _dense._pick(h, jnp.asarray(padded))
        for i in range(0, n, chunk):
            lg = _dense._logits(
                params, jax.lax.dynamic_slice_in_dim(picked, i, chunk),
                bool(cfg["tie_word_embeddings"]), fp8)
            yield i, np.asarray(lg)[:min(chunk, n - i)]
