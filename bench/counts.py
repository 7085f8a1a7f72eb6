"""Operations and bytes a dense decoder step needs, from its shapes alone.

The yardstick for roofline shares and utilisation: what the algorithm must
do, not what the program happens to do. For one token at position ``p``
(``p`` earlier positions in its cache):

- weights: every layer's matrices and norms once per step (shared by all
  the tokens of the step), and the unembedding matrix; an untied embedding
  table is read only at the rows looked up;
- KV: the ``p`` earlier positions read (at most the sliding window), one
  written, for each layer;
- operations: two per weight the token multiplies (projections, MLP,
  unembedding), and four per attended position, head and head dimension
  (scores and the weighted sum).

Sizes are read from a configuration file (``bench/configs/*.json``).
"""
from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def _layer_matrix_params(c: dict) -> int:
    d, H, KV, hd, f = (c["hidden_size"], c["num_attention_heads"],
                       c["num_key_value_heads"], c["head_dim"],
                       c["intermediate_size"])
    attn = d * H * hd + 2 * d * KV * hd + H * hd * d
    mlp = (3 if c["hidden_act"] == "silu" else 2) * d * f
    return attn + mlp


def matmul_params_per_token(c: dict) -> int:
    """Weights one token multiplies: every layer's matrices + unembedding."""
    return (c["num_hidden_layers"] * _layer_matrix_params(c)
            + c["vocab_size"] * c["hidden_size"])


def weight_bytes_per_step(c: dict) -> int:
    """Bytes of weights one step reads (excluding the looked-up rows)."""
    w = DTYPE_BYTES[c["torch_dtype"]]
    d, L = c["hidden_size"], c["num_hidden_layers"]
    norm_vecs = 2 * (2 if c["norm"] == "layernorm" else 1)     # per layer
    per_layer = (_layer_matrix_params(c) * w + norm_vecs * d * 4
                 + (2 * c["head_dim"] * w if c["qk_norm"] else 0))
    final = (2 if c["norm"] == "layernorm" else 1) * d * 4
    unembed = c["vocab_size"] * d * w
    return L * per_layer + final + unembed


def kv_bytes_per_position(c: dict) -> int:
    """K and V of one position over all layers."""
    return (2 * c["num_key_value_heads"] * c["head_dim"]
            * DTYPE_BYTES[c["torch_dtype"]] * c["num_hidden_layers"])


def attended(c: dict, p: int) -> int:
    """Earlier positions a token at position ``p`` attends to."""
    w = c.get("sliding_window")
    return min(p, w - 1) if w else p


def token_flops(c: dict, p: int) -> int:
    attn = (4 * c["num_hidden_layers"] * c["num_attention_heads"]
            * c["head_dim"] * (attended(c, p) + 1))
    return 2 * matmul_params_per_token(c) + attn


def step_cost(c: dict, positions) -> tuple[int, int]:
    """``(flops, bytes)`` of one step that feeds one token per active lane,
    lane ``i`` at position ``positions[i]``."""
    kv = kv_bytes_per_position(c)
    row = c["hidden_size"] * DTYPE_BYTES[c["torch_dtype"]]
    flops = sum(token_flops(c, p) for p in positions)
    nbytes = weight_bytes_per_step(c)
    for p in positions:
        nbytes += (attended(c, p) + 1) * kv
        if not c["tie_word_embeddings"]:
            nbytes += row                       # the embedding row looked up
    return flops, nbytes


def window_cost(c: dict, K: int, starts) -> tuple[int, int]:
    """A window of ``K`` steps, lanes starting at positions ``starts``."""
    flops = nbytes = 0
    for k in range(K):
        f, b = step_cost(c, [s + k for s in starts])
        flops += f
        nbytes += b
    return flops, nbytes
