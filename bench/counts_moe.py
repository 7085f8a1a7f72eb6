"""Operations and bytes a sparse-expert decoder step needs, from its shapes
and the routing the program counted.

The yardstick of the MoE roofline share and utilisation; ``counts.py``
gives the dense part (attention, norms, embeddings and unembedding, the KV
read up to each lane's position), called with an MLP of width zero. On top
of it, for every layer of a step:

- bytes: the held experts' weights once (``num_experts_held`` SwiGLU
  experts of ``moe_intermediate_size``, shared by all the tokens of the
  step) and the float32 router;
- operations: two per router weight for each token, and two per expert
  weight (``3 · d · f``) for each (token, held expert) pair the router chose,
  a count the program reports per window (``moe_pairs``, summed over steps,
  lanes and layers). Pairs routed to experts held elsewhere cost nothing
  here.

Sizes are read from a configuration file (``bench/configs/*.json``).
"""
from __future__ import annotations

import counts


def _dense(c: dict) -> dict:
    return dict(c, intermediate_size=0)


def expert_params(c: dict) -> int:
    """Weights of one SwiGLU expert."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def moe_bytes_per_step(c: dict) -> int:
    """Held experts' and router bytes one step reads, all layers."""
    w = counts.DTYPE_BYTES[c["torch_dtype"]]
    per_layer = (c["num_experts_held"] * expert_params(c) * w
                 + c["hidden_size"] * c["num_experts"] * 4)
    return c["num_hidden_layers"] * per_layer


def weight_bytes_per_step(c: dict) -> int:
    return counts.weight_bytes_per_step(_dense(c)) + moe_bytes_per_step(c)


def router_flops_per_token(c: dict) -> int:
    return 2 * c["num_hidden_layers"] * c["hidden_size"] * c["num_experts"]


def step_cost(c: dict, positions, pairs: int) -> tuple[int, int]:
    """``(flops, bytes)`` of one step that feeds one token per active lane,
    lane ``i`` at position ``positions[i]``, with ``pairs`` (token, held
    expert) pairs routed here over all layers."""
    flops, nbytes = counts.step_cost(_dense(c), positions)
    flops += (len(positions) * router_flops_per_token(c)
              + 2 * expert_params(c) * pairs)
    return flops, nbytes + moe_bytes_per_step(c)


def window_cost(c: dict, K: int, starts, pairs: int) -> tuple[int, int]:
    """A window of ``K`` steps, lanes starting at positions ``starts``, with
    ``pairs`` routed here over the whole window."""
    flops = 2 * expert_params(c) * pairs
    nbytes = 0
    for k in range(K):
        f, b = step_cost(c, [s + k for s in starts], 0)
        flops += f
        nbytes += b
    return flops, nbytes
