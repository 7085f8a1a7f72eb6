"""The decode stack against a per-layer loop: one layer sliced out of the
period stack, decoded on its own cache, and put back.

``apply_stack_decode`` carries the period caches through the layer scan and
writes one row (or a recurrent state) per layer in place. The loop below is
the arithmetic that form replaced; over a window of steps at per-slot
positions that differ, the two give bit-equal logits and caches for every
block kind. The smoke configurations run in float32: in bfloat16 XLA may keep
an intermediate in float32 inside a fusion, so two programs fused differently
are not comparable bit for bit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import build_model
from repro.models.layers import apply_norm, embed_tokens, unembed
from repro.models.transformer import apply_block_decode

MAX_LEN, STEPS = 32, 3
POS = np.array([0, 7, 19, 28], np.int32)   # sliding rings (16) wrap past 16

KINDS = [
    # (id, registry name, overrides)
    ("attn", "qwen3-1.7b", {}),
    ("attn-unrolled", "qwen3-1.7b", {"scan_layers": False}),
    ("sliding-ring", "starcoder2-3b", {}),
    ("sliding+attn", "gemma3-1b", {}),
    ("rglru+sliding", "recurrentgemma-2b", {}),
    ("ssd", "mamba2-2.7b", {}),
    ("cross", "llama-3.2-vision-11b", {}),
    ("moe", "qwen3-moe-30b-a3b", {}),
]


def _per_layer_decode(cfg, params, token, cache, pos):
    """One sequence, one token: every layer sliced out of its stack,
    decoded against its own cache, and written back whole."""
    dt = jnp.dtype(cfg.dtype)
    x = embed_tokens(params["embed"], token, dt)
    if cfg.embed_scale != 1.0:
        x = x * jnp.asarray(cfg.embed_scale, dt)
    periods = dict(cache["periods"])
    routed = 0
    for c in range(cfg.num_periods):
        for i, btype in enumerate(cfg.block_pattern):
            key = f"b{i}"
            pp = jax.tree_util.tree_map(lambda a: a[c],
                                        params["stack"]["periods"][key])
            pc = jax.tree_util.tree_map(lambda a: a[c], periods[key])
            x, nc, d = apply_block_decode(pp, x, pc, pos, cfg, btype)
            periods[key] = jax.tree_util.tree_map(
                lambda full, one: full.at[c].set(one), periods[key], nc)
            routed = routed + d
    rest = []
    for i, btype in enumerate(cfg.remainder_layers):
        x, nc, d = apply_block_decode(params["stack"]["rest"][i], x,
                                      cache["rest"][i], pos, cfg, btype)
        rest.append(nc)
        routed = routed + d
    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = unembed(params.get("unembed"), params["embed"], x,
                     cfg.tie_embeddings, cfg.logit_softcap)
    return logits, {"periods": periods, "rest": rest}, routed


@pytest.mark.parametrize("name,overrides", [k[1:] for k in KINDS],
                         ids=[k[0] for k in KINDS])
def test_stack_decode_equals_per_layer_loop(name, overrides):
    cfg = smoke_config(name).replace(**overrides)
    model = build_model(cfg)
    k_params, k_cache, k_tok = jax.random.split(jax.random.PRNGKey(0), 3)
    params = model.init(k_params)
    slots = len(POS)
    # every state starts non-zero, so what a layer reads shows in its output
    shapes = model.cache_shapes(1, MAX_LEN)
    leaves, tree = jax.tree_util.tree_flatten(shapes)
    keys = jax.random.split(k_cache, len(leaves))
    cache = jax.tree_util.tree_unflatten(tree, [
        jax.random.normal(k, (slots, *s.shape), jnp.float32).astype(s.dtype)
        for k, s in zip(keys, leaves)])

    new = jax.jit(jax.vmap(
        lambda p, t, c, pos: model.decode_step(p, t, c, pos, routed=True),
        in_axes=(None, 0, 0, 0)))
    old = jax.jit(jax.vmap(
        lambda p, t, c, pos: _per_layer_decode(cfg, p, t, c, pos),
        in_axes=(None, 0, 0, 0)))

    tokens = jax.random.randint(k_tok, (slots, 1, 1), 0, cfg.vocab_size)
    pos = jnp.asarray(POS)
    new_cache = old_cache = cache
    for step in range(STEPS):
        got, new_cache, got_routed = new(params, tokens, new_cache, pos)
        want, old_cache, want_routed = old(params, tokens, old_cache, pos)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=f"logits, step {step}")
        if cfg.is_moe:
            np.testing.assert_array_equal(np.asarray(got_routed),
                                          np.asarray(want_routed))
        tokens = jnp.argmax(got, axis=-1).astype(jnp.int32)
        pos = pos + 1
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(new_cache),
                            jax.tree_util.tree_leaves(old_cache)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32),
                                      err_msg=jax.tree_util.keystr(path))
