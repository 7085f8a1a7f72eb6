"""Chunked (custom-VJP flash) attention vs naive oracle: values AND gradients."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import NEG_INF, sdpa_chunked, sdpa_ref

CASES = [
    # (B, S, T, Hq, Hkv, D, causal, window, q_chunk, kv_chunk)
    (2, 16, 16, 4, 2, 8, True, 0, 8, 8),
    (2, 16, 16, 4, 4, 8, False, 0, 8, 4),     # encoder (bidirectional, MHA)
    (1, 32, 32, 4, 1, 16, True, 8, 8, 8),     # sliding window, MQA
    (2, 24, 24, 6, 2, 8, True, 0, 8, 16),     # uneven chunk split
    (1, 17, 17, 2, 1, 8, True, 0, 8, 8),      # padding (S not chunk multiple)
    (1, 16, 16, 8, 2, 4, True, 5, 4, 4),      # window not chunk-aligned
]


@pytest.mark.parametrize("case", CASES)
def test_forward_matches_ref(case):
    B, S, T, Hq, Hkv, D, causal, window, qc, kc = case
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, Hkv, D), jnp.float32)
    got = sdpa_chunked(q, k, v, causal=causal, window=window,
                       q_chunk=qc, kv_chunk=kc)
    want = sdpa_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case", CASES)
def test_gradients_match_ref(case):
    B, S, T, Hq, Hkv, D, causal, window, qc, kc = case
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, Hkv, D), jnp.float32)

    def loss_chunked(q, k, v):
        o = sdpa_chunked(q, k, v, causal=causal, window=window,
                         q_chunk=qc, kv_chunk=kc)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        o = sdpa_ref(q, k, v, causal=causal, window=window)
        return jnp.sum(jnp.sin(o))

    g1 = jax.grad(loss_chunked, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"grad d{name}")


def test_bf16_dtypes():
    key = jax.random.PRNGKey(2)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (2, 16, 4, 8), jnp.bfloat16)
    k = jax.random.normal(ks[1], (2, 16, 2, 8), jnp.bfloat16)
    v = jax.random.normal(ks[2], (2, 16, 2, 8), jnp.bfloat16)
    got = sdpa_chunked(q, k, v, causal=True, q_chunk=8, kv_chunk=8)
    want = sdpa_ref(q, k, v, causal=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


# ------------------------------------------------------------- decode attention
def _decode_cfg(group, qk_norm=False):
    from repro.configs.base import ModelConfig
    return ModelConfig(name="t", family="dense", num_layers=1, d_model=64,
                       num_heads=2 * group, num_kv_heads=2, d_ff=128,
                       vocab_size=32, head_dim=32, qk_norm=qk_norm,
                       rope_theta=1e4)


def _decode_inputs(cfg, cap, seed):
    from repro.models.attention import init_attention
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    p = init_attention(ks[0], cfg, jnp.bfloat16)
    x = jax.random.normal(ks[1], (3, 1, cfg.d_model), jnp.bfloat16)
    shape = (3, cap, cfg.num_kv_heads, cfg.resolved_head_dim)
    cache = {"k": jax.random.normal(ks[2], shape, jnp.bfloat16),
             "v": jax.random.normal(ks[3], shape, jnp.bfloat16)}
    return p, x, cache


def _repeat_f32_decode(p, x, cache, pos, cfg, window):
    """The decode step as it was first written: KV repeated ``group``-fold
    to every query head, then upcast to float32."""
    from repro.models.attention import _project_qkv
    from repro.models.layers import apply_rope
    B, hd = x.shape[0], cfg.resolved_head_dim
    q, k_new, v_new = _project_qkv(p, x, x, cfg)
    posv = jnp.full((B, 1), pos, jnp.int32)
    q = apply_rope(q, posv, theta=cfg.rope_theta, style=cfg.rope_style)
    k_new = apply_rope(k_new, posv, theta=cfg.rope_theta,
                       style=cfg.rope_style)
    cap = cache["k"].shape[1]
    slot = pos % cap if window else min(pos, cap - 1)
    k = cache["k"].at[:, slot].set(k_new[:, 0])
    v = cache["v"].at[:, slot].set(v_new[:, 0])
    slots = np.arange(cap)
    if window:
        slot_pos = pos - (slot - slots) % cap
        valid = (slot_pos >= 0) & (slot_pos > pos - window)
    else:
        valid = slots <= pos
    group = cfg.num_heads // cfg.num_kv_heads
    kr = jnp.repeat(k, group, axis=2).astype(jnp.float32)
    vr = jnp.repeat(v, group, axis=2).astype(jnp.float32)
    scores = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32),
                        kr) / jnp.sqrt(float(hd))
    scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
    out = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(scores, axis=-1), vr)
    out = jnp.einsum("bshe,hed->bsd", out.astype(x.dtype), p["wo"])
    return out, {"k": k, "v": v}


CAP = 16
DECODE_CASES = [
    # (name, window, pos): full attention and a ring of capacity CAP
    ("full-first", 0, 0),
    ("full-mid", 0, CAP // 2 - 1),
    ("full-last", 0, CAP - 1),
    ("ring-before-wrap", CAP, CAP // 2),
    ("ring-after-wrap", CAP, 2 * CAP + 5),
]


@pytest.mark.parametrize("group", [1, 2, 12])
@pytest.mark.parametrize("case", DECODE_CASES, ids=[c[0] for c in DECODE_CASES])
def test_attention_decode_matches_repeat_f32_form(group, case):
    from repro.models.attention import attention_decode
    _, window, pos = case
    cfg = _decode_cfg(group, qk_norm=group == 2)
    p, x, cache = _decode_inputs(cfg, CAP, seed=group * 100 + pos)
    got, got_cache = jax.jit(
        lambda p, x, c, pos: attention_decode(p, x, c, pos, cfg,
                                              window=window))(
        p, x, cache, jnp.int32(pos))
    want, want_cache = _repeat_f32_decode(p, x, cache, pos, cfg, window)
    assert got.dtype == want.dtype == jnp.bfloat16
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(got_cache[name], np.float32),
                                      np.asarray(want_cache[name],
                                                 np.float32))
    # one bf16 rounding of the attention output, carried through ``wo``
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("group", [1, 2, 12])
def test_attention_verify_rows_equal_decode_steps(group):
    """Row t of ``attention_verify`` at ``pos`` is ``attention_decode`` at
    ``pos + t``, bit for bit, and so are the caches they leave."""
    from repro.models.attention import attention_decode, attention_verify
    cfg = _decode_cfg(group)
    T, pos = 4, 5
    p, _, cache = _decode_inputs(cfg, CAP, seed=group)
    x = jax.random.normal(jax.random.PRNGKey(7), (3, T, cfg.d_model),
                          jnp.bfloat16)
    out, ver_cache = jax.jit(lambda p, x, c: attention_verify(
        p, x, c, jnp.int32(pos), cfg))(p, x, cache)
    step = jax.jit(lambda p, x, c, pos: attention_decode(p, x, c, pos, cfg))
    c = cache
    for t in range(T):
        row, c = step(p, x[:, t:t + 1], c, jnp.int32(pos + t))
        np.testing.assert_array_equal(np.asarray(out[:, t:t + 1], np.float32),
                                      np.asarray(row, np.float32),
                                      err_msg=f"row {t}")
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(ver_cache[name], np.float32),
                                      np.asarray(c[name], np.float32))
