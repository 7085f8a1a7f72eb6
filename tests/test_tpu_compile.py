"""Compile the main path's kernels and the qwen3-1.7b serving window for a
described TPU v5e chip, at real widths.

Nothing runs: the TPU compiler (installed with JAX) compiles for a ``v5e:2x2``
topology that is described, not attached, and refuses what the chip would
refuse — misaligned blocks, scalar stores to VMEM, programs that do not fit
the chip's memory. Interpret mode on the CPU catches none of these.

The topology is described inside a fixture, never at import: only one process
at a time may load the TPU library, and every test worker imports this file.
All such compiles live in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 10**9          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _is_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# qwen3-1.7b attention: 16 query heads, 8 KV heads, head_dim 128
@pytest.mark.parametrize("seq", [100, 1000, 2048])
def test_flash_attention_compiles_at_qwen3_widths(one_chip, seq):
    from repro.kernels.flash_attention.kernel import flash_attention_fwd
    hq, hkv, d, blk = 16, 8, 128, 512
    b = min(blk, seq)
    padded = -(-seq // b) * b            # ops.py pads to a block multiple
    q = _sds(one_chip, (hq, padded, d), jnp.bfloat16)
    kv = _sds(one_chip, (hkv, padded, d), jnp.bfloat16)
    compiled = jax.jit(lambda q, k, v: flash_attention_fwd(
        q, k, v, causal=True, block_q=b, block_kv=b, seq_kv=seq,
        interpret=False)).lower(q, kv, kv).compile()
    assert _is_kernel(compiled)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fault_probe_compiles(one_chip, dtype):
    from repro.core.errors import ErrorCode
    from repro.kernels.fault_probe.kernel import probe_rows
    compiled = jax.jit(lambda x, t: probe_rows(
        x, t, nonfinite_code=int(ErrorCode.NONFINITE_GRAD),
        overflow_code=int(ErrorCode.OVERFLOW), block_rows=256,
        interpret=False)).lower(
            _sds(one_chip, (4096, 128), dtype), _sds(one_chip, ())).compile()
    assert _is_kernel(compiled)


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    from repro.configs import get_config
    from repro.kernels.ssd_scan.kernel import ssd_intra_chunk
    cfg = get_config("mamba2-2.7b")
    h, p, n, L = (cfg.ssm_nheads, cfg.ssm_head_dim, cfg.ssm_state_dim,
                  cfg.ssm_chunk)
    nc = 2048 // L
    s = lambda *shape: _sds(one_chip, shape)  # noqa: E731
    compiled = jax.jit(lambda x, a, b, c: ssd_intra_chunk(
        x, a, b, c, interpret=False)).lower(
            s(1, nc, h, L, p), s(1, nc, h, 1, L), s(1, nc, h, L, n),
            s(1, nc, h, L, n)).compile()
    assert _is_kernel(compiled)


def test_rglru_scan_compiles_at_recurrentgemma_widths(one_chip):
    from repro.configs import get_config
    from repro.kernels.rglru_scan.kernel import rglru_scan_blocks
    w = get_config("recurrentgemma-2b").resolved_lru_width
    x = _sds(one_chip, (1, 2048, w))
    compiled = jax.jit(lambda a, x: rglru_scan_blocks(
        a, x, block_w=128, interpret=False)).lower(x, x).compile()
    assert _is_kernel(compiled)


def test_qwen3_decode_window_compiles_and_fits_one_chip(one_chip):
    """The serving hot path chip_smoke.py runs: the overlapped K=8 window over
    8 slots of 2048 positions, params and caches in bf16, caches donated."""
    from repro.configs import get_config
    from repro.launch.steps import make_prefill_decode_window
    from repro.models import build_model
    from repro.serve.replica import SERVE_PROBES
    cfg = get_config("qwen3-1.7b")
    model = build_model(cfg)
    slots, max_len, window = 8, 2048, 8
    put = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: _sds(one_chip, x.shape, x.dtype), t)
    params = put(model.param_shapes())
    caches = jax.tree_util.tree_map(
        lambda x: _sds(one_chip, (slots, *x.shape), x.dtype),
        model.cache_shapes(1, max_len))
    i32 = lambda *shape: _sds(one_chip, shape, jnp.int32)  # noqa: E731
    fn = make_prefill_decode_window(cfg, SERVE_PROBES, window=window,
                                    donate=True)
    compiled = fn.lower(params, caches, i32(slots, 1, 1), i32(slots),
                        i32(window, slots), i32(slots)).compile()
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert mem.alias_size_in_bytes > 0, "caches were not donated"
    assert live < HBM_BYTES, f"window needs {live / 1e9:.1f} GB"
