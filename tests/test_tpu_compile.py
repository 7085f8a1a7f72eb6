"""Compile the main path's kernels and the serving windows of the benchmark's
configurations for a described TPU v5e chip, at real widths.

Nothing runs: the TPU compiler (installed with JAX) compiles for a ``v5e:2x2``
topology that is described, not attached, and refuses what the chip would
refuse — misaligned blocks, scalar stores to VMEM, programs that do not fit
the chip's memory. Interpret mode on the CPU catches none of these.

The topology is described inside a fixture, never at import: only one process
at a time may load the TPU library, and every test worker imports this file.
All such compiles live in this one file.
"""
import math
import os
import re

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


def _compile_window(sharding, name, slots, max_len=2048, window=8):
    """The overlapped K-step window over ``slots`` lanes of ``max_len``
    positions, params and caches in bf16, caches donated; returns the
    config, the compiled program and the caches' bytes."""
    from repro.configs import get_config
    from repro.launch.steps import make_prefill_decode_window
    from repro.models import build_model
    from repro.serve.replica import SERVE_PROBES
    cfg = get_config(name)
    model = build_model(cfg)
    put = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: _sds(sharding, x.shape, x.dtype), t)
    params = put(model.param_shapes())
    caches = jax.tree_util.tree_map(
        lambda x: _sds(sharding, (slots, *x.shape), x.dtype),
        model.cache_shapes(1, max_len))
    i32 = lambda *shape: _sds(sharding, shape, jnp.int32)  # noqa: E731
    fn = make_prefill_decode_window(cfg, SERVE_PROBES, window=window,
                                    donate=True)
    compiled = fn.lower(params, caches, i32(slots, 1, 1), i32(slots),
                        i32(window, slots), i32(slots)).compile()
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(caches))
    return cfg, compiled, cache_bytes


def test_qwen3_decode_window_compiles_and_fits_one_chip(one_chip):
    """The serving hot path chip_smoke.py runs: the overlapped K=8 window over
    8 slots of 2048 positions, params and caches in bf16, caches donated."""
    _, compiled, _ = _compile_window(one_chip, "qwen3-1.7b", slots=8)
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert mem.alias_size_in_bytes > 0, "caches were not donated"
    assert live < HBM_BYTES, f"window needs {live / 1e9:.1f} GB"


@pytest.mark.parametrize("name", ["qwen3-1.7b", "starcoder2-3b"])
def test_decode_window_reads_bf16_cache_in_place(one_chip, name):
    """At the benchmark cells' shapes (16 slots, 2048 positions, K=8) decode
    attention contracts GQA groups against the bf16 cache as stored: no
    float32 tensor holds a slot's cache repeated to every query head
    (positions x num_heads x head_dim) or upcast (positions x num_kv_heads x
    head_dim), per layer or for the whole stack, and the caches stay
    donated."""
    slots, max_len = 16, 2048
    cfg, compiled, cache_bytes = _compile_window(one_chip, name, slots,
                                                 max_len)
    hd = cfg.resolved_head_dim
    copies = {slots * n * max_len * heads * hd
              for heads in (cfg.num_heads, cfg.num_kv_heads)
              for n in (1, cfg.num_layers)}
    found = sorted({shape for shape in
                    re.findall(r"f32\[([0-9,]+)\]", compiled.as_text())
                    if math.prod(map(int, shape.split(","))) in copies})
    assert not found, f"float32 copies of the KV cache: {found}"
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes, \
        "caches were not donated"


def test_moe_decode_window_is_dropless_and_fits_one_chip(one_chip):
    """qwen3-moe-30b-a3b-ep16 at its benchmark cell's shapes (32 slots, 1024
    positions, K=8): the window fits the chip, and its expert layer has no
    capacity buffer, neither the (..., experts, capacity, d) buffers nor the
    flat (experts * capacity + 1, d) one the training path scatters tokens
    into."""
    from repro.models.moe import _capacity
    cfg, compiled, _ = _compile_window(one_chip, "qwen3-moe-30b-a3b-ep16",
                                       slots=32, max_len=1024)
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert mem.alias_size_in_bytes > 0, "caches were not donated"
    assert live < HBM_BYTES, f"window needs {live / 1e9:.1f} GB"
    H, C, d = cfg.experts_held, _capacity(1, cfg), cfg.d_model
    text = compiled.as_text()
    buffers = re.findall(rf"\[(?:[0-9]+,)*(?:{H},{C}|{H * C + 1}),{d}\]", text)
    assert not buffers, f"capacity buffers: {sorted(set(buffers))}"
    assert " scatter(" not in text


_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = \w+\[([0-9,]*)\]\S* ([\w-]+)\(", re.M)


@pytest.mark.parametrize("name,slots,max_len,temp_gb", [
    ("qwen3-1.7b", 16, 2048, 1.0),
    ("starcoder2-3b", 16, 2048, 1.0),
    ("qwen3-moe-30b-a3b-ep16", 32, 1024, 1.5),
])
def test_decode_window_moves_no_whole_cache(one_chip, name, slots, max_len,
                                            temp_gb):
    """At each benchmark cell's shapes the layer scan carries the slot-major
    period caches, leaves (S, L, 1, cap, Kv, D), and writes one row a layer
    in place: no copy, transpose or write-back builds a layer-major
    (L, S, 1, cap, Kv, D) stack, no copy or transpose moves a whole
    slot-major stack (the in-place row writes may output one), the
    temporaries stay small, and the caches stay donated."""
    from repro.models import build_model
    cfg, compiled, cache_bytes = _compile_window(one_chip, name, slots,
                                                 max_len)
    periods = build_model(cfg).cache_shapes(1, max_len)["periods"]
    leaves = [x.shape for x in jax.tree_util.tree_leaves(periods)
              if x.ndim == 5]
    assert leaves, f"{name} has no period-stacked KV cache"
    slot_major = {(slots, *s) for s in leaves}
    layer_major = {(s[0], slots, *s[1:]) for s in leaves}
    moved = []
    for instr, shape, op in _INSTRUCTION.findall(compiled.as_text()):
        dims = tuple(int(d) for d in shape.split(",") if d)
        relayout = (op in ("copy", "transpose")
                    or re.search("copy|transpose", instr))
        write_back = "dynamic-update-slice" in op + instr
        if ((dims in layer_major and (relayout or write_back))
                or (dims in slot_major and relayout)):
            moved.append(f"{instr} {op} {dims}")
    assert not moved, f"whole-cache moves: {moved}"
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < temp_gb * 1e9, \
        f"temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB"
    assert mem.alias_size_in_bytes >= cache_bytes, "caches were not donated"
