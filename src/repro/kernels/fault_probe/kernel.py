"""Pallas kernel: fused non-finite / overflow probe over a flat value stream.

Motivation (paper §II-A): soft-fault detection must run on *every* step over the
full gradient/parameter stream to be useful — so it has to ride the memory roofline.
A naive ``jnp.isfinite``+``jnp.abs``+``jnp.any`` chain materialises boolean
intermediates in HBM; this kernel reads each tile of the stream into VMEM once and
reduces it to a single uint32 error word in registers.

Design for TPU:
* the stream is reshaped to ``(rows, 128)`` (lane-aligned) by ``ops.py``;
* the grid walks row-blocks of ``block_rows`` (8-aligned, sublane-friendly);
* each grid step computes ``any(!isfinite)`` and ``any(|x| > threshold)`` on the VPU
  and bitwise-ORs the encoded word into a one-word accumulator that every grid
  step maps to (TPU grid steps execute sequentially on a core, so the accumulation
  is race-free; the same property holds in interpret mode). The threshold and the
  accumulator live in SMEM: Mosaic cannot store a scalar to VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Error-code bits are passed in as static ints to keep the kernel independent of the
# errors module (and the lattice usable from any layer).


def _probe_kernel(x_ref, thresh_ref, o_ref, *, nonfinite_code: int,
                  overflow_code: int):
    x = x_ref[...].astype(jnp.float32)
    finite = jnp.isfinite(x)
    nonfinite = jnp.any(jnp.logical_not(finite))
    # overflow check must ignore non-finite lanes (inf would always trip it)
    over = jnp.any(jnp.abs(jnp.where(finite, x, 0.0)) > thresh_ref[0])
    word = (jnp.where(nonfinite, jnp.uint32(nonfinite_code), jnp.uint32(0))
            | jnp.where(over, jnp.uint32(overflow_code), jnp.uint32(0)))

    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[0] = jnp.uint32(0)

    o_ref[0] = o_ref[0] | word


def probe_rows(x: jax.Array, threshold: jax.Array, *, nonfinite_code: int,
               overflow_code: int, block_rows: int = 256,
               interpret: bool = True) -> jax.Array:
    """Probe a ``(rows, 128)`` array; returns a scalar uint32 word."""
    rows, lanes = x.shape
    assert lanes == 128 and rows % block_rows == 0, (rows, lanes, block_rows)
    kernel = functools.partial(_probe_kernel, nonfinite_code=nonfinite_code,
                               overflow_code=overflow_code)
    out = pl.pallas_call(
        kernel,
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, 128), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1,), jnp.uint32),
        interpret=interpret,
    )(x, jnp.reshape(threshold, (1,)).astype(jnp.float32))
    return out[0]
