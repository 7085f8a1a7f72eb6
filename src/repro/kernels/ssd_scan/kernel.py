"""Pallas TPU kernel for the Mamba-2 SSD *intra-chunk* computation.

One grid cell = one (batch, chunk, head): loads the chunk's x·dt (L,P), B/C
(L,N) and per-step log-decay ā (1,L) into VMEM and produces

  * ``y_diag``  (L,P): the causal 'attention-like' intra-chunk term
    ``(C Bᵀ ⊙ exp(segsum ā)) · x``  — one L×L decay matrix built in-register,
  * ``state``   (P,N): the chunk's contribution to the inter-chunk recurrence
    ``Σ_j exp(cum_L − cum_j) B_j ⊗ x_j``.

The O(S/L)-length inter-chunk scan and the rank-1 ``y_off`` correction stay in
jnp (``ops.py``) — they are tiny and sequential. Chunk length L and state width N
are 128 by default (MXU-aligned); P = head_dim = 64 for mamba2-2.7b (sublane-
aligned).

Layout: the head axis sits *before* the chunk axis L, so every block's two
minor dims are ``(L, P)``, ``(L, N)``, ``(P, N)`` or ``(1, L)`` — each either
(8, 128)-aligned or the array's full extent, as Mosaic requires. Mosaic has no
cumsum, so the within-chunk prefix sum of ā is a matmul against a triangular
ones matrix (full f32 precision), once as a column and once as a row.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_HIGHEST = jax.lax.Precision.HIGHEST


def _ssd_chunk_kernel(x_ref, a_ref, b_ref, c_ref, y_ref, st_ref):
    x = x_ref[0, 0, 0].astype(jnp.float32)            # (L, P)
    a = a_ref[0, 0, 0].astype(jnp.float32)            # (1, L)
    b = b_ref[0, 0, 0].astype(jnp.float32)            # (L, N)
    c = c_ref[0, 0, 0].astype(jnp.float32)            # (L, N)
    L = x.shape[0]
    tril = (jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (L, L), 1))
    ones = tril.astype(jnp.float32)
    nt = (((1,), (1,)), ((), ()))                     # A · Bᵀ
    cum_col = jax.lax.dot_general(ones, a, nt, precision=_HIGHEST)  # (L, 1)
    cum_row = jax.lax.dot_general(a, ones, nt, precision=_HIGHEST)  # (1, L)
    decay = jnp.where(tril, jnp.exp(cum_col - cum_row), 0.0)       # (L, L)
    scores = jax.lax.dot_general(c, b, nt)                          # (L, L)
    y = jax.lax.dot_general(scores * decay, x, (((1,), (0,)), ((), ())))
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)
    total = jnp.sum(a, axis=1, keepdims=True)         # (1, 1) = cum_L
    dstates = jnp.exp(total - cum_col)                # (L, 1)
    st = jax.lax.dot_general(x * dstates, b,
                             (((0,), (0,)), ((), ())))  # (P, N)
    st_ref[0, 0, 0] = st.astype(st_ref.dtype)


def ssd_intra_chunk(xd, abar, B, C, *, interpret: bool = True):
    """xd: (b,nc,h,L,p); abar: (b,nc,h,1,L); B,C: (b,nc,h,L,n) (heads already
    broadcast). Returns (y_diag (b,nc,h,L,p), states (b,nc,h,p,n))."""
    b, nc, h, L, p = xd.shape
    n = B.shape[-1]

    def idx(bi, ci, hi):
        return (bi, ci, hi, 0, 0)

    y, st = pl.pallas_call(
        _ssd_chunk_kernel,
        grid=(b, nc, h),
        in_specs=[
            pl.BlockSpec((1, 1, 1, L, p), idx),
            pl.BlockSpec((1, 1, 1, 1, L), idx),
            pl.BlockSpec((1, 1, 1, L, n), idx),
            pl.BlockSpec((1, 1, 1, L, n), idx),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, L, p), idx),
            pl.BlockSpec((1, 1, 1, p, n), idx),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nc, h, L, p), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, h, p, n), jnp.float32),
        ],
        interpret=interpret,
    )(xd, abar, B, C)
    return y, st
