"""Full SSD scan: Pallas intra-chunk kernel + jnp inter-chunk recurrence."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .kernel import ssd_intra_chunk


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def ssd_scan(x, dt, A, B, C, chunk: int = 128):
    """Same contract as ``models.ssm.ssd_chunked``:
    x:(b,s,h,p) dt:(b,s,h) A:(h,) B,C:(b,s,g,n) → y:(b,s,h,p)."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    L = min(chunk, s)
    assert s % L == 0, (s, L)
    nc = s // L
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    # kernel layout: heads before the chunk axis (see kernel.py)
    xd = (xf * dtf[..., None]).reshape(b, nc, L, h, p).transpose(0, 1, 3, 2, 4)
    abar = (dtf * A).reshape(b, nc, L, h)
    Bc = jnp.repeat(B, rep, axis=2).astype(jnp.float32).reshape(b, nc, L, h, n)
    Cc = jnp.repeat(C, rep, axis=2).astype(jnp.float32).reshape(b, nc, L, h, n)

    y_diag, states = ssd_intra_chunk(
        xd, abar.transpose(0, 1, 3, 2)[:, :, :, None, :],
        Bc.transpose(0, 1, 3, 2, 4), Cc.transpose(0, 1, 3, 2, 4),
        interpret=_use_interpret())
    y_diag = y_diag.transpose(0, 1, 3, 2, 4)             # (b,nc,L,h,p)

    # inter-chunk recurrence (tiny, sequential)
    cum = jnp.cumsum(abar, axis=2)                       # (b,nc,L,h)
    total = cum[:, :, -1]

    def step(hprev, inp):
        st, tot = inp
        return hprev * jnp.exp(tot)[..., None, None] + st, hprev

    h0 = jnp.zeros((b, h, p, n), jnp.float32)
    _, hprevs = jax.lax.scan(step, h0, (states.transpose(1, 0, 2, 3, 4),
                                        total.transpose(1, 0, 2)))
    hprevs = hprevs.transpose(1, 0, 2, 3, 4)             # (b,nc,h,p,n)

    decay_in = jnp.exp(cum)
    y_off = jnp.einsum("bclhn,bchpn,bclh->bclhp", Cc, hprevs, decay_in)
    return (y_diag + y_off).reshape(b, s, h, p).astype(x.dtype)
