"""Jitted wrapper: mamba2 model layout -> SSD kernel layout."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.mamba2_scan.kernel import ssd_scan


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd(xdt, bmat, cmat, log_a, *, chunk: int = 256):
    """Model layout: xdt [B,S,H,P]; b/c [B,S,N]; log_a [B,S,H].

    Returns y [B,S,H,P] (f32).
    """
    b, s, h, p = xdt.shape
    n = bmat.shape[2]
    chunk = min(chunk, s)
    assert s % chunk == 0
    c = s // chunk
    xk = jnp.moveaxis(xdt.reshape(b, c, chunk, h, p), 3, 1)   # [B,H,C,Q,P]
    bk = bmat.reshape(b, c, chunk, n).astype(jnp.float32)
    ck = cmat.reshape(b, c, chunk, n).astype(jnp.float32)
    la = jnp.cumsum(log_a.reshape(b, c, chunk, h), axis=2)
    la = jnp.moveaxis(la, 3, 1)                               # [B,H,C,Q]
    y = ssd_scan(xk.astype(jnp.float32), bk, ck, la,
                 interpret=interpret_mode())
    return jnp.moveaxis(y, 1, 3).reshape(b, s, h, p)
