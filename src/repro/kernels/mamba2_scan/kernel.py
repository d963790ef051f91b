"""Pallas TPU chunked SSD (Mamba2) scan.

Grid: (batch, heads, chunks) with the chunk axis innermost; the SSM state
h [P, N] persists in VMEM scratch across chunk iterations (the recurrent
carry), while each chunk's intra contribution is a masked quadratic on the
MXU — the same decomposition as the jnp path in repro.models.mamba2.

Inputs are pre-chunked by ops.py:
  xdt [B, H, C, Q, P]   (x * dt, f32)
  bc  [B, C, Q, N]      B matrix (shared across heads)
  cc  [B, C, Q, N]      C matrix
  la  [B, H, C, Q]      cumsum(log a) within chunk
Output: y [B, H, C, Q, P].

``la`` enters the kernel twice, as a [1, Q] row and a [Q, 1] column per
chunk: both blocks span their array's last two dimensions, as the TPU's
(8, 128) tiling requires, and the decay matrix and per-position weights
then need no in-kernel transpose.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _ssd_kernel(xdt_ref, b_ref, c_ref, la_row_ref, la_col_ref, y_ref, h_ref,
                *, chunk: int):
    cj = pl.program_id(2)

    @pl.when(cj == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    xq = xdt_ref[0, 0, 0]          # [Q, P]
    bq = b_ref[0, 0]               # [Q, N]
    cq = c_ref[0, 0]               # [Q, N]
    la_row = la_row_ref[0, 0, 0]   # [1, Q]
    la_col = la_col_ref[0, 0, 0]   # [Q, 1]
    h = h_ref[...]                 # [P, N]

    idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jdx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = idx >= jdx

    # intra-chunk: (C B^T) ⊙ decay, masked causal, times xdt
    g = jax.lax.dot_general(cq, bq, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [Q, Q]
    logdec = la_col - la_row
    dec = jnp.where(causal, jnp.exp(logdec), 0.0)
    y = jax.lax.dot_general(g * dec, xq, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [Q, P]

    # inter-chunk: incoming state decayed to each position
    ch = jax.lax.dot_general(cq, h, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Q, P]
    y = y + ch * jnp.exp(la_col)
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    # state update to the chunk end
    last = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1) == chunk - 1
    la_last = jnp.sum(jnp.where(last, la_row, 0.0), axis=1,
                      keepdims=True)                             # [1, 1]
    w = jnp.exp(la_last - la_col)                                # [Q, 1]
    h_new = jnp.exp(la_last) * h + jax.lax.dot_general(
        xq * w, bq, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                      # [P, N]
    h_ref[...] = h_new


def ssd_scan(xdt: jnp.ndarray, bc: jnp.ndarray, cc: jnp.ndarray,
             la: jnp.ndarray, *, interpret: bool = False) -> jnp.ndarray:
    """See module docstring for layouts. Returns y [B, H, C, Q, P]."""
    b, h, c, q, p = xdt.shape
    n = bc.shape[3]
    grid = (b, h, c)
    kernel = functools.partial(_ssd_kernel, chunk=q)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, q, p),
                         lambda bi, hi, cj: (bi, hi, cj, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda bi, hi, cj: (bi, cj, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda bi, hi, cj: (bi, cj, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, q),
                         lambda bi, hi, cj: (bi, hi, cj, 0, 0)),
            pl.BlockSpec((1, 1, 1, q, 1),
                         lambda bi, hi, cj: (bi, hi, cj, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, q, p),
                               lambda bi, hi, cj: (bi, hi, cj, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, c, q, p), jnp.float32),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xdt, bc, cc, la[..., None, :], la[..., None])
