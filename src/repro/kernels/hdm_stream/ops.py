"""Jitted wrapper for the paged weight-streaming matmul."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.hdm_stream.kernel import paged_matmul


@functools.partial(jax.jit, static_argnames=("block_m", "block_n"))
def stream_matmul(x, w_pages, page_ids, *, block_m: int = 256,
                  block_n: int = 256):
    """y = x @ vstack(w_pages[page_ids]). See kernel.py."""
    return paged_matmul(x, w_pages, page_ids, block_m=block_m,
                        block_n=block_n, interpret=interpret_mode())
