"""Each Pallas kernel at the widths of the configurations it serves.

One case per kernel path, shared by ``chip_smoke.py`` (which runs every
case on the chip against its ``ref.py`` oracle) and
``tests/test_tpu_compile.py`` (which compiles every case for a described
v5e chip). Widths:

* decode attention — qwen3-1.7b decode: 8 slots, 8 KV heads of 128 with
  2 query heads each, 16 pages of 256 tokens (a 4096-token slot), bf16
  pages and int8 pages with per-(page, head) scales;
* flash attention — a 2048-token qwen3-1.7b prefill;
* paged matmul — qwen3-1.7b's MLP up-projection (2048 -> 6144) over 256
  tokens, its weight assembled from eight 256-row pages of a 16-page pool;
* SSD scan — zamba2-2.7b's mamba2 layer (80 heads of 64, state 64) over
  2048 tokens in 256-token chunks.

Every case is judged by ``rel_err``: the largest absolute error over the
largest absolute reference value. ``BOUND`` is 2e-2, about five bf16 ulps
(2^-8 relative each): the kernels round their outputs, and the attention
kernels their softmax weights, to bf16, while the oracles stay in f32.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.kernel import paged_flash_decode
from repro.kernels.decode_attention.ref import (paged_flash_decode_quant_ref,
                                                paged_flash_decode_ref)
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.hdm_stream.kernel import paged_matmul
from repro.kernels.hdm_stream.ref import paged_matmul_ref
from repro.kernels.mamba2_scan.kernel import ssd_scan
from repro.kernels.mamba2_scan.ref import ssd_scan_ref

BOUND = 2e-2


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """One kernel call: ``kernel(*args, interpret=...)`` vs ``ref(*args)``."""

    name: str
    kernel: Callable
    ref: Callable
    make_args: Callable[[jax.Array], Tuple]   # PRNG key -> arguments


def _decode_args(key, quant: bool):
    b, hkv, g, d, p, page = 8, 8, 2, 128, 16, 256
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, hkv, g, d), jnp.bfloat16)
    kv_len = jnp.int32(3000)
    shape = (b, hkv, p, page, d)
    if not quant:
        return (q, jax.random.normal(kk, shape, jnp.bfloat16),
                jax.random.normal(kv, shape, jnp.bfloat16), kv_len)
    k8 = jax.random.randint(kk, shape, -127, 128, jnp.int32).astype(jnp.int8)
    v8 = jax.random.randint(kv, shape, -127, 128, jnp.int32).astype(jnp.int8)
    ks = jax.random.uniform(jax.random.fold_in(kk, 1), (b, hkv, p),
                            jnp.float32, 0.5 / 127, 2.0 / 127)
    vs = jax.random.uniform(jax.random.fold_in(kv, 1), (b, hkv, p),
                            jnp.float32, 0.5 / 127, 2.0 / 127)
    return q, k8, v8, ks, vs, kv_len


def _decode_quant_kernel(q, k8, v8, ks, vs, kv_len, *, interpret):
    return paged_flash_decode(q, k8, v8, kv_len, interpret=interpret,
                              k_scale=ks, v_scale=vs)


def _flash_args(key):
    b, hkv, g, s, d = 1, 8, 2, 2048, 128
    kq, kk, kv = jax.random.split(key, 3)
    return (jax.random.normal(kq, (b, hkv, g, s, d), jnp.bfloat16),
            jax.random.normal(kk, (b, hkv, s, d), jnp.bfloat16),
            jax.random.normal(kv, (b, hkv, s, d), jnp.bfloat16))


def _matmul_args(key):
    m, k, n, page_k, pool = 256, 2048, 6144, 256, 16
    kx, kw, kp = jax.random.split(key, 3)
    x = jax.random.normal(kx, (m, k), jnp.bfloat16)
    w = (jax.random.normal(kw, (pool, page_k, n), jnp.float32)
         / k ** 0.5).astype(jnp.bfloat16)
    page_ids = jax.random.permutation(kp, pool)[:k // page_k]
    return x, w, page_ids.astype(jnp.int32)


def _ssd_args(key):
    b, h, c, q, p, n = 1, 80, 8, 256, 64, 64
    kx, kb, kc, ka = jax.random.split(key, 4)
    xdt = jax.random.normal(kx, (b, h, c, q, p), jnp.float32) * 0.1
    bc = jax.random.normal(kb, (b, c, q, n), jnp.float32)
    cc = jax.random.normal(kc, (b, c, q, n), jnp.float32)
    log_a = -jax.random.uniform(ka, (b, h, c, q), jnp.float32, 1e-3, 0.1)
    return xdt, bc, cc, jnp.cumsum(log_a, axis=-1)


CASES = (
    KernelCase("paged_flash_decode_bf16", paged_flash_decode,
               paged_flash_decode_ref,
               lambda key: _decode_args(key, quant=False)),
    KernelCase("paged_flash_decode_int8", _decode_quant_kernel,
               paged_flash_decode_quant_ref,
               lambda key: _decode_args(key, quant=True)),
    KernelCase("flash_attention", flash_attention, flash_attention_ref,
               _flash_args),
    KernelCase("paged_matmul", paged_matmul, paged_matmul_ref, _matmul_args),
    KernelCase("ssd_scan", ssd_scan, ssd_scan_ref, _ssd_args),
)


def rel_err(out, ref) -> float:
    """Largest absolute error over the largest absolute reference value."""
    out = jnp.asarray(out, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    return float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
