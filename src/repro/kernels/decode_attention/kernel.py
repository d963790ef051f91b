"""Pallas TPU paged flash-decode — single-token attention over KV pages.

This is the per-device kernel behind the distributed paged decode
(models.attention.paged_decode_attention): each device holds a page-
sharded slice of the KV cache (its "endpoint" in the paper's terms) and
scans its local pages with an online softmax; the cross-device combine is
a tiny psum outside the kernel.

Grid: (batch, kv_head, pages) with the page axis innermost; accumulator
state in VMEM scratch; `kv_len` rides in scalar-prefetch memory — the
address pre-share of the paper's MemSpecRd: the page index map can consult
it before the DMA is issued, so out-of-range pages are never fetched
(their iterations clamp to page 0 and the body is skipped).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, *refs, page: int,
                   n_pages: int, scale: float, logit_softcap: float,
                   quant: bool = False):
    if quant:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        o_ref, acc_ref, m_ref, l_ref = refs
    pj = pl.program_id(2)

    @pl.when(pj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    kv_len = len_ref[0]
    run = pj * page < kv_len

    @pl.when(run)
    def _body():
        q = q_ref[0, 0]                    # [G, D]
        k = k_ref[0, 0, 0]                 # [page, D]
        v = v_ref[0, 0, 0]                 # [page, D]
        if quant:
            # int8 pages: dequantize in-kernel with this page's fp32
            # scale, picked out of the head's [1, P] scale row by a
            # masked lane reduction; math stays f32
            sel = jax.lax.broadcasted_iota(
                jnp.int32, ks_ref.shape[2:], 1) == pj
            k = k.astype(jnp.float32) * jnp.sum(
                jnp.where(sel, ks_ref[0, 0], 0.0), axis=1, keepdims=True)
            v = v.astype(jnp.float32) * jnp.sum(
                jnp.where(sel, vs_ref[0, 0], 0.0), axis=1, keepdims=True)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [G, page]
        if logit_softcap > 0.0:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        pos = pj * page + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos < kv_len, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(pj == n_pages - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_flash_decode(q: jnp.ndarray, k_pages: jnp.ndarray,
                       v_pages: jnp.ndarray, kv_len: jnp.ndarray, *,
                       logit_softcap: float = 0.0,
                       interpret: bool = False,
                       k_scale: jnp.ndarray | None = None,
                       v_scale: jnp.ndarray | None = None) -> jnp.ndarray:
    """q: [B, Hkv, G, D]; pages: [B, Hkv, P, page, D]; kv_len scalar int32.

    Quantized cache: pass int8 pages plus fp32 ``k_scale``/``v_scale``
    [B, Hkv, P] (one symmetric scale per page per head); the kernel
    dequantizes each page block in VMEM right after the DMA, so only the
    int8 bytes cross the memory tiers. Returns [B, Hkv, G, D] (f32
    accumulation, q dtype out).
    """
    b, hkv, g, d = q.shape
    n_pages, page = k_pages.shape[2], k_pages.shape[3]
    scale = 1.0 / (d ** 0.5)
    kv_len = jnp.asarray(kv_len, jnp.int32).reshape(1)
    quant = k_scale is not None

    grid = (b, hkv, n_pages)
    kernel = functools.partial(
        _decode_kernel, page=page, n_pages=n_pages, scale=scale,
        logit_softcap=logit_softcap, quant=quant)

    # pages already read are never refetched; the index map clamps
    # out-of-range pages to 0 (their body is skipped via kv_len)
    def page_map(bi, hi, pj, len_ref):
        return (bi, hi, pj, 0, 0)

    # a head's scales ride as one full-extent [1, P] row (a block of one
    # scale would break the TPU's (8, 128) tiling); it is fetched once
    # per (b, hkv) since its block index does not move with the page
    def scale_map(bi, hi, pj, len_ref):
        return (bi, hi, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, g, d),
                     lambda bi, hi, pj, len_ref: (bi, hi, 0, 0)),
        pl.BlockSpec((1, 1, 1, page, d), page_map),
        pl.BlockSpec((1, 1, 1, page, d), page_map),
    ]
    operands = [kv_len, q, k_pages, v_pages]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, 1, n_pages), scale_map),
                     pl.BlockSpec((1, 1, 1, n_pages), scale_map)]
        operands += [k_scale.astype(jnp.float32)[:, :, None],
                     v_scale.astype(jnp.float32)[:, :, None]]

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, 1, g, d), lambda bi, hi, pj, len_ref: (bi, hi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, d), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret,
    )(*operands)
