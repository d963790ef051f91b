"""Attention: chunked (flash-style) causal/full attention + decode step.

``chunked_attention`` is the framework's default sequence-mixing path: an
online-softmax scan over KV blocks that never materializes the [S, S] score
matrix — algorithmically identical to the Pallas flash kernel in
``repro.kernels.flash_attention`` (which is the TPU fast path; this jnp
version is also its oracle shape). Memory per step is O(S·block) instead of
O(S^2), which is what lets the 32k-prefill dry-run cells fit.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import (dense_init, head_rmsnorm, apply_rope,
                                 inner_unroll, pdtype)

NEG_INF = -1e30


def attn_init(key, cfg: ModelConfig, cross: bool = False) -> Dict:
    d, dt = cfg.d_model, pdtype(cfg)
    ks = jax.random.split(key, 6)
    p = {"wq": dense_init(ks[0], d, cfg.q_dim, dt),
         "wk": dense_init(ks[1], d, cfg.kv_dim, dt),
         "wv": dense_init(ks[2], d, cfg.kv_dim, dt),
         "wo": dense_init(ks[3], cfg.q_dim, d, dt)}
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((cfg.head_dim,), dtype=dt)
        p["k_norm"] = jnp.ones((cfg.head_dim,), dtype=dt)
    return p


def _split_heads(x, n_heads, head_dim):
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, head_dim)


def qkv_project(params: Dict, cfg: ModelConfig, x: jnp.ndarray,
                positions: jnp.ndarray, fuse_qkv: bool = True,
                rope: bool = True):
    """x: [B, S, d] -> q [B,S,H,D], k/v [B,S,Hkv,D] with qk-norm + RoPE."""
    if fuse_qkv:
        wqkv = jnp.concatenate([params["wq"], params["wk"], params["wv"]],
                               axis=1)
        qkv = x @ wqkv
        q, k, v = jnp.split(qkv, [cfg.q_dim, cfg.q_dim + cfg.kv_dim], axis=-1)
    else:
        q, k, v = x @ params["wq"], x @ params["wk"], x @ params["wv"]
    q = _split_heads(q, cfg.n_heads, cfg.head_dim)
    k = _split_heads(k, cfg.n_kv_heads, cfg.head_dim)
    v = _split_heads(v, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = head_rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = head_rmsnorm(params["k_norm"], k, cfg.norm_eps)
    if rope and cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      causal: bool = True, q_block: int = 512,
                      kv_block: int = 512,
                      logit_softcap: float = 0.0) -> jnp.ndarray:
    """Flash-style attention. q: [B,Sq,H,D], k/v: [B,Skv,Hkv,D].

    GQA: H must be a multiple of Hkv. Returns [B, Sq, H, D].
    Causal masking assumes q and k cover the same [0, S) positions.
    """
    b, sq, h, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    group = h // hkv
    q_block = min(q_block, sq)
    kv_block = min(kv_block, skv)
    assert sq % q_block == 0
    kv_valid = skv
    if skv % kv_block:                   # pad + mask (e.g. 1601 vision toks)
        pad = kv_block - skv % kv_block
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        skv += pad
    nq, nkv = sq // q_block, skv // kv_block
    scale = 1.0 / (d ** 0.5)

    # [B, nq, Bq, Hkv, G, D] / [B, nkv, Bk, Hkv, D]
    qb = q.reshape(b, nq, q_block, hkv, group, d)
    kb = k.reshape(b, nkv, kv_block, hkv, d)
    vb = v.reshape(b, nkv, kv_block, hkv, d)

    q_pos = (jnp.arange(nq)[:, None] * q_block
             + jnp.arange(q_block)[None, :])            # [nq, Bq]

    def kv_step(carry, inputs):
        acc, m_prev, l_prev = carry                     # acc [B,nq,Bq,Hkv,G,D]
        kj, vj, j = inputs                              # kj [B,Bk,Hkv,D]
        s = jnp.einsum("bnqhgd,bkhd->bnqhgk", qb, kj,
                       preferred_element_type=jnp.float32) * scale
        if logit_softcap > 0.0:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        kv_pos = j * kv_block + jnp.arange(kv_block)           # [Bk]
        if causal:
            mask = q_pos[:, :, None] >= kv_pos[None, None, :]  # [nq,Bq,Bk]
            s = jnp.where(mask[None, :, :, None, None, :], s, NEG_INF)
        if kv_valid != skv:
            vmask = kv_pos < kv_valid                          # [Bk]
            s = jnp.where(vmask[None, None, None, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bnqhgk,bkhd->bnqhgd", p,
                        vj.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        acc = acc * alpha[..., None] + pv
        return (acc, m_new, l_new), None

    acc0 = jnp.zeros((b, nq, q_block, hkv, group, d), jnp.float32)
    m0 = jnp.full((b, nq, q_block, hkv, group), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, nq, q_block, hkv, group), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        kv_step, (acc0, m0, l0),
        (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), jnp.arange(nkv)),
        unroll=inner_unroll())
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, sq, h, d).astype(q.dtype)


def _flash_decode_partial(q: jnp.ndarray, k_cache: jnp.ndarray,
                          v_cache: jnp.ndarray, kv_len: jnp.ndarray,
                          kv_block: int = 2048,
                          logit_softcap: float = 0.0):
    """Unnormalized flash-decode over one (possibly local) cache.

    q: [B,1,H,D]; caches [B,Smax,Hkv,D]; ``kv_len`` (scalar or [B]) masks
    the unwritten tail. Returns the online-softmax partials
    (acc [B,Hkv,G,D], m [B,Hkv,G], l [B,Hkv,G]) — combinable across
    shards/pages.
    """
    b, _, h, d = q.shape
    smax, hkv = k_cache.shape[1], k_cache.shape[2]
    group = h // hkv
    kv_block = min(kv_block, smax)
    assert smax % kv_block == 0
    nkv = smax // kv_block
    scale = 1.0 / (d ** 0.5)
    qh = q.reshape(b, hkv, group, d)
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len), (b,))

    kb = jnp.moveaxis(k_cache.reshape(b, nkv, kv_block, hkv, d), 1, 0)
    vb = jnp.moveaxis(v_cache.reshape(b, nkv, kv_block, hkv, d), 1, 0)

    def kv_step(carry, inputs):
        acc, m_prev, l_prev = carry
        kj, vj, j = inputs
        s = jnp.einsum("bhgd,bkhd->bhgk", qh, kj,
                       preferred_element_type=jnp.float32) * scale
        if logit_softcap > 0.0:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        pos = j * kv_block + jnp.arange(kv_block)             # [Bk]
        mask = pos[None, :] < kv_len[:, None]                 # [B, Bk]
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bhgk,bkhd->bhgd", p, vj.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        acc = acc * alpha[..., None] + pv
        return (acc, m_new, l_new), None

    # initial carry derived from q AND k so its varying-manual-axes cover
    # every axis the scan body produces when this runs inside a shard_map
    # region (paged decode: q varies over batch axes, k over page axes)
    zk = (k_cache.reshape(-1)[0] * 0).astype(jnp.float32)
    q0 = qh.astype(jnp.float32)
    acc0 = q0 * 0.0 + zk                              # [B,Hkv,G,D]
    m0 = q0[..., 0] * 0.0 + zk + NEG_INF              # [B,Hkv,G]
    l0 = q0[..., 0] * 0.0 + zk
    (acc, m, l), _ = jax.lax.scan(kv_step, (acc0, m0, l0),
                                  (kb, vb, jnp.arange(nkv)),
                                  unroll=inner_unroll())
    return acc, m, l


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, kv_len: jnp.ndarray,
                     kv_block: int = 2048,
                     logit_softcap: float = 0.0) -> jnp.ndarray:
    """Single-token flash-decode. q: [B,1,H,D]; caches [B,Smax,Hkv,D]."""
    b, _, h, d = q.shape
    acc, m, l = _flash_decode_partial(q, k_cache, v_cache, kv_len,
                                      kv_block, logit_softcap)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, 1, h, d).astype(q.dtype)


def chunk_prefill_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                            v_cache: jnp.ndarray, pos: jnp.ndarray,
                            kv_block: int = 2048,
                            logit_softcap: float = 0.0) -> jnp.ndarray:
    """Multi-query flash-decode for chunked prefill against a live cache.

    q: [B, C, H, D] — a chunk of C fresh tokens whose K/V were already
    written into the caches at [pos, pos+C) (per-row ``pos``, int32 [B]).
    caches: [B, Smax, Hkv, D]. Query i of row b attends to cache positions
    <= pos[b] + i (prior context plus the intra-chunk causal prefix).
    Returns [B, C, H, D].
    """
    b, c, h, d = q.shape
    smax, hkv = k_cache.shape[1], k_cache.shape[2]
    group = h // hkv
    kv_block = min(kv_block, smax)
    assert smax % kv_block == 0
    nkv = smax // kv_block
    scale = 1.0 / (d ** 0.5)
    qh = q.reshape(b, c, hkv, group, d)
    limit = (jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))[:, None]
             + jnp.arange(c, dtype=jnp.int32)[None])          # [B, C]

    kb = jnp.moveaxis(k_cache.reshape(b, nkv, kv_block, hkv, d), 1, 0)
    vb = jnp.moveaxis(v_cache.reshape(b, nkv, kv_block, hkv, d), 1, 0)

    def kv_step(carry, inputs):
        acc, m_prev, l_prev = carry                 # acc [B,C,Hkv,G,D]
        kj, vj, j = inputs                          # kj [B,Bk,Hkv,D]
        s = jnp.einsum("bchgd,bkhd->bchgk", qh, kj,
                       preferred_element_type=jnp.float32) * scale
        if logit_softcap > 0.0:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        kv_pos = j * kv_block + jnp.arange(kv_block)            # [Bk]
        mask = kv_pos[None, None, :] <= limit[:, :, None]       # [B, C, Bk]
        s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bchgk,bkhd->bchgd", p, vj.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        acc = acc * alpha[..., None] + pv
        return (acc, m_new, l_new), None

    acc0 = jnp.zeros((b, c, hkv, group, d), jnp.float32)
    m0 = jnp.full((b, c, hkv, group), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, c, hkv, group), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(kv_step, (acc0, m0, l0),
                                  (kb, vb, jnp.arange(nkv)),
                                  unroll=inner_unroll())
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, c, h, d).astype(q.dtype)


def paged_decode_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray, new_k: jnp.ndarray,
                           new_v: jnp.ndarray, pos: jnp.ndarray, *,
                           batch_axes, page_axes,
                           kv_block: int = 2048,
                           logit_softcap: float = 0.0,
                           force_shard_map: bool = False,
                           k_scale: Optional[jnp.ndarray] = None,
                           v_scale: Optional[jnp.ndarray] = None):
    """Distributed flash-decode over a page-sharded KV cache (shard_map).

    q: [B,1,H,D]; new_k/new_v: [B,1,Hkv,D]; pages: [B,P,page,Hkv,D] with
    the page axis sharded over ``page_axes``. Each rank owns a contiguous
    token range: the rank holding page(pos) writes the new KV (the paper's
    HDM decoder routes the store to the owning root port/EP), every rank
    runs a local flash-decode over its own pages, and the online-softmax
    partials combine with one tiny pmax/psum pair over ``page_axes`` — the
    cross-root-port read combine. Returns (o [B,1,H,D], k_pages',
    v_pages').

    Quantized cache (``kv_quant="int8"``): pass int8 pages plus fp32
    ``k_scale``/``v_scale`` [B,P,Hkv]. The pages are dequantized before
    the write + flash-decode (decode math stays fp32) and requantized
    with monotone per-page scale growth afterwards, so untouched pages
    round-trip bit-exactly. Returns a 5-tuple (o, k_pages', v_pages',
    k_scale', v_scale') in that case.

    ``force_shard_map`` disables the single-rank fast path so the
    shard_map body runs even on degenerate (size-1) axes — the two paths
    must be numerically identical, and the differential parity suite
    exercises exactly that.
    """
    from jax.sharding import PartitionSpec as P

    from repro.models import kv_quant as kvq

    quantized = k_scale is not None
    b, _, h, d = q.shape
    hkv = k_pages.shape[3]
    group = h // hkv

    def _axes_size(axes):
        mesh = jax.sharding.get_abstract_mesh()
        if axes is None or mesh is None or mesh.empty:
            return 1
        sizes = dict(mesh.shape)
        group_ = axes if isinstance(axes, tuple) else (axes,)
        n = 1
        for a in group_:
            n *= sizes.get(a, 1)
        return n

    # divisibility fallbacks (tiny smoke caches / odd batches)
    if k_pages.shape[1] % max(_axes_size(page_axes), 1):
        page_axes = None
    if b % max(_axes_size(batch_axes), 1):
        batch_axes = None

    # single-rank fast path: with no page or batch parallelism the
    # shard_map wrapper, rank masking and cross-rank combine are pure
    # overhead — write the new KV with one contiguous per-row
    # dynamic_update_slice and run the flash-decode directly (identical
    # math; the serving decode tick is latency-critical)
    if not force_shard_map and _axes_size(page_axes) <= 1 \
            and _axes_size(batch_axes) <= 1:
        hkv_ = k_pages.shape[3]
        smax = k_pages.shape[1] * k_pages.shape[2]
        pb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
        kd = (kvq.dequantize_pages(k_pages, k_scale) if quantized
              else k_pages)
        vd = (kvq.dequantize_pages(v_pages, v_scale) if quantized
              else v_pages)
        kf = kd.reshape(b, smax, hkv_, d)
        vf = vd.reshape(b, smax, hkv_, d)

        def write(buf, new, p):
            return jax.lax.dynamic_update_slice(
                buf, new.astype(buf.dtype), (p, 0, 0))

        kf = jax.vmap(write)(kf, new_k, pb)
        vf = jax.vmap(write)(vf, new_v, pb)
        acc, m, l = _flash_decode_partial(q, kf, vf, pb + 1, kv_block,
                                          logit_softcap)
        out = (acc / jnp.maximum(l[..., None], 1e-30)).reshape(
            b, 1, hkv_ * group, d).astype(q.dtype)
        if quantized:
            kq, ks = kvq.requantize_pages(kf.reshape(kd.shape), k_scale)
            vq, vs = kvq.requantize_pages(vf.reshape(vd.shape), v_scale)
            return out, kq, vq, ks, vs
        return (out, kf.reshape(k_pages.shape), vf.reshape(v_pages.shape))

    q_spec = P(batch_axes, None, None, None)
    kv_spec = P(batch_axes, page_axes, None, None, None)
    scale_spec = P(batch_axes, page_axes, None)       # [B, P, Hkv]
    pos_spec = P(batch_axes)                          # per-slot positions

    def local(qb, kp, vp, nk, nv, p_, ks_, vs_):
        bl, pl, page, _, _ = kp.shape
        L = pl * page
        if page_axes:
            rank = jax.lax.axis_index(page_axes)
        else:
            rank = jnp.zeros((), jnp.int32)
        start = rank.astype(jnp.int32) * L
        # per-slot positions (continuous batching): p_ is [B] (or scalar)
        pb = jnp.broadcast_to(jnp.asarray(p_, jnp.int32), (bl,))
        off = pb - start                              # [B]
        in_range = (off >= 0) & (off < L)
        offc = jnp.clip(off, 0, L - 1)
        # quantized cache: dequantize the local pages before the write +
        # flash-decode; scales are sharded exactly like the pages so each
        # rank sees the scales of its own page shard
        kdl = kvq.dequantize_pages(kp, ks_) if quantized else kp
        vdl = kvq.dequantize_pages(vp, vs_) if quantized else vp
        kf = kdl.reshape(bl, L, hkv, d)
        vf = vdl.reshape(bl, L, hkv, d)
        # owner-only write at each slot's own offset (scatter: in-place)
        rows = jnp.arange(bl)
        old_k = kf[rows, offc]                        # [B, Hkv, D]
        old_v = vf[rows, offc]
        sel = in_range[:, None, None]
        kf = kf.at[rows, offc].set(
            jnp.where(sel, nk[:, 0].astype(kf.dtype), old_k))
        vf = vf.at[rows, offc].set(
            jnp.where(sel, nv[:, 0].astype(vf.dtype), old_v))
        valid = jnp.clip(pb + 1 - start, 0, L)        # [B] visible tokens
        acc, m, l = _flash_decode_partial(qb, kf, vf, valid, kv_block,
                                          logit_softcap)
        if page_axes:
            m_g = jax.lax.pmax(m, page_axes)
            scale = jnp.exp(m - m_g)
            l_g = jax.lax.psum(l * scale, page_axes)
            acc_g = jax.lax.psum(acc * scale[..., None], page_axes)
        else:
            l_g, acc_g = l, acc
        out = acc_g / jnp.maximum(l_g[..., None], 1e-30)
        out = out.reshape(bl, 1, hkv * group, d).astype(qb.dtype)
        if quantized:
            kq, ks2 = kvq.requantize_pages(kf.reshape(kdl.shape), ks_)
            vq, vs2 = kvq.requantize_pages(vf.reshape(vdl.shape), vs_)
            return out, kq, vq, ks2, vs2
        return out, kf.reshape(kp.shape), vf.reshape(vp.shape)

    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    if quantized:
        return jax.shard_map(
            local,
            in_specs=(q_spec, kv_spec, kv_spec, q_spec, q_spec, pos_spec,
                      scale_spec, scale_spec),
            out_specs=(q_spec, kv_spec, kv_spec, scale_spec, scale_spec))(
                q, k_pages, v_pages, new_k, new_v, pos, k_scale, v_scale)
    return jax.shard_map(
        lambda qb, kp, vp, nk, nv, p_: local(qb, kp, vp, nk, nv, p_, None,
                                             None),
        in_specs=(q_spec, kv_spec, kv_spec, q_spec, q_spec, pos_spec),
        out_specs=(q_spec, kv_spec, kv_spec))(
            q, k_pages, v_pages, new_k, new_v, pos)


def naive_attention(q, k, v, causal=True, logit_softcap: float = 0.0):
    """Reference O(S^2) attention (oracle for tests)."""
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    group = h // hkv
    qh = q.reshape(b, sq, hkv, group, d)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qh, k,
                   preferred_element_type=jnp.float32) / (d ** 0.5)
    if logit_softcap > 0.0:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
    if causal:
        mask = jnp.tril(jnp.ones((sq, k.shape[1]), bool))
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return out.reshape(b, sq, h, d).astype(q.dtype)
