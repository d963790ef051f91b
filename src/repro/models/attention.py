"""Attention: chunked (flash-style) causal/full attention + decode step.

``chunked_attention`` is the framework's default sequence-mixing path: an
online-softmax scan over KV blocks that never materializes the [S, S] score
matrix — algorithmically identical to the Pallas flash kernel in
``repro.kernels.flash_attention`` (which is the TPU fast path; this jnp
version is also its oracle shape). Memory per step is O(S·block) instead of
O(S^2), which is what lets the 32k-prefill dry-run cells fit.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import (dense_init, head_rmsnorm, apply_rope,
                                 inner_unroll, pdtype)

NEG_INF = -1e30
KV_BLOCK = 2048         # positions per block of the cache scan
MXU_ROWS = 128          # query rows one pass of the TPU's matrix unit takes


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


def _block_size(smax: int, page: int, kv_block: int) -> int:
    """Sequence block of the cache scan: the largest multiple of ``page``
    that divides ``smax`` and is at most ``kv_block`` (one page at least),
    so a block holds whole pages and their int8 scales."""
    n_pages = smax // page
    per = max(1, min(kv_block // page, n_pages))
    while n_pages % per:
        per -= 1
    return per * page


def _cache_partials(q, k, v, layer, row0, kv_len, *, page: int,
                    logit_softcap: float = 0.0, k_scale=None, v_scale=None):
    """Online-softmax partials of ``q`` over one layer of a stacked cache,
    read in place in the layout it is stored in.

    q: [B, C, H, D]; k/v: [L, Bt, S, Hkv, D], of which rows ``row0`` to
    ``row0 + B`` of layer ``layer`` are read, a block of whole pages
    and at most ``KV_BLOCK`` positions at a time (dynamic slices along
    the sequence axis); row b's queries see its positions
    ``< kv_len[b]``. int8 caches pass their scales
    [L, Bt, S // page, Hkv] and are dequantized per block.

    A block arrives as [blk, Hkv, D], KV heads inside each position, and
    a contraction batched over KV heads would have XLA lay the whole
    cache out again. So the form follows the query rows (C x H):

    * rows that fit one MXU pass (decode): the block is read as a
      [blk, Hkv * D] matrix against a block-diagonal query (query head h
      holds zeros outside its KV head's D columns). The block leaves HBM
      in its stored layout; on a TPU the reshape is a relayout of each
      block in on-chip memory, and the zero products ride the pass that
      streams the block anyway.
    * more rows (a prefill chunk): the block is turned heads-major by a
      contraction with the identity, which the MXU does in one pass per
      block, and the chunk's queries meet their own KV head only.

    Returns acc [B, C, Hkv, G, D] and m, l [B, C, Hkv, G], combinable
    across shards.
    """
    b, c, h, d = q.shape
    smax, hkv = k.shape[2], k.shape[3]
    group = h // hkv
    blk = _block_size(smax, page, KV_BLOCK)
    scale = 1.0 / (d ** 0.5)
    diagonal = c * h <= MXU_ROWS
    eye = jnp.arange(hkv)[:, None] == jnp.arange(hkv)[None, :]
    if diagonal:
        qh = q.reshape(b, c, hkv, group, 1, d)
        qs = jnp.where(eye[:, None, :, None], qh, jnp.zeros((), q.dtype))
        qs = qs.reshape(b, c * h, hkv * d)            # [B, C*H, Hkv*D]
    else:
        qs = q.reshape(b, c, hkv, group, d)
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (b,))

    def read(cache, scales, j):
        x = jax.lax.dynamic_slice(cache, (layer, row0, j * blk, 0, 0),
                                  (1, b, blk, hkv, d))
        if scales is not None:
            sc = jax.lax.dynamic_slice(scales, (layer, row0, j * blk // page,
                                                0), (1, b, blk // page, hkv))
            x = (x.reshape(b, blk // page, page, hkv, d).astype(jnp.float32)
                 * sc.reshape(b, blk // page, 1, hkv, 1))
        x = x.reshape(b, blk, hkv, d)
        if diagonal:
            return x.reshape(b, blk, hkv * d)
        return jnp.einsum("bkjd,jh->bhkd", x, eye.astype(x.dtype),
                          precision=jax.lax.Precision.HIGHEST)

    def kv_step(carry, j):
        acc, m_prev, l_prev = carry
        kj = read(k, k_scale, j)
        vj = read(v, v_scale, j).astype(jnp.float32)
        if diagonal:
            s = jnp.einsum("bqn,bkn->bqk", qs, kj,
                           preferred_element_type=jnp.float32)
        else:
            s = jnp.einsum("bchgd,bhkd->bchgk", qs, kj,
                           preferred_element_type=jnp.float32)
        s = s * scale
        if logit_softcap > 0.0:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        pos = j * blk + jnp.arange(blk)
        mask = (pos[None, :] < kv_len[:, None]).reshape(
            (b,) + (1,) * (s.ndim - 2) + (blk,))
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + p.sum(axis=-1)
        if diagonal:
            pv = jnp.einsum("bqk,bkn->bqn", p, vj,
                            preferred_element_type=jnp.float32)
        else:
            pv = jnp.einsum("bchgk,bhkd->bchgd", p, vj,
                            preferred_element_type=jnp.float32)
        return (acc * alpha[..., None] + pv, m_new, l_new), None

    # initial carry derived from q AND the cache so its varying-manual-axes
    # cover every axis the scan body produces inside a shard_map region
    # (paged decode: q varies over batch axes, the cache over page axes)
    zk = (k.reshape(-1)[0] * 0).astype(jnp.float32)
    q0 = qs[..., 0].astype(jnp.float32) * 0.0 + zk
    width = hkv * d if diagonal else d
    carry = (q0[..., None] + jnp.zeros((width,), jnp.float32),
             q0 + NEG_INF, q0)
    (acc, m, l), _ = jax.lax.scan(kv_step, carry,
                                  jnp.arange(smax // blk, dtype=jnp.int32),
                                  unroll=inner_unroll())
    if diagonal:
        acc = acc.reshape(b, c, hkv, group, hkv, d)
        acc = jnp.where(eye[:, None, :, None], acc, 0.0).sum(axis=4)
    return (acc, m.reshape(b, c, hkv, group), l.reshape(b, c, hkv, group))


def _fold_new(q, new_k, new_v, acc, m, l, logit_softcap: float = 0.0):
    """Fold the C new tokens into cache partials and normalize.

    q: [B, C, H, D]; new_k/new_v: [B, C, Hkv, D] in the dtype the cache
    is read in; query i sees new tokens j <= i. Returns [B, C, H, D].
    """
    b, c, h, d = q.shape
    hkv = new_k.shape[2]
    qh = q.reshape(b, c, hkv, h // hkv, d)
    s = jnp.einsum("bchgd,bjhd->bchgj", qh, new_k,
                   preferred_element_type=jnp.float32) * (1.0 / (d ** 0.5))
    if logit_softcap > 0.0:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
    causal = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
    s = jnp.where(causal[None, :, None, None, :], s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + p.sum(axis=-1)
    acc = acc * alpha[..., None] + jnp.einsum(
        "bchgj,bjhd->bchgd", p, new_v.astype(jnp.float32),
        preferred_element_type=jnp.float32)
    out = acc / jnp.maximum(l_new[..., None], 1e-30)
    return out.reshape(b, c, h, d).astype(q.dtype)


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, kv_len: jnp.ndarray,
                     logit_softcap: float = 0.0) -> jnp.ndarray:
    """Single-token flash-decode. q: [B,1,H,D]; caches [B,Smax,Hkv,D]."""
    b, _, h, d = q.shape
    smax = k_cache.shape[1]
    page = KV_BLOCK if smax % KV_BLOCK == 0 else smax
    acc, m, l = _cache_partials(q, k_cache[None], v_cache[None], 0, 0,
                                kv_len, page=page,
                                logit_softcap=logit_softcap)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, 1, h, d).astype(q.dtype)


def kv_read_dtype(kv: Dict):
    """The dtype attention reads a paged cache in: its own, or float32
    for an int8 cache (dequantized)."""
    return jnp.float32 if "k_scale" in kv else kv["k"].dtype


def cached_attention(q: jnp.ndarray, kv: Dict, new_k: jnp.ndarray,
                     new_v: jnp.ndarray, pos: jnp.ndarray, *, layer,
                     row0=0, batch_axes=None, page_axes=None,
                     logit_softcap: float = 0.0,
                     force_shard_map: bool = False) -> jnp.ndarray:
    """Attention of C new tokens per row against a stacked paged cache.

    q: [B, C, H, D]; new_k/new_v: [B, C, Hkv, D], cast to
    :func:`kv_read_dtype`; ``kv``: {"k","v"} each [L, Bt, P, page, Hkv,
    D] (int8 caches add "k_scale"/"v_scale" [L, Bt, P, Hkv]). Row b is
    cache row ``row0 + b`` of layer ``layer``, and its queries sit at
    positions ``pos[b] + i``: query i sees the stored positions below
    ``pos[b]``, read in place, and new tokens 0..i, taken from
    ``new_k``/``new_v``. Nothing is written: :func:`write_kv` stores the
    new tokens. Returns [B, C, H, D].

    With page or batch parallelism (or ``force_shard_map``) the layer's
    rows are sliced out and each rank scans its own pages inside a
    shard_map; the online-softmax partials combine with one pmax/psum
    pair over ``page_axes`` (the cross-root-port read combine) before
    the new tokens are folded in.
    """
    from jax.sharding import PartitionSpec as P

    quantized = "k_scale" in kv
    b = q.shape[0]
    _, _, n_pages, page, hkv, d = kv["k"].shape
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))

    def flat(a):
        return a.reshape(a.shape[0], a.shape[1], -1, hkv, d)

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
    if n_pages % max(_axes_size(page_axes), 1):
        page_axes = None
    if b % max(_axes_size(batch_axes), 1):
        batch_axes = None

    # single rank: read the stacked cache in place
    if not force_shard_map and _axes_size(page_axes) <= 1 \
            and _axes_size(batch_axes) <= 1:
        acc, m, l = _cache_partials(
            q, flat(kv["k"]), flat(kv["v"]), layer, row0, pos,
            page=page, logit_softcap=logit_softcap,
            k_scale=kv.get("k_scale"), v_scale=kv.get("v_scale"))
        return _fold_new(q, new_k, new_v, acc, m, l, logit_softcap)

    def rows_of_layer(a):
        start = (layer, row0) + (0,) * (a.ndim - 2)
        return jax.lax.dynamic_slice(a, start, (1, b) + a.shape[2:])

    kvl = {name: rows_of_layer(a) for name, a in kv.items()}
    q_spec = P(batch_axes, None, None, None)
    kv_spec = P(None, batch_axes, page_axes, None, None, None)
    scale_spec = P(None, batch_axes, page_axes, None)   # [1, B, P, Hkv]

    def local(qb, kp, vp, nk, nv, p_, ks_, vs_):
        pl = kp.shape[2]
        n_local = pl * page
        if page_axes:
            rank = jax.lax.axis_index(page_axes)
        else:
            rank = jnp.zeros((), jnp.int32)
        start = rank.astype(jnp.int32) * n_local
        valid = jnp.clip(p_ - start, 0, n_local)      # [B] stored tokens
        acc, m, l = _cache_partials(qb, flat(kp), flat(vp), 0, 0, valid,
                                    page=page, logit_softcap=logit_softcap,
                                    k_scale=ks_, v_scale=vs_)
        if page_axes:
            m_g = jax.lax.pmax(m, page_axes)
            sc = jnp.exp(m - m_g)
            l = jax.lax.psum(l * sc, page_axes)
            acc = jax.lax.psum(acc * sc[..., None], page_axes)
            m = m_g
        return _fold_new(qb, nk, nv, acc, m, l, logit_softcap)

    pos_spec = P(batch_axes)
    if quantized:
        return jax.shard_map(
            local,
            in_specs=(q_spec, kv_spec, kv_spec, q_spec, q_spec, pos_spec,
                      scale_spec, scale_spec),
            out_specs=q_spec)(q, kvl["k"], kvl["v"], new_k, new_v, pos,
                              kvl["k_scale"], kvl["v_scale"])
    return jax.shard_map(
        lambda qb, kp, vp, nk, nv, p_: local(qb, kp, vp, nk, nv, p_, None,
                                             None),
        in_specs=(q_spec, kv_spec, kv_spec, q_spec, q_spec, pos_spec),
        out_specs=q_spec)(q, kvl["k"], kvl["v"], new_k, new_v, pos)


def write_kv(kv: Dict, new_k: jnp.ndarray, new_v: jnp.ndarray,
             pos: jnp.ndarray, row0=0) -> Dict:
    """Store every layer's new tokens into the stacked paged cache.

    new_k/new_v: [L, B, C, Hkv, D]; token i of row b lands at position
    ``pos[b] + i`` of cache row ``row0 + b``. One scatter per leaf, in
    place when the cache buffer is donated. An int8 cache dequantizes
    the rows' pages, writes, and requantizes them with monotone scale
    growth, so untouched pages keep their codes (models/kv_quant.py).
    """
    from repro.models import kv_quant as kvq

    b, c = new_k.shape[1], new_k.shape[2]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    posn = pos[:, None] + jnp.arange(c, dtype=jnp.int32)[None]   # [B, C]
    quantized = "k_scale" in kv

    def put(pages, new, rows):
        # every index explicit, so each update is one position's [Hkv, D]:
        # the minor dims of the stored layout
        shape = pages.shape
        f = pages.reshape(shape[0], shape[1], -1, shape[4], shape[5])
        layers = jnp.arange(shape[0])[:, None, None]
        f = f.at[layers, rows[None], posn[None]].set(new.astype(f.dtype))
        return f.reshape(shape)

    if not quantized:
        rows = row0 + jnp.broadcast_to(jnp.arange(b)[:, None], (b, c))
        return dict(kv, k=put(kv["k"], new_k, rows),
                    v=put(kv["v"], new_v, rows))
    rows = jnp.broadcast_to(jnp.arange(b)[:, None], (b, c))
    out = dict(kv)
    for name, new in (("k", new_k), ("v", new_v)):
        sc = name + "_scale"
        pages = jax.lax.dynamic_slice_in_dim(kv[name], row0, b, axis=1)
        scales = jax.lax.dynamic_slice_in_dim(kv[sc], row0, b, axis=1)
        full = put(kvq.dequantize_pages(pages, scales), new, rows)
        codes, grown = kvq.requantize_pages(full, scales)
        out[name] = jax.lax.dynamic_update_slice_in_dim(kv[name], codes,
                                                        row0, axis=1)
        out[sc] = jax.lax.dynamic_update_slice_in_dim(kv[sc], grown, row0,
                                                      axis=1)
    return out


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
