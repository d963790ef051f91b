"""Model assembly: init / train forward / prefill / decode for all families.

Layer stacks carry a leading layer (or group) axis so the SR pipeline
(repro.core.speculative_read.stream_layers) can stream them from the pool
tier. KV caches are *paged*: [B, n_pages, page, Hkv, D], which (a) keeps
decode attention a block-parallel flash-decode with a cheap cross-page
combine, and (b) is the same layout the serving engine's tiered pager uses.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, RunConfig
from repro.core import speculative_read as sr
from repro.models import attention as attn_lib
from repro.models import kv_quant as kv_quant_lib
from repro.models import mamba2, moe, transformer, xlstm
from repro.models.layers import (embed_apply, embed_init, mlp_apply, pdtype,
                                 rmsnorm, rmsnorm_init, sinusoidal_positions,
                                 softmax_xent, unembed_apply)


def _stack_init(fn, key, n, *args):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: fn(k, *args))(keys)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_model(key, cfg: ModelConfig) -> Dict:
    k_embed, k_blocks, k_extra = jax.random.split(key, 3)
    params: Dict[str, Any] = {"embed": embed_init(k_embed, cfg),
                              "ln_f": rmsnorm_init(cfg.d_model, pdtype(cfg))}
    fam = cfg.family
    if fam in ("dense", "audio"):
        params["blocks"] = _stack_init(transformer.block_init, k_blocks,
                                       cfg.n_layers, cfg)
    elif fam == "moe":
        params["blocks"] = _stack_init(moe.moe_block_init, k_blocks,
                                       cfg.n_layers, cfg)
    elif fam == "vlm":
        period = cfg.cross_attn_period
        n_groups = cfg.n_layers // period
        ks = jax.random.split(k_blocks, 2)
        params["groups"] = {
            "self_blocks": jax.vmap(lambda k: _stack_init(
                transformer.block_init, k, period - 1, cfg))(
                    jax.random.split(ks[0], n_groups)),
            "cross": _stack_init(transformer.cross_block_init, ks[1],
                                 n_groups, cfg)}
    elif fam == "hybrid":
        period = cfg.shared_block_period
        n_groups = cfg.n_layers // period
        params["groups"] = jax.vmap(lambda k: _stack_init(
            mamba2.mamba_init, k, period, cfg))(
                jax.random.split(k_blocks, n_groups))
        ks = jax.random.split(k_extra, 3)
        params["shared"] = {
            "in_map": (jax.random.normal(ks[0],
                                         (2 * cfg.d_model, cfg.d_model))
                       * 0.02).astype(pdtype(cfg)),
            "block": transformer.block_init(ks[1], cfg),
            "out_map": (jax.random.normal(ks[2], (cfg.d_model, cfg.d_model))
                        * 0.02).astype(pdtype(cfg))}
    elif fam == "ssm":
        period = cfg.slstm_every
        n_groups = cfg.n_layers // period
        ks = jax.random.split(k_blocks, 2)
        params["groups"] = {
            "mlstm": jax.vmap(lambda k: _stack_init(
                xlstm.mlstm_init, k, period - 1, cfg))(
                    jax.random.split(ks[0], n_groups)),
            "slstm": _stack_init(xlstm.slstm_init, ks[1], n_groups, cfg)}
    else:
        raise ValueError(fam)
    return params


def stacked_key(cfg: ModelConfig) -> str:
    return "blocks" if cfg.family in ("dense", "moe", "audio") else "groups"


def n_stacked(cfg: ModelConfig) -> int:
    if cfg.family in ("dense", "moe", "audio"):
        return cfg.n_layers
    if cfg.family == "vlm":
        return cfg.n_layers // cfg.cross_attn_period
    if cfg.family == "hybrid":
        return cfg.n_layers // cfg.shared_block_period
    return cfg.n_layers // cfg.slstm_every


# ---------------------------------------------------------------------------
# forward bodies (one stacked step each)
# ---------------------------------------------------------------------------


def _act_spec(rc: RunConfig, seq_sharded: bool) -> P:
    dp = ("pod", "data") if rc.mesh.multi_pod else "data"
    return P(dp, "model" if seq_sharded else None, None)


def _constrain_act(x, rc: RunConfig, seq_sharded: bool):
    return jax.lax.with_sharding_constraint(x, _act_spec(rc, seq_sharded))


def _body_train(cfg: ModelConfig, rc: RunConfig, positions, seq_sharded,
                shared=None, vision=None):
    """Returns body(x_carry, layer_params, extra) -> (x_carry, out)."""
    fam = cfg.family

    def body(carry, layer, extra):
        del extra
        x, aux = carry if isinstance(carry, tuple) else (carry, 0.0)
        x = _constrain_act(x, rc, seq_sharded)
        if fam in ("dense", "audio"):
            x = transformer.block_apply(layer, cfg, x, positions,
                                        fuse_qkv=rc.fuse_qkv,
                                        use_pallas=rc.use_pallas)
            return (x, aux), None
        if fam == "moe":
            x, a = moe.moe_block_apply(layer, cfg, x, positions,
                                       fuse_qkv=rc.fuse_qkv)
            return (x, aux + a), None
        if fam == "vlm":
            for i in range(cfg.cross_attn_period - 1):
                blk = jax.tree_util.tree_map(lambda a: a[i],
                                             layer["self_blocks"])
                x = transformer.block_apply(blk, cfg, x, positions,
                                            fuse_qkv=rc.fuse_qkv)
            kv = transformer.vision_kv(layer["cross"], cfg, vision)
            x = transformer.cross_block_apply(layer["cross"], cfg, x, kv)
            return (x, aux), None
        if fam == "hybrid":
            emb = shared["emb"]
            for i in range(cfg.shared_block_period):
                blk = jax.tree_util.tree_map(lambda a: a[i], layer)
                x = x + mamba2.mamba_apply(blk, cfg, x)
            x = _shared_block_apply(shared["params"], cfg, x, emb, positions,
                                    rc)
            return (x, aux), None
        if fam == "ssm":
            for i in range(cfg.slstm_every - 1):
                blk = jax.tree_util.tree_map(lambda a: a[i], layer["mlstm"])
                x = xlstm.mlstm_apply(blk, cfg, x)
            x = xlstm.slstm_apply(layer["slstm"], cfg, x)
            return (x, aux), None
        raise ValueError(fam)

    return body


def _shared_block_apply(sp, cfg, x, emb, positions, rc):
    """zamba2 shared attention block: concat(h, emb) -> attn+mlp -> project."""
    z = jnp.concatenate([x, emb], axis=-1) @ sp["in_map"]
    z = transformer.block_apply(sp["block"], cfg, z, positions,
                                fuse_qkv=rc.fuse_qkv)
    return x + z @ sp["out_map"]


# ---------------------------------------------------------------------------
# train forward + loss
# ---------------------------------------------------------------------------


def loss_fn(params: Dict, cfg: ModelConfig, rc: RunConfig, batch: Dict,
            param_specs: Dict, *, mode: str = "train") -> jnp.ndarray:
    tokens = batch["tokens"]
    seq_sharded = mode == "train" or rc.seq_shard_attn
    x = embed_apply(params["embed"], cfg, tokens)
    bsz, seq = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32)[None],
                                 (bsz, seq))
    if cfg.family == "audio" or not cfg.use_rope:
        x = x + sinusoidal_positions(positions, cfg.d_model).astype(x.dtype)
    x = _constrain_act(x, rc, seq_sharded)

    shared = None
    vision = batch.get("vision_embeds")
    if cfg.family == "hybrid":
        shared = {"params": params["shared"], "emb": x}
    body = _body_train(cfg, rc, positions, seq_sharded, shared=shared,
                       vision=vision)

    key = stacked_key(cfg)
    (x, aux), _ = sr.stream_layers(
        body, (x, jnp.zeros((), jnp.float32)), params[key],
        param_specs[key], n_layers=n_stacked(cfg),
        prefetch_depth=rc.sr_prefetch_depth, granularity=rc.sr_granularity,
        mode="train", remat=rc.remat, unroll=rc.scan_unroll,
        remat_policy=rc.remat_policy)

    x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
    loss = _chunked_xent(params, cfg, x, batch["labels"])
    return loss + aux


def _chunked_xent(params, cfg, x, labels, n_chunks: int = 8):
    """Cross-entropy without materializing full [T, V] logits."""
    b, s, d = x.shape
    if s % n_chunks or s // n_chunks == 0:
        n_chunks = 1
    cs = s // n_chunks
    xs = jnp.moveaxis(x.reshape(b, n_chunks, cs, d), 1, 0)
    if cfg.family == "audio":
        lab = jnp.moveaxis(labels.reshape(b, labels.shape[1], n_chunks, cs),
                           2, 0)
    else:
        lab = jnp.moveaxis(labels.reshape(b, n_chunks, cs), 1, 0)

    def chunk(carry, inp):
        xc, lc = inp
        logits = unembed_apply(params["embed"], cfg, xc)
        return carry + softmax_xent(logits, lc), None

    total, _ = jax.lax.scan(chunk, jnp.zeros((), jnp.float32), (xs, lab),
                            unroll=n_chunks)
    return total / n_chunks


# ---------------------------------------------------------------------------
# KV caches (paged layout)
# ---------------------------------------------------------------------------


def cache_init(cfg: ModelConfig, rc: RunConfig, batch: int, max_seq: int,
               as_shape: bool = False) -> Dict:
    """Paged cache pytree. as_shape=True -> ShapeDtypeStructs (dry-run).

    With ``rc.kv_quant == "int8"`` the paged self-attention K/V leaves are
    int8 and each gains a sibling fp32 per-(page, head) scale leaf
    ("k_scale"/"v_scale", [n, B, n_pages, Hkv] — see models/kv_quant.py).
    The vlm cross-attention K/V (written once at prefill, never behind the
    tier hot path) stays at the model dtype.
    """
    page = min(rc.kv_page_size, max_seq)
    n_pages = max(max_seq // page, 1)
    dt = pdtype(cfg)
    quant = rc.kv_quant == "int8"
    if rc.kv_quant != "none":
        kv_quant_lib.validate_mode(rc.kv_quant)

    def arr(shape, dtype, fill=None):
        if as_shape:
            return jax.ShapeDtypeStruct(shape, dtype)
        if fill is not None:
            return jnp.full(shape, fill, dtype)
        return jnp.zeros(shape, dtype)

    def kv(n):
        kv_dt = jnp.int8 if quant else dt
        pages = {"k": arr((n, batch, n_pages, page, cfg.n_kv_heads,
                           cfg.head_dim), kv_dt),
                 "v": arr((n, batch, n_pages, page, cfg.n_kv_heads,
                           cfg.head_dim), kv_dt)}
        if quant:
            sshape = (n, batch, n_pages, cfg.n_kv_heads)
            pages["k_scale"] = arr(sshape, jnp.float32,
                                   fill=kv_quant_lib.INIT_SCALE)
            pages["v_scale"] = arr(sshape, jnp.float32,
                                   fill=kv_quant_lib.INIT_SCALE)
        return pages

    fam = cfg.family
    if fam in ("dense", "moe", "audio"):
        return {"kv": kv(cfg.n_layers), "pos": arr((batch,), jnp.int32)}
    if fam == "vlm":
        g = n_stacked(cfg)
        nv = cfg.n_vision_tokens
        return {"kv": kv(g * (cfg.cross_attn_period - 1)),
                "cross_k": arr((g, batch, nv, cfg.n_kv_heads, cfg.head_dim),
                               dt),
                "cross_v": arr((g, batch, nv, cfg.n_kv_heads, cfg.head_dim),
                               dt),
                "pos": arr((batch,), jnp.int32)}
    if fam == "hybrid":
        g = n_stacked(cfg)
        d_in = cfg.ssm_expand * cfg.d_model
        nh = d_in // cfg.ssm_head_dim
        return {
            "kv": kv(g),  # one shared-block invocation cache per group
            "h": arr((g, cfg.shared_block_period, batch, nh,
                      cfg.ssm_head_dim, cfg.ssm_state), jnp.float32),
            "conv": arr((g, cfg.shared_block_period, batch, cfg.ssm_conv - 1,
                         d_in + 2 * cfg.ssm_state), jnp.float32),
            "pos": arr((batch,), jnp.int32)}
    if fam == "ssm":
        g = n_stacked(cfg)
        m = cfg.slstm_every - 1
        d_in = cfg.mlstm_expand * cfg.d_model
        nh = cfg.n_heads
        dh_m = d_in // nh
        dh_s = cfg.d_model // nh
        return {
            "mC": arr((g, m, batch, nh, dh_m, dh_m), jnp.float32),
            "mn": arr((g, m, batch, nh, dh_m), jnp.float32),
            "mm": arr((g, m, batch, nh), jnp.float32),
            "mconv": arr((g, m, batch, 3, d_in), jnp.float32),
            "sh": arr((g, batch, nh, dh_s), jnp.float32),
            "sc": arr((g, batch, nh, dh_s), jnp.float32),
            "sn": arr((g, batch, nh, dh_s), jnp.float32),
            "sm": arr((g, batch, nh, dh_s), jnp.float32),
            "sconv": arr((g, batch, 3, cfg.d_model), jnp.float32),
            "pos": arr((batch,), jnp.int32)}
    raise ValueError(fam)


def decode_axes(rc: RunConfig, batch: int):
    """(batch_axes, page_axes) for the page-sharded decode cache.

    batch > 1: batch over the DP axes, pages over "model" — each model
    rank plays one root port/EP owning a contiguous token range.
    batch == 1: no batch parallelism; pages spread over the whole mesh.
    """
    dp = ("pod", "data") if rc.mesh.multi_pod else "data"
    if batch == 1:
        page_axes = (("pod", "data", "model") if rc.mesh.multi_pod
                     else ("data", "model"))
        return None, page_axes
    return dp, "model"


def cache_specs(cfg: ModelConfig, rc: RunConfig, batch: int) -> Dict:
    """PartitionSpecs for the cache pytree (leading stack axis included)."""
    dp = ("pod", "data") if rc.mesh.multi_pod else "data"
    batch_axes, page_axes = decode_axes(rc, batch)
    kv_spec = P(None, batch_axes, page_axes, None, None, None)

    cache = cache_init(cfg, rc, batch, max_seq=rc.kv_page_size,
                       as_shape=True)

    def spec_for(path, leaf):
        name = str(path[-1].key) if hasattr(path[-1], "key") else ""
        if name in ("k", "v"):
            return kv_spec
        if name in ("k_scale", "v_scale"):
            # per-(page, head) int8 scales shard exactly like the pages
            return P(None, batch_axes, page_axes, None)
        if name in ("cross_k", "cross_v"):
            return P(None, batch_axes, None, None, None)
        if name == "pos":
            return P(batch_axes)
        # SSM / conv states: batch-sharded when batch parallelism exists
        shape = leaf.shape
        out = [None] * len(shape)
        if batch_axes is not None:
            # find the batch axis (first axis whose size == batch)
            for i, s in enumerate(shape):
                if s == batch:
                    out[i] = batch_axes
                    break
        return P(*out)

    return jax.tree_util.tree_map_with_path(spec_for, cache)


def cache_batch_axes(cfg: ModelConfig, rc: RunConfig) -> Dict:
    """Each cache leaf's batch axis (found by differencing two batch
    sizes)."""
    a = cache_init(cfg, rc, 2, max_seq=rc.kv_page_size, as_shape=True)
    b = cache_init(cfg, rc, 3, max_seq=rc.kv_page_size, as_shape=True)
    return jax.tree_util.tree_map(
        lambda x, y: next(i for i, (p, q) in enumerate(zip(x.shape, y.shape))
                          if p != q), a, b)


# ---------------------------------------------------------------------------
# decode (serve_step body)
# ---------------------------------------------------------------------------


def _paged_block_decode(block_fn, layer, cfg, x, pos, kv, l, rc, row0=0):
    """One paged-attention block against layer ``l`` of the stacked cache
    ``kv``, read in place; the cache stays page-sharded (any cross-rank
    combine happens inside attention.cached_attention)."""
    batch_axes, page_axes = decode_axes(rc, x.shape[0])
    return block_fn(layer, cfg, x, pos, kv, l, row0=row0,
                    batch_axes=batch_axes, page_axes=page_axes,
                    fuse_qkv=rc.fuse_qkv)


def _block_decode_layer(block_fn, layer, cfg, x, pos, kv, rc):
    """One block against a single layer's cache ``kv``, written in the
    same call: the recurrent families' per-layer cache route."""
    stacked = {name: a[None] for name, a in kv.items()}
    x, (k, v) = _paged_block_decode(block_fn, layer, cfg, x, pos, stacked, 0,
                                    rc)
    out = attn_lib.write_kv(stacked, k[None], v[None], pos)
    return x, {name: a[0] for name, a in out.items()}


def _layers_cached(params, cfg, rc, x, pos, kv, param_specs, row0=0):
    """The attention families' layer scan over C new tokens per row.

    Each layer reads its K/V from the stacked cache ``kv`` in place (rows
    ``row0`` on); the scan carries no cache and emits only the new
    tokens' K/V. Returns (x, ((k, v) each [L, B, C, Hkv, D], the MoE
    layers' routing counts [L, ...] or None)); attention.write_kv stores
    the new K/V in one scatter after the scan.
    """
    block_fn = (moe.moe_block_decode_paged if cfg.family == "moe"
                else transformer.block_decode_paged)

    def body(x, layer, l):
        out = _paged_block_decode(block_fn, layer, cfg, x, pos, kv, l, rc,
                                  row0)
        return out[0], (out[1], out[2] if len(out) > 2 else None)

    key = stacked_key(cfg)
    return sr.stream_layers(
        body, x, params[key], param_specs[key], n_layers=cfg.n_layers,
        prefetch_depth=rc.sr_prefetch_depth, granularity=rc.sr_granularity,
        mode="infer", remat=False,
        stacked_extras=jnp.arange(cfg.n_layers, dtype=jnp.int32),
        unroll=rc.scan_unroll)


def decode_step(params: Dict, cfg: ModelConfig, rc: RunConfig, tokens,
                cache: Dict, param_specs: Dict, *, routing: bool = False):
    """One-token decode. tokens: [B, 1] (audio: [B, K, 1]).

    Returns (logits, new cache), and with ``routing`` a third value: the
    MoE layers' routing counts summed over layers (``moe.moe_apply_held``),
    None for families without experts.
    """
    pos = cache["pos"]                     # [B] per-slot positions
    x = embed_apply(params["embed"], cfg, tokens)
    b = x.shape[0]
    if cfg.family == "audio" or not cfg.use_rope:
        ppos = pos.reshape(b, 1).astype(jnp.int32)
        x = x + sinusoidal_positions(ppos, cfg.d_model).astype(x.dtype)

    fam = cfg.family
    new_cache = dict(cache)
    counts = None

    if fam in ("dense", "moe", "audio"):
        x, (kv_new, counts) = _layers_cached(params, cfg, rc, x, pos,
                                             cache["kv"], param_specs)
        new_cache["kv"] = attn_lib.write_kv(cache["kv"], *kv_new, pos)
    elif fam == "vlm":
        x, new_cache = _decode_vlm(params, cfg, rc, x, pos, cache,
                                   param_specs)
    elif fam == "hybrid":
        x, new_cache = _decode_hybrid(params, cfg, rc, x, pos, cache,
                                      param_specs)
    elif fam == "ssm":
        x, new_cache = _decode_ssm(params, cfg, rc, x, pos, cache,
                                   param_specs)

    x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
    logits = unembed_apply(params["embed"], cfg, x)
    new_cache["pos"] = pos + 1
    if routing:
        return logits, new_cache, _layer_sum(counts)
    return logits, new_cache


def _layer_sum(counts):
    """Per-layer routing counts [L, ...] summed over layers (None stays)."""
    return None if counts is None else counts.sum(axis=0)


def _decode_vlm(params, cfg, rc, x, pos, cache, param_specs):
    g = n_stacked(cfg)
    per = cfg.cross_attn_period - 1
    kv_g = jax.tree_util.tree_map(
        lambda a: a.reshape((g, per) + a.shape[1:]), cache["kv"])

    def body(x, group, extra):
        kv, ck, cv = extra
        kv_new = []
        for i in range(per):
            blk = jax.tree_util.tree_map(lambda a: a[i],
                                         group["self_blocks"])
            kv_i = jax.tree_util.tree_map(lambda a: a[i], kv)
            x2, kv2 = _block_decode_layer(transformer.block_decode_paged, blk,
                                          cfg, x, pos, kv_i, rc)
            x = x2
            kv_new.append(kv2)
        # cross layer: reuse cached vision K/V, single-query attention
        h = rmsnorm(group["cross"]["ln_attn"], x, cfg.norm_eps)
        ppos = jnp.zeros((x.shape[0], 1), jnp.int32)
        q, _, _ = attn_lib.qkv_project(group["cross"]["attn"], cfg, h, ppos,
                                       rope=False)
        o = attn_lib.decode_attention(q, ck, cv, kv_len=ck.shape[1])
        gate = jnp.tanh(group["cross"]["attn_gate"].astype(jnp.float32)
                        ).astype(x.dtype)
        x = x + gate * (o.reshape(x.shape[0], 1, cfg.q_dim)
                        @ group["cross"]["attn"]["wo"])
        h = rmsnorm(group["cross"]["ln_mlp"], x, cfg.norm_eps)
        from repro.models.layers import mlp_apply
        gate = jnp.tanh(group["cross"]["mlp_gate"].astype(jnp.float32)
                        ).astype(x.dtype)
        x = x + gate * mlp_apply(group["cross"]["mlp"], cfg, h)
        kv_stack = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *kv_new)
        return x, kv_stack

    x, kv_out = sr.stream_layers(
        body, x, params["groups"], param_specs["groups"], n_layers=g,
        prefetch_depth=rc.sr_prefetch_depth, granularity=rc.sr_granularity,
        mode="infer", remat=False,
        stacked_extras=(kv_g, cache["cross_k"], cache["cross_v"]),
        unroll=rc.scan_unroll)
    new_cache = dict(cache)
    new_cache["kv"] = jax.tree_util.tree_map(
        lambda a: a.reshape((g * per,) + a.shape[2:]), kv_out)
    return x, new_cache


def _decode_hybrid(params, cfg, rc, x, pos, cache, param_specs):
    g = n_stacked(cfg)
    emb = x

    def body(x, group, extra):
        kv, hs, convs = extra
        h_new, conv_new = [], []
        for i in range(cfg.shared_block_period):
            blk = jax.tree_util.tree_map(lambda a: a[i], group)
            st = {"h": hs[i], "conv": convs[i]}
            y, st2 = mamba2.mamba_step(blk, cfg, x, st)
            x = x + y
            h_new.append(st2["h"])
            conv_new.append(st2["conv"])
        # shared attention block (single-token)
        sp = params["shared"]
        z = jnp.concatenate([x, emb], axis=-1) @ sp["in_map"]
        z, kv2 = _block_decode_layer(transformer.block_decode_paged,
                                     sp["block"], cfg, z, pos, kv, rc)
        x = x + z @ sp["out_map"]
        return x, (kv2, jnp.stack(h_new), jnp.stack(conv_new))

    x, (kv_out, h_out, conv_out) = sr.stream_layers(
        body, x, params["groups"], param_specs["groups"], n_layers=g,
        prefetch_depth=rc.sr_prefetch_depth, granularity=rc.sr_granularity,
        mode="infer", remat=False,
        stacked_extras=(cache["kv"], cache["h"], cache["conv"]),
        unroll=rc.scan_unroll)
    new_cache = dict(cache)
    new_cache.update({"kv": kv_out, "h": h_out, "conv": conv_out})
    return x, new_cache


def _decode_ssm(params, cfg, rc, x, pos, cache, param_specs):
    g = n_stacked(cfg)
    m = cfg.slstm_every - 1

    def body(x, group, extra):
        mC, mn, mm, mconv, sh, sc, sn, sm, sconv = extra
        outC, outn, outm, outconv = [], [], [], []
        for i in range(m):
            blk = jax.tree_util.tree_map(lambda a: a[i], group["mlstm"])
            st = {"C": mC[i], "n": mn[i], "m": mm[i], "conv": mconv[i]}
            x, st2 = xlstm.mlstm_step(blk, cfg, x, st)
            outC.append(st2["C"])
            outn.append(st2["n"])
            outm.append(st2["m"])
            outconv.append(st2["conv"])
        st = {"h": sh, "c": sc, "n": sn, "m": sm, "conv": sconv}
        x, st2 = xlstm.slstm_step(group["slstm"], cfg, x, st)
        return x, (jnp.stack(outC), jnp.stack(outn), jnp.stack(outm),
                   jnp.stack(outconv), st2["h"], st2["c"], st2["n"],
                   st2["m"], st2["conv"])

    x, outs = sr.stream_layers(
        body, x, params["groups"], param_specs["groups"], n_layers=g,
        prefetch_depth=rc.sr_prefetch_depth, granularity=rc.sr_granularity,
        mode="infer", remat=False,
        stacked_extras=(cache["mC"], cache["mn"], cache["mm"],
                        cache["mconv"], cache["sh"], cache["sc"],
                        cache["sn"], cache["sm"], cache["sconv"]),
        unroll=rc.scan_unroll)
    new_cache = dict(cache)
    for name, val in zip(("mC", "mn", "mm", "mconv", "sh", "sc", "sn", "sm",
                          "sconv"), outs):
        new_cache[name] = val
    return x, new_cache


# ---------------------------------------------------------------------------
# prefill (inference context ingestion; returns logits of last position)
# ---------------------------------------------------------------------------


def prefill_step_cached(params: Dict, cfg: ModelConfig, rc: RunConfig,
                        tokens, cache: Dict, param_specs: Dict, *,
                        routing: bool = False, slot=0):
    """Chunked multi-token prefill that writes the paged KV cache in-graph.

    tokens: [B, C] int32 (audio: [B, K, C]). Row b ingests its C tokens
    into cache row ``slot + b`` (``slot`` may be traced; 0 with a
    full-batch chunk), starting at that row's ``cache["pos"]``; returns
    (logits for all C chunk positions, the cache with those rows' pos
    advanced by C), and with ``routing`` the routing counts as
    ``decode_step`` gives them. Attention families (dense/moe/audio) run
    one parallel chunk forward per layer, reading each layer's K/V in
    place, and write the chunk's K/V after the layer scan; recurrent
    families (vlm/hybrid/ssm) fall back to an in-graph ``lax.scan`` over
    ``decode_step`` on the rows' slice -- still a single dispatch per
    chunk.
    """
    fam = cfg.family
    if fam not in ("dense", "moe", "audio"):
        out = _prefill_scan_cached(params, cfg, rc, tokens, cache,
                                   param_specs, slot)
        return out + (None,) if routing else out
    b = tokens.shape[0]
    pos = jax.lax.dynamic_slice_in_dim(cache["pos"], slot, b)
    x = embed_apply(params["embed"], cfg, tokens)
    c = x.shape[1]
    if fam == "audio" or not cfg.use_rope:
        positions = (pos.reshape(b, 1).astype(jnp.int32)
                     + jnp.arange(c, dtype=jnp.int32)[None])
        x = x + sinusoidal_positions(positions, cfg.d_model).astype(x.dtype)

    x, (kv_new, counts) = _layers_cached(params, cfg, rc, x, pos,
                                         cache["kv"], param_specs, row0=slot)
    new_cache = dict(cache)
    new_cache["kv"] = attn_lib.write_kv(cache["kv"], *kv_new, pos, row0=slot)
    new_cache["pos"] = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], pos + c, slot, axis=0)
    x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
    logits = unembed_apply(params["embed"], cfg, x)
    if routing:
        return logits, new_cache, _layer_sum(counts)
    return logits, new_cache


def _prefill_scan_cached(params, cfg, rc, tokens, cache, param_specs, slot):
    """Sequential-family prefill: scan decode_step over the chunk in-graph,
    on the slice of rows ``slot`` on."""
    b = tokens.shape[0]
    axes = cache_batch_axes(cfg, rc)
    rows = jax.tree_util.tree_map(
        lambda a, ax: jax.lax.dynamic_slice_in_dim(a, slot, b, axis=ax),
        cache, axes)

    def step(rows, tok):
        logits, rows = decode_step(params, cfg, rc, tok[:, None], rows,
                                   param_specs)
        return rows, logits

    rows, ls = jax.lax.scan(step, rows, jnp.moveaxis(tokens, -1, 0))
    cache = jax.tree_util.tree_map(
        lambda a, r, ax: jax.lax.dynamic_update_slice_in_dim(
            a, r.astype(a.dtype), slot, axis=ax), cache, rows, axes)
    # ls: [C, B, 1, V] -> [B, C, V]
    logits = jnp.moveaxis(ls[:, :, 0], 0, 1)
    return logits, cache


# ---------------------------------------------------------------------------
# on-device sampling (fused into the serving hot path)
# ---------------------------------------------------------------------------


def last_token_logits(logits: jnp.ndarray) -> jnp.ndarray:
    """Final-position logits row per batch element: [B, V].

    [B, S, V] -> last position; audio [B, K, S, V] -> codebook-0 last
    position (the serving engine feeds one shared token to all codebooks).
    """
    if logits.ndim == 4:
        return logits[:, 0, -1]
    return logits[:, -1]


def sample_tokens(logits_row: jnp.ndarray, key,
                  temperature: float) -> jnp.ndarray:
    """Greedy / temperature sampling on device. logits_row: [B, V] -> [B].

    Deterministic for a given PRNG key — no host RNG anywhere, so results
    cannot vary with the host numpy version. Temperature sampling draws one
    uniform per row and inverts the softmax CDF: exact categorical sampling
    with B PRNG evaluations instead of the B*V gumbel draws
    ``jax.random.categorical`` needs (~4x cheaper per tick at serving-scale
    vocabs on CPU).
    """
    row = logits_row.astype(jnp.float32)
    if temperature and temperature > 0:
        p = jax.nn.softmax(row / temperature, axis=-1)
        cdf = jnp.cumsum(p, axis=-1)
        u = jax.random.uniform(key, (row.shape[0],), dtype=jnp.float32)
        return (cdf < u[:, None] * cdf[:, -1:]).sum(axis=-1).astype(
            jnp.int32)
    return jnp.argmax(row, axis=-1).astype(jnp.int32)


def prefill_step(params: Dict, cfg: ModelConfig, rc: RunConfig, batch: Dict,
                 param_specs: Dict) -> jnp.ndarray:
    """Prefill forward. Returns last-position logits (the cache write path
    is exercised in decode; prefill here validates the long-context forward
    at scale — in serving, repro.serving.engine folds prefill KV into pages).
    """
    tokens = batch["tokens"]
    x = embed_apply(params["embed"], cfg, tokens)
    b, s = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None],
                                 (b, s))
    if cfg.family == "audio" or not cfg.use_rope:
        x = x + sinusoidal_positions(positions, cfg.d_model).astype(x.dtype)
    x = _constrain_act(x, rc, rc.seq_shard_attn)
    shared = ({"params": params["shared"], "emb": x}
              if cfg.family == "hybrid" else None)
    body = _body_train(cfg, rc, positions, rc.seq_shard_attn, shared=shared,
                       vision=batch.get("vision_embeds"))
    key = stacked_key(cfg)
    (x, _), _ = sr.stream_layers(
        body, (x, jnp.zeros((), jnp.float32)), params[key],
        param_specs[key], n_layers=n_stacked(cfg),
        prefetch_depth=rc.sr_prefetch_depth, granularity=rc.sr_granularity,
        mode="infer" if rc.sr_prefetch_depth else "train", remat=False,
        unroll=rc.scan_unroll)
    x = rmsnorm(params["ln_f"], x[:, -1:], cfg.norm_eps)
    return unembed_apply(params["embed"], cfg, x)
