"""Dense transformer blocks (self-attention and cross-attention variants).

Block params are single-layer dicts; the model assembler stacks them with a
leading layer axis for the SR streaming scan.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models.layers import (dense_init, mlp_apply, mlp_init, pdtype,
                                 rmsnorm, rmsnorm_init)


def block_init(key, cfg: ModelConfig) -> Dict:
    ks = jax.random.split(key, 4)
    return {"ln_attn": rmsnorm_init(cfg.d_model, pdtype(cfg)),
            "attn": attn.attn_init(ks[0], cfg),
            "ln_mlp": rmsnorm_init(cfg.d_model, pdtype(cfg)),
            "mlp": mlp_init(ks[1], cfg)}


def cross_block_init(key, cfg: ModelConfig) -> Dict:
    """Cross-attention image layer (llama-3.2-vision style): gated."""
    ks = jax.random.split(key, 4)
    return {"ln_attn": rmsnorm_init(cfg.d_model, pdtype(cfg)),
            "attn": attn.attn_init(ks[0], cfg),
            "attn_gate": jnp.zeros((), dtype=pdtype(cfg)),
            "ln_mlp": rmsnorm_init(cfg.d_model, pdtype(cfg)),
            "mlp": mlp_init(ks[1], cfg),
            "mlp_gate": jnp.zeros((), dtype=pdtype(cfg))}


def block_apply(params: Dict, cfg: ModelConfig, x: jnp.ndarray,
                positions: jnp.ndarray, *, causal: bool = True,
                fuse_qkv: bool = True, q_block: int = 512,
                kv_block: int = 512,
                return_kv: bool = False, use_pallas: bool = False):
    """Full-sequence forward (train / prefill). x: [B, S, d]."""
    h = rmsnorm(params["ln_attn"], x, cfg.norm_eps)
    q, k, v = attn.qkv_project(params["attn"], cfg, h, positions,
                               fuse_qkv=fuse_qkv)
    if use_pallas:
        from repro.kernels.flash_attention.ops import attention as _fa
        o = _fa(q, k, v, causal=causal,
                q_block=min(q_block, q.shape[1]),
                kv_block=min(kv_block, k.shape[1]),
                logit_softcap=cfg.attn_logit_softcap)
    else:
        o = attn.chunked_attention(q, k, v, causal=causal, q_block=q_block,
                                   kv_block=kv_block,
                                   logit_softcap=cfg.attn_logit_softcap)
    b, s, _, _ = o.shape
    x = x + o.reshape(b, s, cfg.q_dim) @ params["attn"]["wo"]
    h = rmsnorm(params["ln_mlp"], x, cfg.norm_eps)
    x = x + mlp_apply(params["mlp"], cfg, h)
    if return_kv:
        return x, (k, v)
    return x


def attn_sublayer_cached(params: Dict, cfg: ModelConfig, x: jnp.ndarray,
                         pos: jnp.ndarray, kv: Dict, layer, *, row0=0,
                         batch_axes=None, page_axes=None,
                         fuse_qkv: bool = True):
    """Attention sublayer of C new tokens per row against a stacked paged
    cache, which it reads and does not write.

    x: [B, C, d], row b's tokens at positions ``pos[b] + i``; kv:
    {"k","v"} each [L, Bt, n_pages, page, Hkv, D] (int8 caches add
    "k_scale"/"v_scale"), of which row ``row0 + b`` of layer ``layer``
    is read (see attention.cached_attention). Returns (x + attention,
    (k, v) [B, C, Hkv, D]): the new tokens' K/V in the dtype the cache
    is read in, for attention.write_kv.
    """
    b, c = x.shape[0], x.shape[1]
    h = rmsnorm(params["ln_attn"], x, cfg.norm_eps)
    positions = (jnp.asarray(pos, jnp.int32).reshape(-1, 1)
                 + jnp.arange(c, dtype=jnp.int32)[None])
    q, k, v = attn.qkv_project(params["attn"], cfg, h,
                               jnp.broadcast_to(positions, (b, c)),
                               fuse_qkv=fuse_qkv)
    dt = attn.kv_read_dtype(kv)
    k, v = k.astype(dt), v.astype(dt)
    o = attn.cached_attention(q, kv, k, v, pos, layer=layer, row0=row0,
                              batch_axes=batch_axes, page_axes=page_axes,
                              logit_softcap=cfg.attn_logit_softcap)
    return x + o.reshape(b, c, cfg.q_dim) @ params["attn"]["wo"], (k, v)


def block_decode_paged(params: Dict, cfg: ModelConfig, x: jnp.ndarray,
                       pos: jnp.ndarray, kv: Dict, layer, *, row0=0,
                       batch_axes=None, page_axes=None,
                       fuse_qkv: bool = True):
    """One block over C new tokens per row (decode: C = 1) against a
    page-sharded stacked cache; returns (x, new (k, v)) as
    :func:`attn_sublayer_cached` does."""
    x, kv_new = attn_sublayer_cached(params, cfg, x, pos, kv, layer,
                                     row0=row0, batch_axes=batch_axes,
                                     page_axes=page_axes, fuse_qkv=fuse_qkv)
    h = rmsnorm(params["ln_mlp"], x, cfg.norm_eps)
    return x + mlp_apply(params["mlp"], cfg, h), kv_new


def cross_block_apply(params: Dict, cfg: ModelConfig, x: jnp.ndarray,
                      vision_kv: Tuple[jnp.ndarray, jnp.ndarray],
                      *, q_block: int = 512) -> jnp.ndarray:
    """Gated cross-attention layer; vision_kv from precomputed embeddings."""
    k, v = vision_kv
    h = rmsnorm(params["ln_attn"], x, cfg.norm_eps)
    positions = jnp.zeros(h.shape[:2], jnp.int32)
    q, _, _ = attn.qkv_project(params["attn"], cfg, h, positions, rope=False)
    o = attn.chunked_attention(q, k, v, causal=False, q_block=q_block,
                               kv_block=min(512, k.shape[1]))
    b, s, _, _ = o.shape
    gate = jnp.tanh(params["attn_gate"].astype(jnp.float32)).astype(x.dtype)
    x = x + gate * (o.reshape(b, s, cfg.q_dim) @ params["attn"]["wo"])
    h = rmsnorm(params["ln_mlp"], x, cfg.norm_eps)
    gate = jnp.tanh(params["mlp_gate"].astype(jnp.float32)).astype(x.dtype)
    x = x + gate * mlp_apply(params["mlp"], cfg, h)
    return x


def vision_kv(params: Dict, cfg: ModelConfig,
              vision_embeds: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Precompute cross-attention K/V from (stubbed) vision embeddings."""
    h = vision_embeds
    k = (h @ params["attn"]["wk"]).reshape(
        h.shape[0], h.shape[1], cfg.n_kv_heads, cfg.head_dim)
    v = (h @ params["attn"]["wv"]).reshape(
        h.shape[0], h.shape[1], cfg.n_kv_heads, cfg.head_dim)
    return k, v
