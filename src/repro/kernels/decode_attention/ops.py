"""Jitted wrapper for the paged flash-decode kernel (model layout)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.decode_attention.kernel import paged_flash_decode


@functools.partial(jax.jit, static_argnames=("logit_softcap",))
def decode(q, k_pages, v_pages, kv_len, *, logit_softcap: float = 0.0,
           k_scale=None, v_scale=None):
    """q: [B, 1, H, D]; pages: [B, P, page, Hkv, D]; kv_len scalar.

    int8 pages take fp32 ``k_scale``/``v_scale`` [B, P, Hkv] (per-page,
    per-head symmetric scales — models/kv_quant.py layout); the kernel
    dequantizes in-VMEM. Returns [B, 1, H, D] — the local-shard result
    (combine across page shards outside).
    """
    b, _, h, d = q.shape
    p, page, hkv = k_pages.shape[1], k_pages.shape[2], k_pages.shape[3]
    g = h // hkv
    qk = q.reshape(b, hkv, g, d)
    kp = jnp.moveaxis(k_pages, 3, 1)          # [B, Hkv, P, page, D]
    vp = jnp.moveaxis(v_pages, 3, 1)
    ks = None if k_scale is None else jnp.moveaxis(k_scale, 2, 1)
    vs = None if v_scale is None else jnp.moveaxis(v_scale, 2, 1)
    o = paged_flash_decode(qk, kp, vp, kv_len, logit_softcap=logit_softcap,
                           interpret=interpret_mode(), k_scale=ks, v_scale=vs)
    return o.reshape(b, 1, h, d)
