"""Jitted public wrapper: model layout <-> kernel layout adaptation.

On the CPU backend the kernel body runs under ``interpret=True``; a TPU
compiles the Mosaic kernel (see ``repro.kernels.interpret_mode``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.flash_attention.kernel import flash_attention


@functools.partial(jax.jit, static_argnames=("causal", "q_block",
                                             "kv_block", "logit_softcap"))
def attention(q, k, v, *, causal: bool = True, q_block: int = 256,
              kv_block: int = 256, logit_softcap: float = 0.0):
    """Model-layout entry point. q: [B, S, H, D]; k/v: [B, S, Hkv, D]."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qk = jnp.moveaxis(q.reshape(b, s, hkv, g, d), 1, 3)   # [B,Hkv,G,S,D]
    kk = jnp.moveaxis(k, 1, 2)                            # [B,Hkv,S,D]
    vk = jnp.moveaxis(v, 1, 2)
    o = flash_attention(qk, kk, vk, causal=causal, q_block=q_block,
                        kv_block=kv_block, logit_softcap=logit_softcap,
                        interpret=interpret_mode())
    return jnp.moveaxis(o, 3, 1).reshape(b, s, h, d)
