"""Operations and bytes the dense model needs per call, from its shapes.

These count what the live requests need, not what an implementation
happens to do: a decode call reads every weight once, the K/V of each
live slot's positions so far, and writes one token's K/V per slot. The
jnp flash-decode of the program scans every slot's whole ``max_seq``;
that excess is the implementation's, and shows as roofline lost.
"""
from __future__ import annotations

from typing import Dict, Iterable

BF16 = 2


def block_params(m: Dict) -> int:
    """Parameters of all decoder layers (norm scales left out)."""
    d, f = m["d_model"], m["d_ff"]
    q, kv = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    return m["n_layers"] * (d * (q + 2 * kv) + q * d + 3 * d * f)


def head_params(m: Dict) -> int:
    """Parameters of the output head (the tied table, where tied)."""
    return m["d_model"] * m["vocab_size"]


def kv_bytes_per_token(m: Dict) -> int:
    """K and V of one position over all layers, in bf16."""
    return 2 * m["n_layers"] * m["n_kv_heads"] * m["head_dim"] * BF16


def _attn_flops(m: Dict, context: int) -> int:
    """Q.K and P.V of one query over ``context`` positions, all layers."""
    return 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * context


def decode_call(m: Dict, live: Iterable[int]):
    """(flops, bytes) of one decode call; ``live`` lists each live
    slot's context length including the token being decoded."""
    live = list(live)
    p = block_params(m) + head_params(m)
    flops = 2 * p * len(live) + sum(_attn_flops(m, c) for c in live)
    kvb = kv_bytes_per_token(m)
    nbytes = (p * BF16 + sum(c - 1 for c in live) * kvb
              + len(live) * (kvb + m["d_model"] * BF16))
    return flops, nbytes


def prefill_call(m: Dict, pos0: int, n: int) -> int:
    """Model flops of ``n`` prompt tokens after ``pos0`` cached ones."""
    p = block_params(m) + head_params(m)
    ctx = n * pos0 + n * (n + 1) // 2
    return 2 * p * n + _attn_flops(m, ctx)


def least_time(flops: float, nbytes: float, peaks: Dict) -> float:
    """Seconds the chip needs at least: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
