"""The mixture-of-experts decoder family (qwen3-moe), at one chip's share
of its experts: weights made on the device from the seed, and the
operations and bytes of a call.

A layer is the dense family's attention block (``bench/weights.py``'s
leaves and value ranges) and, in place of its MLP, a float32 router over
all ``n_experts`` and the SwiGLU experts held here: ``n_experts_held``
(0: all) from ``first_expert`` on. Expert ``e``'s leaves are made from a
key folded from its global index, so a share holds the very experts the
uncut model has. The router's values (std ``ROUTER_SCALE`` over
sqrt(fan-in)) are rounded to bf16 like every other leaf and held in
float32, as the program's router is.

Costs count what the live requests need. A decode call reads every
weight outside the experts once, the live K/V, and the held experts that
its tokens' routings touch: under uniform routing, of ``T`` tokens with
``k`` experts each of ``E``, ``E_held * (1 - (1 - k/E)^T)`` experts a
layer (``touched`` replaces that with a count). A token does the work of
``k * E_held / E`` held experts on average, in decode and in prefill.
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, Optional

import jax
import jax.numpy as jnp

from bench import costs, weights as W

EXPERTS = ("e_gate", "e_up", "e_down")
EXPERT_KEY = 2_000_000          # folded into a layer's key for its experts
# router std over 1/sqrt(fan-in): logits of std about 2 put 0.62 of a
# token's probability on its top 8 of 128 experts (0.29 at std 1), as a
# router trained to choose does, rather than spreading it evenly
ROUTER_SCALE = 2.0


def n_held(m: Dict) -> int:
    """Experts held here of each layer."""
    return m.get("n_experts_held") or m["n_experts"]


def _shapes(m: Dict) -> Dict[str, tuple]:
    """Per-layer leaves outside the experts, by the reference's names."""
    s = {n: v for n, v in W.leaf_shapes(m).items()
         if n not in ("w_gate", "w_up", "w_down")}
    s["router"] = (m["d_model"], m["n_experts"])
    return s


def _expert_shapes(m: Dict) -> Dict[str, tuple]:
    d, f = m["d_model"], m["d_ff"]
    return {"e_gate": (d, f), "e_up": (d, f), "e_down": (f, d)}


def layer_leaves(key, m: Dict, layer, dtype=jnp.float32) -> Dict:
    """Layer ``layer``'s leaves (bf16 values held in ``dtype``; the
    router always in float32), the experts stacked [E_held, ...] in the
    order of their global index."""
    lk = jax.random.fold_in(key, layer)
    out = {}
    for i, (name, shape) in enumerate(sorted(_shapes(m).items())):
        if name == "router":
            v = W._leaf(jax.random.fold_in(lk, i), name, shape,
                        ROUTER_SCALE / math.sqrt(shape[0]))
            out[name] = v.astype(jnp.bfloat16).astype(jnp.float32)
        else:
            v = W._leaf(jax.random.fold_in(lk, i), name, shape)
            out[name] = v.astype(jnp.bfloat16).astype(dtype)
    ek = jax.random.fold_in(lk, EXPERT_KEY)

    def expert(e):
        k = jax.random.fold_in(ek, e)
        return {name: W._leaf(jax.random.fold_in(k, j), name, shape)
                .astype(jnp.bfloat16).astype(dtype)
                for j, (name, shape) in enumerate(
                    sorted(_expert_shapes(m).items()))}

    first = m.get("first_expert", 0)
    out.update(jax.vmap(expert)(first + jnp.arange(n_held(m))))
    return out


def program_params(seed: int, m: Dict):
    """The program's parameter tree in bf16 (router float32), made in one
    jitted call."""
    key = W.key_for(seed)

    def make(key):
        lv = jax.lax.map(lambda i: layer_leaves(key, m, i, jnp.bfloat16),
                         jnp.arange(m["n_layers"]))
        blocks = {"ln_attn": {"scale": lv["ln_attn"]},
                  "attn": {k: lv[k] for k in
                           ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
                           if k in lv},
                  "ln_mlp": {"scale": lv["ln_mlp"]},
                  "moe": {k: lv[k] for k in ("router",) + EXPERTS}}
        embed = {"embedding": W.table(key, m, "embedding", jnp.bfloat16)}
        if not m["tie_embeddings"]:
            embed["unembed"] = W.table(key, m, "unembed", jnp.bfloat16)
        return {"embed": embed,
                "ln_f": {"scale": W.table(key, m, "ln_f", jnp.bfloat16)},
                "blocks": blocks}

    return jax.jit(make)(key)


def _params(m: Dict):
    """(attention, router, one expert) parameters of a layer, and the
    output head's."""
    d, f = m["d_model"], m["d_ff"]
    q, kv = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    return (d * (q + 2 * kv) + q * d, d * m["n_experts"], 3 * d * f,
            d * m["vocab_size"])


def expected_touched(m: Dict, tokens: int) -> float:
    """Held experts of a layer that ``tokens`` uniformly routed tokens
    touch, on average."""
    return n_held(m) * (1.0 - (1.0 - m["top_k"] / m["n_experts"]) ** tokens)


def routed_per_token(m: Dict) -> float:
    """Routings of a token that land on held experts, on average."""
    return m["top_k"] * n_held(m) / m["n_experts"]


def _flops(m: Dict, tokens: int, context: int) -> float:
    """Model flops of ``tokens`` tokens whose attention spans ``context``
    query-key pairs in all."""
    attn, router, expert, head = _params(m)
    L = m["n_layers"]
    return (2 * (L * (attn + router) + head) * tokens
            + 2 * L * expert * routed_per_token(m) * tokens
            + 4 * L * m["n_heads"] * m["head_dim"] * context)


def decode_call(m: Dict, live: Iterable[int],
                touched: Optional[float] = None):
    """(flops, bytes) of one decode call; ``live`` lists each live
    slot's context including the token being decoded; ``touched``, held
    experts read per layer (default: the uniform expectation)."""
    live = list(live)
    attn, router, expert, head = _params(m)
    L = m["n_layers"]
    if touched is None:
        touched = expected_touched(m, len(live))
    kvb = costs.kv_bytes_per_token(m)
    weights = (L * attn + head) * costs.BF16 + L * router * 4 \
        + L * touched * expert * costs.BF16
    nbytes = weights + sum(c - 1 for c in live) * kvb \
        + len(live) * (kvb + m["d_model"] * costs.BF16)
    return _flops(m, len(live), sum(live)), nbytes


def prefill_call(m: Dict, pos0: int, n: int) -> float:
    """Model flops of ``n`` prompt tokens after ``pos0`` cached ones."""
    return _flops(m, n, n * pos0 + n * (n + 1) // 2)
