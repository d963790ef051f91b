"""Seeded weights for the dense family, made on the device.

The benchmark makes the weights, not the program: :func:`program_params`
builds the program's parameter tree in bf16 in one jitted call, and the
reference remakes any leaf in float32 from the same seed with
:func:`layer_leaves` and :func:`table`. Each value comes from
``jax.random.bits`` through integer arithmetic, one float32 multiply and
a rounding to bf16, so both sides get the same numbers on any backend.

Layer matrices are uniform with standard deviation 1/sqrt(fan-in), so
every sublayer adds about as much to the residual as it holds and the
served tokens depend on the whole context, not mostly on the last token;
vocabulary tables have standard deviation ``TABLE_STD``. Norm scales are
uniform in [0.75, 1.25], so a norm applied in the wrong place shows.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

TABLE_STD = 0.02
ROW_BLOCK = 128          # rows of a vocabulary table made per key


def key_for(seed: int):
    """A threefry key from a seed of any size (JAX keeps 32 bits)."""
    words = np.random.default_rng([seed, 7]).integers(
        0, 2 ** 32, size=2, dtype=np.uint64).astype(np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def _uniform(key, shape, half_width: float, centre: float = 0.0):
    bits = jax.random.bits(key, shape, jnp.uint32)
    i = (bits >> 8).astype(jnp.int32) - (1 << 23)     # [-2^23, 2^23)
    x = i.astype(jnp.float32) * jnp.float32(half_width / (1 << 23))
    return x + jnp.float32(centre) if centre else x


def leaf_shapes(m: Dict) -> Dict[str, tuple]:
    """Per-layer leaves of a dense block, by the reference's names."""
    d, f = m["d_model"], m["d_ff"]
    q, kv = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    s = {"ln_attn": (d,), "wq": (d, q), "wk": (d, kv), "wv": (d, kv),
         "wo": (q, d), "ln_mlp": (d,), "w_gate": (d, f), "w_up": (d, f),
         "w_down": (f, d)}
    if m.get("qk_norm"):
        s["q_norm"] = (m["head_dim"],)
        s["k_norm"] = (m["head_dim"],)
    return s


def _leaf(key, name: str, shape, std=None):
    if len(shape) == 1:                       # a norm's scale
        return _uniform(key, shape, 0.25, 1.0)
    std = std or 1.0 / math.sqrt(shape[0])
    return _uniform(key, shape, std * math.sqrt(3.0))


def layer_leaves(key, m: Dict, layer, dtype=jnp.float32) -> Dict:
    """Layer ``layer``'s leaves (bf16 values, held in ``dtype``)."""
    lk = jax.random.fold_in(key, layer)
    return {n: _leaf(jax.random.fold_in(lk, i), n, s).astype(jnp.bfloat16)
            .astype(dtype)
            for i, (n, s) in enumerate(sorted(leaf_shapes(m).items()))}


_TABLES = ("embedding", "unembed", "ln_f")


def table(key, m: Dict, name: str, dtype=jnp.float32):
    """``embedding`` [V, d], ``unembed`` [d, V] (untied only) or the
    final norm's ``ln_f`` [d], made in blocks of ``ROW_BLOCK`` rows."""
    tk = jax.random.fold_in(key, 1_000_000 + _TABLES.index(name))
    d, v = m["d_model"], m["vocab_size"]
    if name == "ln_f":
        return _leaf(tk, name, (d,)).astype(jnp.bfloat16).astype(dtype)
    rows, cols = (v, d) if name == "embedding" else (d, v)
    assert rows % ROW_BLOCK == 0, (name, rows)

    def block(i):
        return _leaf(jax.random.fold_in(tk, i), name, (ROW_BLOCK, cols),
                     TABLE_STD).astype(jnp.bfloat16).astype(dtype)

    out = jax.lax.map(block, jnp.arange(rows // ROW_BLOCK))
    return out.reshape(rows, cols)


def program_params(seed: int, m: Dict):
    """The program's parameter tree in bf16, made in one jitted call
    (no float32 copy of a stacked leaf is ever held)."""
    key = key_for(seed)

    def make(key):
        def layer(i):
            return layer_leaves(key, m, i, jnp.bfloat16)

        leaves = jax.lax.map(layer, jnp.arange(m["n_layers"]))
        blocks = {"ln_attn": {"scale": leaves["ln_attn"]},
                  "attn": {k: leaves[k] for k in
                           ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
                           if k in leaves},
                  "ln_mlp": {"scale": leaves["ln_mlp"]},
                  "mlp": {k: leaves[k] for k in
                          ("w_gate", "w_up", "w_down")}}
        embed = {"embedding": table(key, m, "embedding", jnp.bfloat16)}
        if not m["tie_embeddings"]:
            embed["unembed"] = table(key, m, "unembed", jnp.bfloat16)
        return {"embed": embed,
                "ln_f": {"scale": table(key, m, "ln_f", jnp.bfloat16)},
                "blocks": blocks}

    return jax.jit(make)(key)
