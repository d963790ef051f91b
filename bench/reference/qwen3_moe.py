"""Plain float32 reference of the qwen3-moe family at one chip's share of
its experts.

Straightforward ``jax.numpy`` at ``highest`` matmul precision: token
embedding, then per layer the attention block of the dense reference
(RMSNorm, Q/K/V projections, per-head RMSNorm of Q and K, rotary
embedding, causal grouped-query attention, output projection, residual),
then RMSNorm and the sparse MoE block: a softmax over the router's logits
for all ``n_experts``, the top ``top_k`` of them renormalised over the
``top_k``, and the sum of the routed experts' SwiGLU outputs, each times
its gate, with its residual; then the final RMSNorm and the output head
(untied in the published model; the embedding's transpose where tied).
Only the experts held here (``n_experts_held`` from ``first_expert`` on)
add their part: what the absent experts would add is left out, as in the
program, and that partial result goes on to the next layer. No cache, no
batching, no kernel; each sequence runs whole, its weights remade from the
seed through the family's module (``bench/families/moe.py``) one layer at
a time.

``precision="fp8"`` is the control, as in :mod:`bench.reference.dense`:
every matmul operand (the router's too) rounded to float8 e4m3 after
scaling, products summed in float32.

Beside each served position's gap, :func:`gaps` counts the layers in
which the routing there was a near tie that the held share could see:
the ``top_k``-th and the next router probabilities within ``TIE_MARGIN``
of each other in log, with one of the two experts held here. A bf16
program can route such a token the other way, so a gap at a position
with ties is named as a flip.
"""
from __future__ import annotations

import functools
import pathlib
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec, weights as W
from bench.reference import dense as D

FAMILY = spec.family_module(pathlib.Path(__file__).resolve().parents[2],
                            "moe")
# log(p_k / p_k+1) under which a routing counts as a near tie: a few bf16
# roundings (2^-8 relative) of the router's logits
TIE_MARGIN = 2.0 ** -6


def _emm(a, w, spec_, fp8: bool):
    """An expert-batched matmul (einsum ``spec_``); fp8 rounds each
    expert's weights per output channel and the activations per row."""
    if fp8:
        a, w = D._fp8(a, -1), D._fp8(w, -2)
    return jnp.einsum(spec_, a, w)


def _block(x, w, m: Dict, fp8: bool):
    """One layer on [S, d]; also the near ties of its routing [S] and
    the experts it routed to [S, k]."""
    eps, hd, k = m["norm_eps"], m["head_dim"], m["top_k"]
    s = x.shape[0]
    h = D._rmsnorm(x, w["ln_attn"], eps)
    q = D._mm(h, w["wq"], fp8).reshape(s, m["n_heads"], hd)
    kk = D._mm(h, w["wk"], fp8).reshape(s, m["n_kv_heads"], hd)
    v = D._mm(h, w["wv"], fp8).reshape(s, m["n_kv_heads"], hd)
    q = D._rmsnorm(q, w["q_norm"], eps)
    kk = D._rmsnorm(kk, w["k_norm"], eps)
    q, kk = D._rope(q, m["rope_theta"]), D._rope(kk, m["rope_theta"])
    x = x + D._mm(D._attention(q, kk, v, fp8).reshape(s, -1), w["wo"], fp8)

    h = D._rmsnorm(x, w["ln_mlp"], eps)
    p = jax.nn.softmax(D._mm(h, w["router"], fp8), axis=-1)     # [S, E]
    top, idx = jax.lax.top_k(p, k + 1)
    gate = top[:, :k] / top[:, :k].sum(-1, keepdims=True)       # [S, k]
    first, held = m.get("first_expert", 0), FAMILY.n_held(m)
    mine = jnp.arange(held) + first                             # global ids
    g = (gate[:, :, None] * (idx[:, :k, None] == mine)).sum(1)  # [S, held]
    a = jax.nn.silu(_emm(h, w["e_gate"], "sd,edf->esf", fp8)) \
        * _emm(h, w["e_up"], "sd,edf->esf", fp8)
    y = _emm(a, w["e_down"], "esf,efd->esd", fp8)               # [held,S,d]
    x = x + jnp.einsum("se,esd->sd", g, y)

    edge = idx[:, k - 1:k + 1] - first
    tie = (jnp.log(top[:, k - 1] / top[:, k]) < TIE_MARGIN) \
        & ((edge >= 0) & (edge < held)).any(-1)
    return x, tie.astype(jnp.int32), idx[:, :k]


def _head(key, m: Dict, emb):
    """The output head [d, V]: the embedding's transpose where tied."""
    return emb.T if m["tie_embeddings"] else W.table(key, m, "unembed")


@functools.lru_cache(maxsize=32)
def _program(m_items: tuple, s_pad: int, n_pad: int, fp8: bool):
    m = dict(m_items)

    def run(key_data, tokens, rows, targets):
        key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
        with jax.default_matmul_precision("highest"):
            emb = W.table(key, m, "embedding")
            x = emb[tokens]

            def layer(i, carry):
                x, ties = carry
                x, tie, _ = _block(x, FAMILY.layer_leaves(key, m, i), m,
                                   fp8)
                return x, ties + tie

            x, ties = jax.lax.fori_loop(
                0, m["n_layers"], layer,
                (x, jnp.zeros(x.shape[0], jnp.int32)))
            x = D._rmsnorm(x[rows], W.table(key, m, "ln_f"), m["norm_eps"])
            head = _head(key, m, emb)
            chunk = min(n_pad, D.ROW_CHUNK)

            def logits_gap(args):
                xc, tc = args
                lg = D._mm(xc, head, fp8)
                best = lg.max(-1)
                got = jnp.take_along_axis(lg, tc[:, None], -1)[:, 0]
                return best - got, jnp.argmax(lg, -1).astype(jnp.int32)

            gaps, arg = jax.lax.map(
                logits_gap, (x.reshape(-1, chunk, x.shape[-1]),
                             targets.reshape(-1, chunk)))
        return gaps.reshape(-1), arg.reshape(-1), ties[rows]

    return jax.jit(run)


def logits(seed: int, m: Dict, tokens: Sequence[int],
           precision: str = "f32") -> np.ndarray:
    """Every position's logits [S, V] of one sequence (small sizes)."""
    fp8 = precision == "fp8"
    key = W.key_for(seed)

    @jax.jit
    def run(tokens):
        with jax.default_matmul_precision("highest"):
            emb = W.table(key, m, "embedding")
            x = emb[tokens]
            for i in range(m["n_layers"]):
                x, _, _ = _block(x, FAMILY.layer_leaves(key, m, i), m, fp8)
            x = D._rmsnorm(x, W.table(key, m, "ln_f"), m["norm_eps"])
            return D._mm(x, _head(key, m, emb), fp8)

    return np.asarray(run(jnp.asarray(tokens, jnp.int32)))


def routing(seed: int, m: Dict, tokens: Sequence[int]) -> np.ndarray:
    """The experts each position routes to in each layer, [L, S, k],
    global ids (small sizes)."""
    key = W.key_for(seed)

    @jax.jit
    def run(tokens):
        out = []
        with jax.default_matmul_precision("highest"):
            x = W.table(key, m, "embedding")[tokens]
            for i in range(m["n_layers"]):
                x, _, idx = _block(x, FAMILY.layer_leaves(key, m, i), m,
                                   False)
                out.append(idx)
        return jnp.stack(out)

    return np.asarray(run(jnp.asarray(tokens, jnp.int32)))


def gaps(seed: int, m: Dict, seqs: Sequence, precision: str = "f32"
         ) -> List[Dict]:
    """Per sequence ``(prompt, served, targets)``: at each served
    position, the gap by which ``targets`` (default: the served tokens)
    lies below the reference's best logit, the reference's own argmax
    there, and the layers whose routing there was a near tie (``ties``).
    ``precision`` is ``"f32"`` or the ``"fp8"`` control."""
    assert precision in ("f32", "fp8")
    key_data = jax.random.key_data(W.key_for(seed))
    m_items = tuple(sorted(m.items()))
    out = []
    for prompt, served, targets in seqs:
        prompt, served = list(prompt), list(served)
        targets = list(served if targets is None else targets)
        toks = prompt + served[:-1]
        n = len(served)
        s_pad = D._pad(len(toks), D.LADDER)
        n_pad = D._pad(n, 128) if n <= D.ROW_CHUNK else D._pad(n, D.ROW_CHUNK)
        t = np.zeros(s_pad, np.int32)
        t[: len(toks)] = toks
        rows = np.zeros(n_pad, np.int32)
        rows[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
        tg = np.zeros(n_pad, np.int32)
        tg[:n] = targets
        g, a, ties = _program(m_items, s_pad, n_pad, precision == "fp8")(
            key_data, t, rows, tg)
        out.append({"gaps": np.asarray(g)[:n], "argmax": np.asarray(a)[:n],
                    "ties": np.asarray(ties)[:n]})
    return out
