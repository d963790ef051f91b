"""Plain float32 reference of the dense decoder family.

Straightforward ``jax.numpy`` at ``highest`` matmul precision: token
embedding, then per layer RMSNorm, Q/K/V projections, optional per-head
RMSNorm of Q and K (qwen3), rotary embedding over the whole head in the
half-split layout, causal grouped-query attention, output projection,
RMSNorm and a SwiGLU MLP, each with its residual; then a final RMSNorm
and the (tied or separate) output head. No cache, no batching, no
kernel: each sequence runs whole. It imports nothing of the program;
its weights are remade from the seed by :mod:`bench.weights`, one layer
at a time, so the reference fits beside nothing else on the chip.

``precision="fp8"`` is the control: the same computation with every
matmul operand (weights per output channel, activations per row, K and
V per head) rounded to float8 e4m3 after scaling its largest value to
448, products summed in float32.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

Q_BLOCK = 512            # query rows per attention block
ROW_CHUNK = 512          # output-head rows per block
LADDER = 512             # sequence lengths are padded to a multiple


def _round_e4m3(x):
    """``x`` (|x| <= 448) rounded to float8 e4m3 (3 mantissa bits,
    subnormal step 2^-9), ties to even."""
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    b = (b + jnp.uint32(0x7FFFF) + ((b >> 20) & 1)) & jnp.uint32(0xFFF00000)
    normal = jax.lax.bitcast_convert_type(b, jnp.float32)
    sub = jnp.round(x * 512.0) / 512.0
    return jnp.where(jnp.abs(x) < 2.0 ** -6, sub, normal)


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return _round_e4m3(x / s) * s


def _mm(a, w, fp8: bool):
    """``a [.., k] @ w [k, n]``; fp8 rounds both operands first."""
    if fp8:
        a, w = _fp8(a, -1), _fp8(w, 0)
    return a @ w


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _rope(x, theta):
    """x [S, H, D]; positions 0..S-1; pairs (i, i + D/2) rotate."""
    s, d = x.shape[0], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs    # [S, D/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, fp8: bool):
    """Causal attention, q [S, H, D], k/v [S, Hkv, D] -> [S, H, D]."""
    s, h, d = q.shape
    g = h // k.shape[1]
    if fp8:
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, -1)
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    outs = []
    for start in range(0, s, Q_BLOCK):
        end = min(start + Q_BLOCK, s)
        sc = jnp.einsum("qhd,khd->hqk", q[start:end], k[:end]) / math.sqrt(d)
        mask = (jnp.arange(start, end)[:, None] >= jnp.arange(end)[None])
        sc = jnp.where(mask[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        if fp8:
            p = _fp8(p, -1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v[:end]))
    return jnp.concatenate(outs, 0)


def _block(x, w, m: Dict, fp8: bool):
    eps, hd = m["norm_eps"], m["head_dim"]
    s = x.shape[0]
    h = _rmsnorm(x, w["ln_attn"], eps)
    q = _mm(h, w["wq"], fp8).reshape(s, m["n_heads"], hd)
    k = _mm(h, w["wk"], fp8).reshape(s, m["n_kv_heads"], hd)
    v = _mm(h, w["wv"], fp8).reshape(s, m["n_kv_heads"], hd)
    if m.get("qk_norm"):
        q = _rmsnorm(q, w["q_norm"], eps)
        k = _rmsnorm(k, w["k_norm"], eps)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    o = _attention(q, k, v, fp8).reshape(s, -1)
    x = x + _mm(o, w["wo"], fp8)
    h = _rmsnorm(x, w["ln_mlp"], eps)
    a = jax.nn.silu(_mm(h, w["w_gate"], fp8)) * _mm(h, w["w_up"], fp8)
    return x + _mm(a, w["w_down"], fp8)


@functools.lru_cache(maxsize=32)
def _program(m_items: tuple, s_pad: int, n_pad: int, fp8: bool):
    m = dict(m_items)

    def run(key_data, tokens, rows, targets):
        key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
        with jax.default_matmul_precision("highest"):
            emb = W.table(key, m, "embedding")
            x = emb[tokens]

            def layer(i, x):
                return _block(x, W.layer_leaves(key, m, i), m, fp8)

            x = jax.lax.fori_loop(0, m["n_layers"], layer, x)
            x = _rmsnorm(x[rows], W.table(key, m, "ln_f"), m["norm_eps"])
            head = emb.T if m["tie_embeddings"] else \
                W.table(key, m, "unembed")
            chunk = min(n_pad, ROW_CHUNK)

            def logits_gap(args):
                xc, tc = args
                lg = _mm(xc, head, fp8)
                best = lg.max(-1)
                got = jnp.take_along_axis(lg, tc[:, None], -1)[:, 0]
                return best - got, jnp.argmax(lg, -1).astype(jnp.int32)

            gaps, arg = jax.lax.map(
                logits_gap, (x.reshape(-1, chunk, x.shape[-1]),
                             targets.reshape(-1, chunk)))
        return gaps.reshape(-1), arg.reshape(-1)

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
                x = _block(x, W.layer_leaves(key, m, i), m, fp8)
            x = _rmsnorm(x, W.table(key, m, "ln_f"), m["norm_eps"])
            head = emb.T if m["tie_embeddings"] else \
                W.table(key, m, "unembed")
            return _mm(x, head, fp8)

    return np.asarray(run(jnp.asarray(tokens, jnp.int32)))


def _pad(n: int, q: int) -> int:
    return max(q, -(-n // q) * q)


def gaps(seed: int, m: Dict, seqs: Sequence, precision: str = "f32"
         ) -> List[Dict]:
    """Per sequence ``(prompt, served, targets)``: at each served
    position, the gap by which ``targets`` (default: the served tokens)
    lies below the reference's best logit, and the reference's own
    argmax there. ``precision`` is ``"f32"`` or the ``"fp8"`` control."""
    assert precision in ("f32", "fp8")
    key_data = jax.random.key_data(W.key_for(seed))
    m_items = tuple(sorted(m.items()))
    out = []
    for prompt, served, targets in seqs:
        prompt, served = list(prompt), list(served)
        targets = list(served if targets is None else targets)
        toks = prompt + served[:-1]
        n = len(served)
        s_pad = _pad(len(toks), LADDER)
        n_pad = _pad(n, 128) if n <= ROW_CHUNK else _pad(n, ROW_CHUNK)
        t = np.zeros(s_pad, np.int32)
        t[: len(toks)] = toks
        rows = np.zeros(n_pad, np.int32)
        rows[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
        tg = np.zeros(n_pad, np.int32)
        tg[:n] = targets
        g, a = _program(m_items, s_pad, n_pad, precision == "fp8")(
            key_data, t, rows, tg)
        out.append({"gaps": np.asarray(g)[:n], "argmax": np.asarray(a)[:n]})
    return out
