"""Property-style differential parity: every Pallas kernel package vs its
pure-jnp oracle across randomly drawn shapes/dtypes/seeds.

Each test executes against many hypothesis-drawn examples, complementing
the fixed-case sweep in ``test_kernels.py``. Kernels execute in interpret
mode on CPU (the same code path Mosaic compiles on TPU).

Also covers the serving engine's dispatch split: the single-rank fast
path in ``attention.cached_attention`` vs the shard_map path must be
numerically identical (``force_shard_map`` pins the latter on).
"""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.kernels.decode_attention.ops import decode
from repro.kernels.decode_attention.ref import (paged_flash_decode_ref,
                                                paged_flash_decode_quant_ref)
from repro.kernels.flash_attention.ops import attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.hdm_stream.ops import stream_matmul
from repro.kernels.hdm_stream.ref import paged_matmul_ref
from repro.kernels.mamba2_scan.ops import ssd
from repro.kernels.mamba2_scan.ref import ssd_scan_ref
from repro.models import attention as attn, kv_quant as kvq


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=3e-5, rtol=3e-5)


def _key(seed, i=0):
    return jax.random.fold_in(jax.random.PRNGKey(seed), i)


def _decode_layer(q, kp, vp, nk, nv, pos, *, k_scale=None, v_scale=None,
                  **kw):
    """One layer's decode tick as the serving programs run it:
    ``cached_attention`` over the stored tokens plus the new one, then
    ``write_kv`` of the new token at ``pos``. pages: [B, P, page, Hkv, D]
    (int8 with ``k_scale``/``v_scale`` [B, P, Hkv]). Returns (out, k, v)
    or, int8, (out, k, v, k_scale, v_scale)."""
    kv = {"k": kp[None], "v": vp[None]}
    if k_scale is not None:
        kv.update(k_scale=k_scale[None], v_scale=v_scale[None])
    dt = attn.kv_read_dtype(kv)
    nk, nv = nk.astype(dt), nv.astype(dt)
    out = attn.cached_attention(q, kv, nk, nv, pos, layer=0, **kw)
    kv = attn.write_kv(kv, nk[None], nv[None], pos)
    names = ("k", "v", "k_scale", "v_scale") if k_scale is not None \
        else ("k", "v")
    return (out,) + tuple(kv[n][0] for n in names)


DTYPES = [jnp.float32, jnp.bfloat16]

# (B, S, H, Hkv, D, q_block, kv_block) — tiny so interpret mode stays fast
FLASH_SHAPES = [
    (1, 32, 2, 2, 16, 16, 16),
    (1, 64, 4, 2, 16, 32, 16),
    (2, 32, 4, 1, 16, 16, 32),
]


@settings(max_examples=10, deadline=None)
@given(shape=st.sampled_from(FLASH_SHAPES), dtype=st.sampled_from(DTYPES),
       causal=st.booleans(), seed=st.integers(0, 2 ** 16))
def test_flash_attention_parity(shape, dtype, causal, seed):
    B, S, H, Hkv, D, qb, kb = shape
    q = jax.random.normal(_key(seed, 0), (B, S, H, D), dtype)
    k = jax.random.normal(_key(seed, 1), (B, S, Hkv, D), dtype)
    v = jax.random.normal(_key(seed, 2), (B, S, Hkv, D), dtype)
    out = attention(q, k, v, causal=causal, q_block=qb, kv_block=kb)
    g = H // Hkv
    qr = jnp.moveaxis(q.reshape(B, S, Hkv, g, D), 1, 3)
    ref = flash_attention_ref(qr, jnp.moveaxis(k, 1, 2),
                              jnp.moveaxis(v, 1, 2), causal=causal)
    ref = jnp.moveaxis(ref, 3, 1).reshape(B, S, H, D)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


# (B, H, Hkv, D, P, page)
DECODE_SHAPES = [
    (1, 4, 4, 16, 2, 8),
    (2, 4, 2, 16, 4, 8),
    (1, 4, 1, 32, 3, 8),
]


@settings(max_examples=10, deadline=None)
@given(shape=st.sampled_from(DECODE_SHAPES), dtype=st.sampled_from(DTYPES),
       lendraw=st.integers(0, 2 ** 16), seed=st.integers(0, 2 ** 16))
def test_paged_flash_decode_parity(shape, dtype, lendraw, seed):
    B, H, Hkv, D, P, page = shape
    kv_len = 1 + lendraw % (P * page)          # every fill level reachable
    q = jax.random.normal(_key(seed, 0), (B, 1, H, D), dtype)
    kp = jax.random.normal(_key(seed, 1), (B, P, page, Hkv, D), dtype)
    vp = jax.random.normal(_key(seed, 2), (B, P, page, Hkv, D), dtype)
    out = decode(q, kp, vp, jnp.int32(kv_len))
    g = H // Hkv
    ref = paged_flash_decode_ref(
        q.reshape(B, Hkv, g, D), jnp.moveaxis(kp, 3, 1),
        jnp.moveaxis(vp, 3, 1), kv_len)
    np.testing.assert_allclose(
        np.asarray(out, np.float32).reshape(B, Hkv, g, D),
        np.asarray(ref, np.float32), **_tol(dtype))


# (B, S, H, P, N, chunk) — chunk divides S
SSD_SHAPES = [
    (1, 32, 2, 8, 16, 16),
    (2, 32, 3, 8, 8, 32),
    (1, 64, 1, 16, 8, 16),
]


@settings(max_examples=10, deadline=None)
@given(shape=st.sampled_from(SSD_SHAPES), seed=st.integers(0, 2 ** 16))
def test_ssd_scan_parity(shape, seed):
    B, S, H, P, N, chunk = shape
    xdt = jax.random.normal(_key(seed, 0), (B, S, H, P))
    bm = jax.random.normal(_key(seed, 1), (B, S, N)) * 0.5
    cm = jax.random.normal(_key(seed, 2), (B, S, N)) * 0.5
    la = -jnp.abs(jax.random.normal(_key(seed, 3), (B, S, H))) * 0.1
    y = ssd(xdt, bm, cm, la, chunk=chunk)
    c = S // chunk
    lac = jnp.moveaxis(jnp.cumsum(la.reshape(B, c, chunk, H), axis=2), 3, 1)
    ref = ssd_scan_ref(jnp.moveaxis(xdt.reshape(B, c, chunk, H, P), 3, 1),
                       bm.reshape(B, c, chunk, N),
                       cm.reshape(B, c, chunk, N), lac)
    ref = jnp.moveaxis(ref, 1, 3).reshape(B, S, H, P)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


# (M, K, N, page_k, n_pages, block_m, block_n)
HDM_SHAPES = [
    (32, 64, 64, 16, 8, 32, 32),
    (32, 64, 32, 32, 4, 32, 32),
    (64, 32, 32, 16, 4, 32, 32),
]


@settings(max_examples=10, deadline=None)
@given(shape=st.sampled_from(HDM_SHAPES), dtype=st.sampled_from(DTYPES),
       seed=st.integers(0, 2 ** 16))
def test_hdm_stream_matmul_parity(shape, dtype, seed):
    M, K, N, page_k, n_pages, bm, bn = shape
    x = jax.random.normal(_key(seed, 0), (M, K), dtype)
    wp = jax.random.normal(_key(seed, 1), (n_pages, page_k, N), dtype)
    rng = np.random.default_rng(seed)
    pids = jnp.asarray(rng.permutation(n_pages)[:K // page_k], jnp.int32)
    y = stream_matmul(x, wp, pids, block_m=bm, block_n=bn)
    ref = paged_matmul_ref(x, wp, pids)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


# ------------------------------------------- fast path vs shard_map path

@settings(max_examples=8, deadline=None)
@given(shape=st.sampled_from(DECODE_SHAPES), lendraw=st.integers(0, 2 ** 16),
       seed=st.integers(0, 2 ** 16))
def test_paged_decode_fast_path_matches_shard_map(shape, lendraw, seed):
    """The serving decode tick picks the single-rank fast path when the
    mesh axes are degenerate; both it and the rank-masked shard_map body
    must produce identical outputs AND identical updated page buffers.
    Both store through the one ``write_kv``; its sharded form is checked
    against a single device in ``tests/test_sharded_serving.py``."""
    from repro.launch.mesh import make_host_mesh

    B, H, Hkv, D, P, page = shape
    q = jax.random.normal(_key(seed, 0), (B, 1, H, D), jnp.float32)
    kp = jax.random.normal(_key(seed, 1), (B, P, page, Hkv, D), jnp.float32)
    vp = jax.random.normal(_key(seed, 2), (B, P, page, Hkv, D), jnp.float32)
    nk = jax.random.normal(_key(seed, 3), (B, 1, Hkv, D), jnp.float32)
    nv = jax.random.normal(_key(seed, 4), (B, 1, Hkv, D), jnp.float32)
    # per-slot positions in [0, P*page): continuous batching leaves every
    # slot at a different fill level
    pos = jnp.asarray([(lendraw + 7 * i) % (P * page) for i in range(B)],
                      jnp.int32)
    with jax.set_mesh(make_host_mesh()):
        fast = _decode_layer(q, kp, vp, nk, nv, pos,
                              batch_axes="data", page_axes="model")
        smap = _decode_layer(q, kp, vp, nk, nv, pos,
                              batch_axes="data", page_axes="model",
                              force_shard_map=True)
    for a, b, name in zip(fast, smap, ("out", "k_pages", "v_pages")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5, err_msg=name)


# ----------------------------------------------- int8 KV page parity

def _quantized_pages(x):
    """Model-layout pages [B, P, page, Hkv, D] -> (int8 pages, fp32 scales)."""
    s = kvq.page_scales(x)
    return kvq.quantize_pages(x, s), s


def _qdq(x, page_shape):
    """Quantize-dequantize roundtrip through the int8 page format.

    Views ``x`` in the kv_quant page layout [..., P, page, Hkv, D],
    roundtrips it to int8 codes and back, and returns the dequantized
    array in the original shape/dtype. Feeding the SAME roundtripped
    array to kernel and oracle checks that int8-representable inputs
    (exact multiples of the per-page scale) keep kernel parity — any
    divergence is a kernel bug, not a quantization artifact.
    """
    xr = x.reshape(page_shape)
    s = kvq.page_scales(xr)
    q = kvq.quantize_pages(xr, s)
    return kvq.dequantize_pages(q, s).astype(x.dtype).reshape(x.shape)


@settings(max_examples=10, deadline=None)
@given(shape=st.sampled_from(DECODE_SHAPES), lendraw=st.integers(0, 2 ** 16),
       seed=st.integers(0, 2 ** 16))
def test_paged_flash_decode_int8_parity(shape, lendraw, seed):
    """True int8 kernel path: the Pallas kernel dequantizes in-VMEM from
    int8 codes + per-(page, head) scales; the oracle dequantizes in fp32
    then runs the exact-softmax reference. Both see the same codes, so
    the tolerance is kernel-math tolerance, not quantization error."""
    B, H, Hkv, D, P, page = shape
    kv_len = 1 + lendraw % (P * page)
    q = jax.random.normal(_key(seed, 0), (B, 1, H, D), jnp.float32)
    kp = jax.random.normal(_key(seed, 1), (B, P, page, Hkv, D), jnp.float32)
    vp = jax.random.normal(_key(seed, 2), (B, P, page, Hkv, D), jnp.float32)
    kq, ks = _quantized_pages(kp)
    vq, vs = _quantized_pages(vp)
    out = decode(q, kq, vq, jnp.int32(kv_len), k_scale=ks, v_scale=vs)
    g = H // Hkv
    ref = paged_flash_decode_quant_ref(
        q.reshape(B, Hkv, g, D), jnp.moveaxis(kq, 3, 1),
        jnp.moveaxis(vq, 3, 1), jnp.moveaxis(ks, 2, 1),
        jnp.moveaxis(vs, 2, 1), kv_len)
    np.testing.assert_allclose(
        np.asarray(out, np.float32).reshape(B, Hkv, g, D),
        np.asarray(ref, np.float32), atol=3e-5, rtol=3e-5)


@settings(max_examples=8, deadline=None)
@given(shape=st.sampled_from(FLASH_SHAPES), causal=st.booleans(),
       seed=st.integers(0, 2 ** 16))
def test_flash_attention_int8_qdq_parity(shape, causal, seed):
    B, S, H, Hkv, D, qb, kb = shape
    q = jax.random.normal(_key(seed, 0), (B, S, H, D), jnp.float32)
    k = _qdq(jax.random.normal(_key(seed, 1), (B, S, Hkv, D), jnp.float32),
             (B, 1, S, Hkv, D))
    v = _qdq(jax.random.normal(_key(seed, 2), (B, S, Hkv, D), jnp.float32),
             (B, 1, S, Hkv, D))
    out = attention(q, k, v, causal=causal, q_block=qb, kv_block=kb)
    g = H // Hkv
    qr = jnp.moveaxis(q.reshape(B, S, Hkv, g, D), 1, 3)
    ref = flash_attention_ref(qr, jnp.moveaxis(k, 1, 2),
                              jnp.moveaxis(v, 1, 2), causal=causal)
    ref = jnp.moveaxis(ref, 3, 1).reshape(B, S, H, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


@settings(max_examples=8, deadline=None)
@given(shape=st.sampled_from(HDM_SHAPES), seed=st.integers(0, 2 ** 16))
def test_hdm_stream_matmul_int8_qdq_parity(shape, seed):
    M, K, N, page_k, n_pages, bm, bn = shape
    x = jax.random.normal(_key(seed, 0), (M, K), jnp.float32)
    wp = _qdq(jax.random.normal(_key(seed, 1), (n_pages, page_k, N),
                                jnp.float32),
              (n_pages, page_k, N, 1))
    rng = np.random.default_rng(seed)
    pids = jnp.asarray(rng.permutation(n_pages)[:K // page_k], jnp.int32)
    y = stream_matmul(x, wp, pids, block_m=bm, block_n=bn)
    ref = paged_matmul_ref(x, wp, pids)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


@settings(max_examples=8, deadline=None)
@given(shape=st.sampled_from(SSD_SHAPES), seed=st.integers(0, 2 ** 16))
def test_ssd_scan_int8_qdq_parity(shape, seed):
    B, S, H, P, N, chunk = shape
    xdt = _qdq(jax.random.normal(_key(seed, 0), (B, S, H, P)),
               (B, 1, S, H, P))
    bm = _qdq(jax.random.normal(_key(seed, 1), (B, S, N)) * 0.5,
              (B, 1, S, N, 1))
    cm = _qdq(jax.random.normal(_key(seed, 2), (B, S, N)) * 0.5,
              (B, 1, S, N, 1))
    la = -jnp.abs(jax.random.normal(_key(seed, 3), (B, S, H))) * 0.1
    y = ssd(xdt, bm, cm, la, chunk=chunk)
    c = S // chunk
    lac = jnp.moveaxis(jnp.cumsum(la.reshape(B, c, chunk, H), axis=2), 3, 1)
    ref = ssd_scan_ref(jnp.moveaxis(xdt.reshape(B, c, chunk, H, P), 3, 1),
                       bm.reshape(B, c, chunk, N),
                       cm.reshape(B, c, chunk, N), lac)
    ref = jnp.moveaxis(ref, 1, 3).reshape(B, S, H, P)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


@settings(max_examples=6, deadline=None)
@given(shape=st.sampled_from(DECODE_SHAPES), lendraw=st.integers(0, 2 ** 16),
       seed=st.integers(0, 2 ** 16))
def test_paged_decode_int8_fast_path_matches_shard_map(shape, lendraw, seed):
    """Quantized dispatch split: fast path and rank-masked shard_map body
    must agree on all five outputs — the attention result bitwise-close,
    the requantized int8 page buffers and the grown scales exactly (the
    monotone-scale requantization makes non-owner ranks' masked writes
    round-trip bit-exactly, so the combine cannot drift)."""
    from repro.launch.mesh import make_host_mesh

    B, H, Hkv, D, P, page = shape
    q = jax.random.normal(_key(seed, 0), (B, 1, H, D), jnp.float32)
    kp = jax.random.normal(_key(seed, 1), (B, P, page, Hkv, D), jnp.float32)
    vp = jax.random.normal(_key(seed, 2), (B, P, page, Hkv, D), jnp.float32)
    nk = jax.random.normal(_key(seed, 3), (B, 1, Hkv, D), jnp.float32)
    nv = jax.random.normal(_key(seed, 4), (B, 1, Hkv, D), jnp.float32)
    kq, ks = _quantized_pages(kp)
    vq, vs = _quantized_pages(vp)
    pos = jnp.asarray([(lendraw + 7 * i) % (P * page) for i in range(B)],
                      jnp.int32)
    with jax.set_mesh(make_host_mesh()):
        fast = _decode_layer(q, kq, vq, nk, nv, pos,
                              batch_axes="data", page_axes="model",
                              k_scale=ks, v_scale=vs)
        smap = _decode_layer(q, kq, vq, nk, nv, pos,
                              batch_axes="data", page_axes="model",
                              force_shard_map=True,
                              k_scale=ks, v_scale=vs)
    assert len(fast) == 5 and len(smap) == 5
    names = ("out", "k_pages", "v_pages", "k_scale", "v_scale")
    for a, b, name in zip(fast, smap, names):
        if a.dtype == jnp.int8:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)
        else:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-5, err_msg=name)


# ------------------------------------- real >1-rank mesh vs single rank

# shapes whose page axis divides a 2-rank model axis (P % 2 == 0) — the
# others fall back to unsharded pages under shard_map by design
MULTI_RANK_SHAPES = [s for s in DECODE_SHAPES if s[4] % 2 == 0]

_multirank = __import__("pytest").mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >= 2 devices (XLA_FLAGS="
           "--xla_force_host_platform_device_count=4)")


@_multirank
@settings(max_examples=6, deadline=None)
@given(shape=st.sampled_from(MULTI_RANK_SHAPES),
       lendraw=st.integers(0, 2 ** 16), seed=st.integers(0, 2 ** 16))
def test_paged_decode_two_rank_mesh_matches_single_rank_and_ref(
        shape, lendraw, seed):
    """The shard_map body on a REAL (1, 2) mesh — pages physically split
    over two model-axis ranks — must match both the single-rank fast
    path and the exact-softmax oracle on the updated pages. This is the
    sharded serving engine's decode tick, minus the engine."""
    from repro.launch.mesh import make_host_mesh, make_production_mesh

    B, H, Hkv, D, P, page = shape
    q = jax.random.normal(_key(seed, 0), (B, 1, H, D), jnp.float32)
    kp = jax.random.normal(_key(seed, 1), (B, P, page, Hkv, D), jnp.float32)
    vp = jax.random.normal(_key(seed, 2), (B, P, page, Hkv, D), jnp.float32)
    nk = jax.random.normal(_key(seed, 3), (B, 1, Hkv, D), jnp.float32)
    nv = jax.random.normal(_key(seed, 4), (B, 1, Hkv, D), jnp.float32)
    pos = jnp.asarray([(lendraw + 7 * i) % (P * page) for i in range(B)],
                      jnp.int32)
    with jax.set_mesh(make_production_mesh(shape=(1, 2))):
        two = _decode_layer(q, kp, vp, nk, nv, pos,
                             batch_axes="data", page_axes="model",
                             force_shard_map=True)
    with jax.set_mesh(make_host_mesh()):
        one = _decode_layer(q, kp, vp, nk, nv, pos,
                             batch_axes="data", page_axes="model")
    for a, b, name in zip(two, one, ("out", "k_pages", "v_pages")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5, err_msg=name)
    # oracle on the updated pages: new_k/new_v land at pos, attention
    # spans kv_len = pos + 1 (per-slot, so check slot by slot)
    out2, kp2, vp2 = (np.asarray(x) for x in two)
    g = H // Hkv
    for b in range(B):
        p_idx, s_idx = int(pos[b]) // page, int(pos[b]) % page
        np.testing.assert_allclose(kp2[b, p_idx, s_idx],
                                   np.asarray(nk)[b, 0], atol=1e-6)
        ref = paged_flash_decode_ref(
            jnp.asarray(q[b:b + 1]).reshape(1, Hkv, g, D),
            jnp.moveaxis(jnp.asarray(kp2[b:b + 1]), 3, 1),
            jnp.moveaxis(jnp.asarray(vp2[b:b + 1]), 3, 1),
            int(pos[b]) + 1)
        np.testing.assert_allclose(
            out2[b].reshape(Hkv, g, D), np.asarray(ref)[0],
            atol=1e-5, rtol=1e-5, err_msg=f"slot {b} vs oracle")


@_multirank
@settings(max_examples=6, deadline=None)
@given(shape=st.sampled_from(MULTI_RANK_SHAPES),
       lendraw=st.integers(0, 2 ** 16), seed=st.integers(0, 2 ** 16))
def test_paged_decode_int8_two_rank_mesh_matches_single_rank(
        shape, lendraw, seed):
    """Quantized 5-output path on a real (1, 2) mesh: per-page int8
    scales are sharded alongside the pages, and the sharded combine must
    reproduce the single-rank fast path bit-for-bit on the int8 buffers
    (monotone-scale requantization) and bitwise-close on the floats."""
    from repro.launch.mesh import make_host_mesh, make_production_mesh

    B, H, Hkv, D, P, page = shape
    q = jax.random.normal(_key(seed, 0), (B, 1, H, D), jnp.float32)
    kp = jax.random.normal(_key(seed, 1), (B, P, page, Hkv, D), jnp.float32)
    vp = jax.random.normal(_key(seed, 2), (B, P, page, Hkv, D), jnp.float32)
    nk = jax.random.normal(_key(seed, 3), (B, 1, Hkv, D), jnp.float32)
    nv = jax.random.normal(_key(seed, 4), (B, 1, Hkv, D), jnp.float32)
    kq, ks = _quantized_pages(kp)
    vq, vs = _quantized_pages(vp)
    pos = jnp.asarray([(lendraw + 7 * i) % (P * page) for i in range(B)],
                      jnp.int32)
    with jax.set_mesh(make_production_mesh(shape=(1, 2))):
        two = _decode_layer(q, kq, vq, nk, nv, pos,
                             batch_axes="data", page_axes="model",
                             force_shard_map=True,
                             k_scale=ks, v_scale=vs)
    with jax.set_mesh(make_host_mesh()):
        one = _decode_layer(q, kq, vq, nk, nv, pos,
                             batch_axes="data", page_axes="model",
                             k_scale=ks, v_scale=vs)
    assert len(two) == 5 and len(one) == 5
    names = ("out", "k_pages", "v_pages", "k_scale", "v_scale")
    for a, b, name in zip(two, one, names):
        if a.dtype == jnp.int8:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)
        else:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-5, err_msg=name)
