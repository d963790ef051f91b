"""Property tests for the int8 KV page format (``repro.models.kv_quant``).

Pins the three contracts the quantized tier path rests on:

 * per-page roundtrip error is bounded by half a quantization step
   (0.5 * scale) for every drawn shape/magnitude, including pages of
   zeros and subnormals (the amax floor keeps scales normal fp32);
 * monotone scale growth makes dequantize -> requantize of an untouched
   page *bit*-stable — the property the tier flush -> restore -> decode
   round trip relies on;
 * the serving engine's flush -> restore -> decode path preserves the
   int8 payload byte-exactly and charges quantized (roughly halved)
   byte counts end-to-end.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import registry
from repro.configs.base import MeshConfig, RunConfig, SHAPES
from repro.models import kv_quant as kvq
from repro.models import model as M
from repro.serving.engine import Request, ServingEngine

# (..., n_pages, page, Hkv, D) page layouts, tiny so draws stay fast
PAGE_SHAPES = [
    (2, 8, 2, 16),
    (1, 3, 4, 16, 4),
    (2, 2, 2, 4, 2, 8),
]
# page magnitudes spanning tiny to huge (scale must track amax per page)
MAGNITUDES = [1e-12, 1e-3, 1.0, 1e4, 1e12]


def _key(seed, i=0):
    return jax.random.fold_in(jax.random.PRNGKey(seed), i)


def _draw(shape, seed, magnitude):
    return jax.random.normal(_key(seed), shape, jnp.float32) * magnitude


# ----------------------------------------------------- roundtrip bound

@settings(max_examples=25, deadline=None)
@given(shape=st.sampled_from(PAGE_SHAPES),
       magnitude=st.sampled_from(MAGNITUDES),
       seed=st.integers(0, 2 ** 16))
def test_roundtrip_error_bounded_per_page(shape, magnitude, seed):
    """|x - dequantize(quantize(x))| <= 0.5 * scale elementwise: codes are
    round-to-nearest on a symmetric grid whose step is the page's scale,
    and scale = amax/127 means no value is ever out of clip range."""
    x = _draw(shape, seed, magnitude)
    s = kvq.page_scales(x)
    q = kvq.quantize_pages(x, s)
    dq = kvq.dequantize_pages(q, s)
    err = np.abs(np.asarray(x, np.float64) - np.asarray(dq, np.float64))
    bound = 0.5 * np.asarray(s, np.float64)[..., :, None, :, None]
    assert (err <= bound * (1 + 1e-5)).all()
    assert np.asarray(q).dtype == np.int8
    assert np.abs(np.asarray(q)).max() <= kvq.QMAX


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2 ** 16))
def test_mixed_magnitude_pages_scale_independently(seed):
    """A huge page must not inflate a tiny page's quantization step: the
    error on each page is bounded by that page's own scale."""
    tiny = _draw((1, 8, 2, 16), seed, 1e-6)
    huge = _draw((1, 8, 2, 16), seed + 1, 1e6)
    x = jnp.concatenate([tiny, huge], axis=0)        # pages axis
    s = kvq.page_scales(x)
    dq = kvq.dequantize_pages(kvq.quantize_pages(x, s), s)
    err_tiny = np.abs(np.asarray(tiny) - np.asarray(dq[:1]))
    assert err_tiny.max() <= 0.5 * float(np.asarray(s)[0].max()) * (1 + 1e-5)
    assert float(np.asarray(s)[0].max()) < 1e-5      # not polluted by huge


# ----------------------------------------- zero / subnormal edge cases

def test_zero_page_scale_is_normal_and_codes_zero():
    x = jnp.zeros((2, 8, 2, 16), jnp.float32)
    s = kvq.page_scales(x)
    tiny_normal = np.finfo(np.float32).tiny          # smallest NORMAL f32
    assert (np.asarray(s) >= tiny_normal).all()      # never zero/subnormal
    q = kvq.quantize_pages(x, s)
    assert not np.asarray(q).any()
    assert not np.asarray(kvq.dequantize_pages(q, s)).any()


def test_subnormal_page_quantizes_to_zero_with_normal_scale():
    """A page of subnormals sits far below the amax floor: the scale
    stays a normal fp32 (no division blow-ups) and every code rounds
    to 0 — the reconstruction error is the (subnormal) input itself."""
    x = jnp.full((1, 8, 2, 16), 1e-40, jnp.float32)
    s = kvq.page_scales(x)
    assert (np.asarray(s) >= np.finfo(np.float32).tiny).all()
    assert np.isfinite(np.asarray(1.0 / s)).all()
    q = kvq.quantize_pages(x, s)
    assert not np.asarray(q).any()


def test_init_scale_is_positive_and_normal():
    assert kvq.INIT_SCALE > 0
    assert np.float32(kvq.INIT_SCALE) >= np.finfo(np.float32).tiny


# ------------------------------------------- monotone-scale bit stability

@settings(max_examples=25, deadline=None)
@given(shape=st.sampled_from(PAGE_SHAPES),
       magnitude=st.sampled_from(MAGNITUDES),
       seed=st.integers(0, 2 ** 16))
def test_requantize_untouched_page_bit_stable(shape, magnitude, seed):
    """dequantize -> requantize(prev_scale) of an unchanged page must
    reproduce the identical codes AND scales: this is what keeps tier
    flush -> restore -> decode round trips byte-exact."""
    x = _draw(shape, seed, magnitude)
    s = kvq.page_scales(x)
    q = kvq.quantize_pages(x, s)
    dq = kvq.dequantize_pages(q, s)
    q2, s2 = kvq.requantize_pages(dq, s)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s2))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2 ** 16))
def test_scale_growth_is_monotone(seed):
    """Scales only grow: shrinking page contents keeps the old scale
    (bit-stability dominates), growing contents raises it to the new
    amax/127 so nothing clips."""
    x = _draw((2, 8, 2, 16), seed, 1.0)
    s0 = kvq.page_scales(x)
    _, s_small = kvq.requantize_pages(x * 0.01, s0)
    np.testing.assert_array_equal(np.asarray(s_small), np.asarray(s0))
    q_big, s_big = kvq.requantize_pages(x * 100.0, s0)
    assert (np.asarray(s_big) >= np.asarray(s0)).all()
    assert np.abs(np.asarray(q_big)).max() <= kvq.QMAX   # no clip overflow


# --------------------------------------------------------- mode validation

def test_validate_mode_spellings():
    assert kvq.validate_mode("none") == "none"
    assert kvq.validate_mode("int8") == "int8"
    with pytest.raises(ValueError, match="unknown"):
        kvq.validate_mode("int4")
    with pytest.raises(ValueError, match="reserved"):
        kvq.validate_mode("fp8")


# ------------------------- engine flush -> restore -> decode byte-exactness

PROMPT = [1, 2, 3, 7, 9, 4, 2, 8, 1, 5, 6]


def _make(kv_quant, page_size=8):
    """Smoke engine with small KV pages so the cache spans several pages
    (page geometry: page=8, n_pages=4 at max_seq=32)."""
    cfg = registry.smoke("qwen3-1.7b")
    rc = dataclasses.replace(
        RunConfig(model=cfg, shape=SHAPES["decode_32k"], mesh=MeshConfig()),
        kv_page_size=page_size)
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    return ServingEngine(params, cfg, rc, n_slots=1, max_seq=32,
                         prefill_chunk=4, kv_quant=kv_quant)


def test_tier_flush_restore_decode_byte_exact(mesh_ctx):
    """Serve -> retire -> flush -> resubmit -> restore -> decode with the
    int8 cache: the restored continuation reproduces the original greedy
    tokens, and the int8 codes + scales of every fully-prefix page come
    back byte-identical after further decode steps (monotone scales)."""
    eng = _make("int8")
    assert eng.cache["kv"]["k"].dtype == jnp.int8
    assert "k_scale" in eng.cache["kv"]
    eng.submit(Request(rid=42, prompt=PROMPT, max_new_tokens=4))
    done = eng.run(max_ticks=100)
    original = done[0].generated
    for _ in range(10):
        if 42 in eng.store.pages:
            break
        eng.flusher.maybe_flush()
    assert 42 in eng.store.pages
    entry = eng.store.pages[42]
    assert entry["kv"]["k"].dtype == np.int8
    assert entry["kv"]["k_scale"].dtype == np.float32

    pf = eng.stats["prefill_dispatches"]
    eng.submit(Request(rid=42, prompt=PROMPT, max_new_tokens=2))
    done = eng.run(max_ticks=100)
    assert done[-1].restored
    assert done[-1].generated == original[:2]
    assert eng.stats["prefill_dispatches"] == pf   # no re-prefill

    # stored entry covered pos=len(PROMPT)=11 -> page 0 (tokens 0..7) is
    # full and untouched by the 2 extra decode steps (tokens 11, 12 land
    # on page 1); its codes and scales must round-trip byte-exactly
    page = 8
    full = len(PROMPT) // page                     # fully-written pages
    assert full >= 1
    cache_k = np.asarray(eng.cache["kv"]["k"])[:, 0, :full]
    np.testing.assert_array_equal(cache_k, entry["kv"]["k"][:, :full])
    cache_ks = np.asarray(eng.cache["kv"]["k_scale"])[:, 0, :full]
    np.testing.assert_array_equal(cache_ks, entry["kv"]["k_scale"][:, :full])


def test_quantized_store_entry_bytes_roughly_halved(mesh_ctx):
    """The host store (and therefore every tier charge, which uses the
    same leaf nbytes) sees the quantized payload: entry bytes shrink by
    ~the dtype itemsize ratio, plus the small per-page scale overhead."""
    sizes = {}
    for mode in ("none", "int8"):
        eng = _make(mode)
        eng.submit(Request(rid=1, prompt=PROMPT, max_new_tokens=2))
        eng.run(max_ticks=100)
        for _ in range(10):
            if 1 in eng.store.pages:
                break
            eng.flusher.maybe_flush()
        sizes[mode] = eng.store._entry_bytes(eng.store.pages[1])
        if mode == "none":
            itemsize = np.asarray(eng.cache["kv"]["k"]).dtype.itemsize
    ratio = sizes["int8"] / sizes["none"]
    assert ratio < 1.0 / itemsize + 0.05
