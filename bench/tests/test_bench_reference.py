"""The plain float32 reference against the program's own model code
(``repro.models``) at a smoke size: the no-cache prefill, and chunked
cache-writing prefill followed by decode through the paged cache, on the
benchmark's seeded weights. The program runs in bf16, so the two agree
to bf16 rounding of logits, not exactly."""
import numpy as np
import pytest

from bench import spec, weights
from bench.reference import dense

SMALL = {"arch_id": "small", "family": "dense", "n_layers": 3,
         "d_model": 128, "n_heads": 4, "n_kv_heads": 2, "head_dim": 32,
         "d_ff": 256, "vocab_size": 512, "qk_norm": True,
         "rope_theta": 10000.0, "activation": "swiglu",
         "tie_embeddings": True, "norm_eps": 1e-6, "dtype": "bfloat16"}
# bf16 weights and activations through 3 layers against float32: a few
# roundings of 2^-8 relative; a misplaced cache entry or a missing norm
# moves logits by their own size
TOL = 3e-2
SEED = 2 ** 32 + 9


def _engine(m):
    from repro.configs.base import SHAPES, MeshConfig, RunConfig
    from repro.serving.engine import ServingEngine
    cfg = spec.model_config({"model": m})
    rc = RunConfig(model=cfg, shape=SHAPES["decode_32k"], mesh=MeshConfig())
    return ServingEngine(weights.program_params(SEED, m), cfg, rc,
                         n_slots=1, max_seq=256, prefill_chunk=32)


def _err(got, ref):
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


@pytest.mark.parametrize("tied", [True, False])
def test_reference_matches_no_cache_prefill(mesh_ctx, tied):
    from repro.models import model as M
    m = dict(SMALL, tie_embeddings=tied, qk_norm=tied)
    eng = _engine(m)
    toks = np.random.default_rng(0).integers(1, 512, (1, 96)).astype(np.int32)
    got = M.prefill_step(eng.params, eng.cfg, eng.hot_rc, {"tokens": toks},
                         eng.pspecs)
    ref = dense.logits(SEED, m, toks[0])
    assert _err(np.asarray(got, np.float32).reshape(-1), ref[-1]) < TOL


def test_reference_matches_cached_prefill_then_decode(mesh_ctx):
    from repro.models import model as M
    eng = _engine(SMALL)
    cfg, rc = eng.cfg, eng.hot_rc
    toks = np.random.default_rng(1).integers(1, 512, (1, 72)).astype(np.int32)
    cache = M.cache_init(cfg, rc, 1, max_seq=256)
    for i in range(0, 64, 32):
        _, cache = M.prefill_step_cached(eng.params, cfg, rc,
                                         toks[:, i:i + 32], cache, eng.pspecs)
    ref = dense.logits(SEED, SMALL, toks[0])
    for i in range(64, 72):
        logits, cache = M.decode_step(eng.params, cfg, rc, toks[:, i:i + 1],
                                      cache, eng.pspecs)
        got = np.asarray(logits, np.float32).reshape(-1)
        assert _err(got, ref[i]) < TOL, i


def test_gaps_of_served_tokens():
    """Served token j is read at the position that predicts it, with the
    served tokens before it as context."""
    toks = np.random.default_rng(2).integers(1, 512, 70)
    ref = dense.logits(SEED, SMALL, toks)[63:69]
    out = dense.gaps(SEED, SMALL, [(toks[:64], list(toks[64:70]), None)])[0]
    want = ref.max(-1) - ref[np.arange(6), toks[64:70]]
    np.testing.assert_allclose(out["gaps"], want, atol=1e-4)
    np.testing.assert_array_equal(out["argmax"], ref.argmax(-1))
    assert want.max() > 0.5
