"""The mixture-of-experts family at a small size on the CPU: its weights
against its costs, the program's held-share layer against the plain
float32 reference (``bench/reference/qwen3_moe.py``) through the engine's
own cache, the engine's routing counters against the reference's routing,
and a cell run through the harness from added files, where the fp8
control and three planted faults in the expert layer read
``correct: false``."""
import json
import pathlib
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run, spec
from bench.reference import qwen3_moe as R
from bench.tests.test_bench_harness import CLOSED, SECONDS, SEED, TINY

ROOT = pathlib.Path(__file__).resolve().parents[2]
FAM = spec.family_module(ROOT, "moe")

# 4 of 32 experts held, from the 5th: a share off the first index
SMALL = dict(TINY["model"], arch_id="small-moe", family="moe", n_layers=2,
             d_ff=64, n_experts=32, n_experts_held=4, first_expert=4,
             top_k=4, tie_embeddings=False)
# a bf16 program against float32 through 2 layers: a few roundings of
# 2^-8 relative; a routing lost or gated wrongly moves logits by the
# expert's part, tenths of their size
TOL = 3e-2
CELL = "tiny-moe.closed"
# the bf16 CPU program reads 0.025-0.059 here (5 seeds; its widest where
# a near-tie routing flips), the fp8 control 0.124-0.150 (3 seeds) and
# each fault below 0.112 or more (3 seeds each)
MOE = dict(TINY, reference="qwen3_moe", model=SMALL,
           check={"max_logit_gap": 0.09})


def _engine(m, dtype="bfloat16", **serve):
    from repro.configs.base import SHAPES, MeshConfig, RunConfig
    from repro.serving.engine import ServingEngine
    cfg = spec.model_config({"model": dict(m, dtype=dtype)})
    rc = RunConfig(model=cfg, shape=SHAPES["decode_32k"], mesh=MeshConfig())
    params = FAM.program_params(SEED, m)
    if dtype == "float32":
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                        params)
    return ServingEngine(params, cfg, rc, **(serve or dict(
        n_slots=1, max_seq=256, prefill_chunk=32)))


def _err(got, ref):
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


def test_family_tree_matches_its_costs():
    m = SMALL
    p = FAM.program_params(SEED, m)
    L, d, f, E, held = (m["n_layers"], m["d_model"], m["d_ff"],
                        m["n_experts"], m["n_experts_held"])
    moe = p["blocks"]["moe"]
    assert moe["router"].shape == (L, d, E) and \
        moe["router"].dtype == jnp.float32
    assert moe["e_gate"].shape == moe["e_up"].shape == (L, held, d, f)
    assert moe["e_down"].shape == (L, held, f, d)
    # every weight a decode call reads: all but the embedding table (a
    # gather) and the norm scales, with every held expert touched
    read = sum(a.size * a.dtype.itemsize for path, a in
               jax.tree_util.tree_leaves_with_path(p)
               if a.ndim > 2 or (a.ndim == 2 and "unembed" in str(path))
               or ("attn" in str(path) and a.ndim == 3
                   and "norm" not in str(path)))
    kvb = 2 * L * m["n_kv_heads"] * m["head_dim"] * 2
    _, nbytes = FAM.decode_call(m, [1], touched=held)
    assert nbytes == read + kvb + 2 * d
    # flops: every non-expert weight per token, and k * held / E experts
    attn = sum(p["blocks"]["attn"][k].size for k in ("wq", "wk", "wv", "wo"))
    per_tok = 2 * (attn + moe["router"].size + p["embed"]["unembed"].size
                   + moe["e_gate"][:, 0].size * 3 * m["top_k"] * held / E)
    ctx = 4 * L * m["n_heads"] * m["head_dim"]
    assert FAM.prefill_call(m, 0, 8) == pytest.approx(
        8 * per_tok + ctx * 36)
    assert FAM.decode_call(m, [5, 9])[0] == pytest.approx(
        2 * per_tok + ctx * 14)
    # a uniform router touches E_held (1 - (1 - k/E)^T) held experts
    assert FAM.expected_touched(m, 1) == pytest.approx(held * 4 / 32)


def test_share_holds_the_uncut_models_experts():
    whole = dict(SMALL, n_experts_held=0, first_expert=0)
    a, b = FAM.program_params(SEED, SMALL), FAM.program_params(SEED, whole)
    for name in ("e_gate", "e_up", "e_down"):
        np.testing.assert_array_equal(
            np.asarray(a["blocks"]["moe"][name]),
            np.asarray(b["blocks"]["moe"][name][:, 4:8]))
    for name in ("router", "wq"):
        sub = "moe" if name == "router" else "attn"
        np.testing.assert_array_equal(np.asarray(a["blocks"][sub][name]),
                                      np.asarray(b["blocks"][sub][name]))


@pytest.mark.parametrize("dtype,tol", [("bfloat16", TOL),
                                       ("float32", 1e-4)])
def test_reference_matches_cached_prefill_then_decode(mesh_ctx, dtype, tol):
    from repro.models import model as M
    eng = _engine(SMALL, dtype)
    cfg, rc = eng.cfg, eng.hot_rc
    toks = np.random.default_rng(1).integers(1, 512, (1, 72)).astype(np.int32)
    cache = M.cache_init(cfg, rc, 1, max_seq=256)
    for i in range(0, 64, 32):
        _, cache = M.prefill_step_cached(eng.params, cfg, rc,
                                         toks[:, i:i + 32], cache, eng.pspecs)
    ref = R.logits(SEED, SMALL, toks[0])
    for i in range(64, 72):
        logits, cache = M.decode_step(eng.params, cfg, rc, toks[:, i:i + 1],
                                      cache, eng.pspecs)
        got = np.asarray(logits, np.float32).reshape(-1)
        assert _err(got, ref[i]) < tol, i


def test_engine_counters_match_reference_routing(mesh_ctx):
    """One request served in float32 (where no routing can tie): every
    prompt and decoded position's routings that land on held experts,
    and each decode call's held experts, as the reference routes them."""
    from repro.serving.engine import Request
    eng = _engine(SMALL, "float32")
    prompt = [int(t) for t in np.random.default_rng(3).integers(1, 512, 40)]
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=6))
    out = eng.run(max_ticks=50)[0].generated
    routes = R.routing(SEED, SMALL, prompt + out[:-1]) - 4     # [L, S, k]
    held = (routes >= 0) & (routes < 4)
    st = eng.stats
    assert st["moe_decode_calls_counted"] == 5
    assert st["moe_local_routings"] == int(held.sum())
    # one live token a call: its held routings are the experts touched
    assert st["moe_expert_touches"] == int(held[:, 40:].sum())


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout of the benchmark with a small MoE cell added."""
    r = tmp_path_factory.mktemp("checkout")
    shutil.copytree(ROOT / "bench", r / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (r / "bench/configs/tiny-moe.json").write_text(json.dumps(MOE))
    (r / "bench/traffic/tiny-closed.json").write_text(json.dumps(CLOSED))
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny-moe", "source": "throwaway",
                         "file": "bench/configs/tiny-moe.json",
                         "reduced": [], "why": "throwaway"})
    b["workloads"].append({"name": CELL, "config": "tiny-moe",
                           "traffic": "tiny-closed", "chips": 1,
                           "why": "throwaway"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "qwen3-moe-235b-a22b-ep16.reason-batch" in m.get("workloads", []):
            m["workloads"].append(CELL)
    (r / "BENCHMARK.json").write_text(json.dumps(b))
    return r


def _run(root, seed=SEED, traced=False, control=False):
    return run.run_cell(root, CELL, seed, SECONDS, traced,
                        require_tpu=False, compile_cache=False,
                        control=control, log=lambda s: None)


def test_cell_runs_correct_and_reads_its_counters(root):
    res = _run(root, traced=True)
    assert res["correct"], res["check"]
    share = res["metrics"]["expert_touch_share.moe"]["value"]
    assert 0 < share <= 100
    # no TPU plane and no peaks on a CPU: the device metrics read nothing
    assert "decode_roofline.moe" not in res["metrics"]


def test_control_reads_above_the_limit(root):
    res = _run(root, SEED + 1, control=True)
    assert not res["correct"], res["check"]
    assert res["check"]["max_logit_gap"]["value"] > \
        MOE["check"]["max_logit_gap"]


def _off_by_one(orig):
    def held_part(xt, wr, wg, wu, wd, first, k):
        return orig(xt, wr, wg, wu, wd, first + 1, k)
    return held_part


def _capacity(params, cfg, x, dp_axes="data"):
    from repro.models import moe
    return moe.moe_apply(params, cfg, x)[0], jnp.zeros((2,), jnp.int32)


def _unrenormalised(router, xt, k):
    probs = jax.nn.softmax(xt.astype(jnp.float32) @ router, axis=-1)
    gate_w, gate_i = jax.lax.top_k(probs, k)
    return gate_w, gate_i, probs


@pytest.mark.parametrize("fault", ["held_range_off_by_one",
                                   "routings_dropped",
                                   "gates_unrenormalised"])
def test_fault_reads_incorrect(root, monkeypatch, fault):
    from repro.models import moe
    if fault == "held_range_off_by_one":
        monkeypatch.setattr(moe, "_held_part", _off_by_one(moe._held_part))
    elif fault == "routings_dropped":       # capacity 1.25 * t * k / e
        monkeypatch.setattr(moe, "moe_apply_held", _capacity)
    else:
        monkeypatch.setattr(moe, "route", _unrenormalised)
    res = _run(root, SEED + 2)
    assert not res["correct"], res["check"]
    assert res["check"]["max_logit_gap"]["value"] > \
        MOE["check"]["max_logit_gap"]


def test_reference_gaps_count_near_ties(monkeypatch):
    """Each served position's ``ties`` counts the layers whose routing
    edge (the k-th and next expert, one of them held) lies within
    ``TIE_MARGIN``: none at a margin of 0, every such edge at an
    infinite one."""
    toks = np.random.default_rng(4).integers(1, 512, 70)
    seqs = [(toks[:64], list(toks[64:70]), None)]
    counts = {}
    for margin in (0.0, R.TIE_MARGIN, np.inf):
        monkeypatch.setattr(R, "TIE_MARGIN", margin)
        R._program.cache_clear()
        counts[margin] = R.gaps(SEED, SMALL, seqs)[0]["ties"]
    R._program.cache_clear()
    assert (counts[0.0] == 0).all()
    assert (counts[R.TIE_MARGIN] <= counts[np.inf]).all()
    assert counts[np.inf].max() <= SMALL["n_layers"]
    assert counts[np.inf].sum() > 0
