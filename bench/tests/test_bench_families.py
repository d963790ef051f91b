"""Model families from added files only. The dense family's module gives
the weights and costs of ``bench/weights.py`` and ``bench/costs.py``
unchanged. A throwaway mixture-of-experts family (its module under
``bench/families/``, its float32 reference and its configuration, all
written into a copy of the benchmark) runs a traced cell through
``run_cell`` to ``correct: true``, with its costs in the readers, every
engine counter in the record and the engine's span log kept. A family
with no module stops set-up, naming the missing file.
"""
import json
import pathlib
import shutil

import jax
import numpy as np
import pytest

from bench import costs, readers, run, spec, weights
from bench.tests.test_bench_harness import OPEN, SECONDS, SEED, TINY

ROOT = pathlib.Path(__file__).resolve().parents[2]

# every token goes to every expert (top_k = n_experts) and the capacity
# holds every token, so no routing tie or drop can part the bf16 program
# from the float32 reference; the gates still weigh the experts apart
MOE = dict(TINY, reference="tiny_moe",
           model=dict(TINY["model"], arch_id="tiny-moe", family="moe",
                      n_experts=4, top_k=4, capacity_factor=1.0))
HYBRID = dict(TINY, model=dict(TINY["model"], arch_id="tiny-hybrid",
                               family="hybrid"))

FAMILY = '''"""A throwaway mixture-of-experts family: the dense family's attention
and tables, and in each layer a float32 router over SwiGLU experts in
place of the MLP."""
import math

import jax
import jax.numpy as jnp

from bench import costs, weights as W

EXPERTS = ("router", "e_gate", "e_up", "e_down")


def layer_leaves(key, m, layer, dtype=jnp.float32):
    leaves = {k: v for k, v in W.layer_leaves(key, m, layer, dtype).items()
              if k not in ("w_gate", "w_up", "w_down")}
    d, f, e = m["d_model"], m["d_ff"], m["n_experts"]
    shapes = {"router": (d, e), "e_gate": (e, d, f), "e_up": (e, d, f),
              "e_down": (e, f, d)}
    lk = jax.random.fold_in(jax.random.fold_in(key, layer), 1000)
    for i, name in enumerate(EXPERTS):
        shape = shapes[name]
        half = math.sqrt(3.0 / shape[-2]) * (4.0 if name == "router" else 1.0)
        x = jax.random.uniform(jax.random.fold_in(lk, i), shape, jnp.float32,
                               -half, half)
        leaves[name] = x.astype(jnp.bfloat16).astype(dtype)
    return leaves


def program_params(seed, m):
    key = W.key_for(seed)

    def make(key):
        def layer(i):
            lv = layer_leaves(key, m, i)
            return {k: v if k == "router" else v.astype(jnp.bfloat16)
                    for k, v in lv.items()}

        lv = jax.lax.map(layer, jnp.arange(m["n_layers"]))
        blocks = {"ln_attn": {"scale": lv["ln_attn"]},
                  "attn": {k: lv[k] for k in
                           ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
                           if k in lv},
                  "ln_mlp": {"scale": lv["ln_mlp"]},
                  "moe": {k: lv[k] for k in EXPERTS}}
        embed = {"embedding": W.table(key, m, "embedding", jnp.bfloat16)}
        return {"embed": embed,
                "ln_f": {"scale": W.table(key, m, "ln_f", jnp.bfloat16)},
                "blocks": blocks}

    return jax.jit(make)(key)


def _params(m):
    d, f = m["d_model"], m["d_ff"]
    q, kv = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    return d * (q + 2 * kv) + q * d, d * m["n_experts"], 3 * d * f


def _active(m):
    attn, router, expert = _params(m)
    return (m["n_layers"] * (attn + router + m["top_k"] * expert)
            + m["d_model"] * m["vocab_size"])


def _attn(m, context):
    return 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * context


def decode_call(m, live):
    live = list(live)
    attn, router, expert = _params(m)
    read = min(m["n_experts"], m["top_k"] * len(live))
    weights = (m["n_layers"] * (attn + read * expert)
               + m["d_model"] * m["vocab_size"]) * 2 \\
        + m["n_layers"] * router * 4
    kvb = costs.kv_bytes_per_token(m)
    flops = 2 * _active(m) * len(live) + sum(_attn(m, c) for c in live)
    nbytes = weights + sum(c - 1 for c in live) * kvb \\
        + len(live) * (kvb + m["d_model"] * 2)
    return flops, nbytes


def prefill_call(m, pos0, n):
    return 2 * _active(m) * n + _attn(m, n * pos0 + n * (n + 1) // 2)
'''

REFERENCE = '''"""Plain float32 reference of the throwaway mixture-of-experts family:
whole sequences at test sizes, its leaves remade through the family's
module."""
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec, weights as W
from bench.reference import dense as D

FAMILY = spec.family_module(pathlib.Path(__file__).resolve().parents[2],
                            "moe")


def _block(x, w, m):
    eps, hd, s = m["norm_eps"], m["head_dim"], x.shape[0]
    h = D._rmsnorm(x, w["ln_attn"], eps)
    q = (h @ w["wq"]).reshape(s, m["n_heads"], hd)
    k = (h @ w["wk"]).reshape(s, m["n_kv_heads"], hd)
    v = (h @ w["wv"]).reshape(s, m["n_kv_heads"], hd)
    q, k = D._rmsnorm(q, w["q_norm"], eps), D._rmsnorm(k, w["k_norm"], eps)
    q, k = D._rope(q, m["rope_theta"]), D._rope(k, m["rope_theta"])
    x = x + D._attention(q, k, v, False).reshape(s, -1) @ w["wo"]
    h = D._rmsnorm(x, w["ln_mlp"], eps)
    p = jax.nn.softmax(h @ w["router"], -1)
    top, idx = jax.lax.top_k(p, m["top_k"])
    gate = jnp.zeros_like(p).at[jnp.arange(s)[:, None], idx].set(
        top / top.sum(-1, keepdims=True))
    a = jax.nn.silu(jnp.einsum("sd,edf->esf", h, w["e_gate"])) \\
        * jnp.einsum("sd,edf->esf", h, w["e_up"])
    y = jnp.einsum("esf,efd->esd", a, w["e_down"])
    return x + jnp.einsum("se,esd->sd", gate, y)


def gaps(seed, m, seqs, precision="f32"):
    if precision != "f32":
        raise ValueError("the throwaway family has no control")
    key = W.key_for(seed)

    @jax.jit
    def run(tokens):
        with jax.default_matmul_precision("highest"):
            emb = W.table(key, m, "embedding")
            x = emb[tokens]
            for i in range(m["n_layers"]):
                x = _block(x, FAMILY.layer_leaves(key, m, i), m)
            return D._rmsnorm(x, W.table(key, m, "ln_f"),
                              m["norm_eps"]) @ emb.T

    out = []
    for prompt, served, targets in seqs:
        toks = list(prompt) + list(served)[:-1]
        lg = np.asarray(run(jnp.asarray(toks, jnp.int32)))
        lg = lg[len(prompt) - 1:]
        tg = np.asarray(served if targets is None else targets)
        out.append({"gaps": lg.max(-1) - lg[np.arange(len(tg)), tg],
                    "argmax": lg.argmax(-1)})
    return out
'''

COUNTER = '''"""Flushes whose copy to the host returned in flight (a throwaway
reader of a counter outside the first seven)."""


def read(record):
    return record["counters"]["flush_async"] or None
'''
SPAN_METRICS = ("queue_wait_ms.serve", "flush_ms.serve", "restore_ms.serve",
                "flush_idle_share.serve")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout of the benchmark with two families' entries added: a
    mixture of experts with its files, and a hybrid with none."""
    r = tmp_path_factory.mktemp("checkout")
    shutil.copytree(ROOT / "bench", r / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (r / "bench/families/moe.py").write_text(FAMILY)
    (r / "bench/reference/tiny_moe.py").write_text(REFERENCE)
    (r / "bench/configs/tiny-moe.json").write_text(json.dumps(MOE))
    (r / "bench/configs/tiny-hybrid.json").write_text(json.dumps(HYBRID))
    (r / "bench/traffic/tiny-open.json").write_text(json.dumps(OPEN))
    (r / "bench/metrics/flush_async.tiny.py").write_text(COUNTER)
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    for name in ("tiny-moe", "tiny-hybrid"):
        b["configs"].append({"name": name, "source": "throwaway",
                             "file": f"bench/configs/{name}.json",
                             "reduced": [], "why": "throwaway"})
        b["workloads"].append({"name": f"{name}.open", "config": name,
                               "traffic": "tiny-open", "chips": 1,
                               "why": "throwaway"})
    for m in b["end_to_end"] + b["per_layer"]:
        if m["name"] == "latency_p50_ms" or m["name"] in SPAN_METRICS:
            m["workloads"].append("tiny-moe.open")
    b["per_layer"].append({"name": "flush_async.tiny", "unit": "flushes",
                           "better": "higher", "source": "program_counter",
                           "layer": "host page store",
                           "moves": "latency_p50_ms",
                           "workloads": ["tiny-moe.open"]})
    (r / "BENCHMARK.json").write_text(json.dumps(b))
    return r


@pytest.fixture(scope="module")
def moe_run(root):
    """One traced run of the throwaway family's cell, with its record."""
    rec = {}
    res = run.run_cell(root, "tiny-moe.open", SEED, SECONDS, True,
                       require_tpu=False, compile_cache=False,
                       record_out=rec, log=lambda s: None)
    return res, rec


def test_dense_family_is_the_dense_weights_and_costs():
    dense = spec.family_module(ROOT, "dense")
    m = TINY["model"]
    got = jax.tree_util.tree_leaves_with_path(dense.program_params(SEED, m))
    want = jax.tree_util.tree_leaves_with_path(
        weights.program_params(SEED, m))
    assert [p for p, _ in got] == [p for p, _ in want]
    for (path, a), (_, b) in zip(got, want):
        assert a.dtype == b.dtype, path
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for name in ("qwen3-1.7b", "glm4-9b-20l"):
        big = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
        for cfg in (m, big["model"]):
            for live in ([1], [5, 4096, 17], list(range(1, 9))):
                assert dense.decode_call(cfg, live) == \
                    costs.decode_call(cfg, live)
            for pos0, n in ((0, 128), (1920, 128), (0, 1)):
                assert dense.prefill_call(cfg, pos0, n) == \
                    costs.prefill_call(cfg, pos0, n)


def test_added_family_runs_correct(moe_run):
    res, rec = moe_run
    assert res["correct"], res["check"]
    assert res["check"]["max_logit_gap"]["value"] < \
        MOE["check"]["max_logit_gap"]
    assert res["failed"] == 0 and rec["requests"]["completed"] > 0
    assert rec["counters"]["prefix_hits"] > 0       # restores ran too


def test_added_family_costs_come_from_its_module(root, moe_run):
    _, rec = moe_run
    fam = spec.family_module(root, "moe")
    m = rec["model"]
    assert readers.family(rec).__file__ == fam.__file__
    want = sum(fam.decode_call(m, live)[0] for _, live in rec["decode_calls"])
    want += sum(fam.prefill_call(m, p0, n) for _, p0, n in
                rec["prefill_calls"])
    assert want > 0 and readers.window_flops(rec) == want
    dense = sum(costs.decode_call(m, live)[0]
                for _, live in rec["decode_calls"])
    assert dense != sum(fam.decode_call(m, live)[0]
                        for _, live in rec["decode_calls"])
    peaks = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
    assert readers.mfu(dict(rec, peaks=peaks)) == pytest.approx(
        100 * want / (rec["window_s"] * 1e12))


def test_record_holds_every_engine_counter(moe_run):
    res, rec = moe_run
    from repro.serving.stats import EngineStats
    numeric = {f for f, v in EngineStats().items()
               if isinstance(v, (int, float))}
    assert set(rec["counters"]) == numeric
    n = rec["counters"]["flush_async"]
    assert n > 0 and res["metrics"]["flush_async.tiny"]["value"] == n


def test_traced_run_keeps_the_span_log(moe_run):
    res, rec = moe_run
    red = rec["serve_spans"]
    by = red["by_name"]
    for name in ("serve.queue", "serve.step", "serve.prefill",
                 "serve.restore", "serve.decode", "serve.flush"):
        assert by[name]["n"] > 0, name
    assert by["serve.restore"]["n"] == rec["counters"]["prefix_hits"]
    # no device plane in a CPU trace: the part read against the device
    # reads nothing and is left out, never reported as 0
    assert red["trace"] is None
    m = res["metrics"]
    assert "flush_idle_share.serve" not in m
    for name in ("queue_wait_ms.serve", "flush_ms.serve",
                 "restore_ms.serve"):
        assert m[name]["value"] > 0, name
    assert m["restore_ms.serve"]["value"] == pytest.approx(
        1e3 * by["serve.restore"]["seconds"] / by["serve.restore"]["n"])


def test_untraced_run_leaves_the_span_log_off(root):
    rec = {}
    res = run.run_cell(root, "tiny-moe.open", SEED + 2, SECONDS, False,
                       require_tpu=False, compile_cache=False,
                       record_out=rec, log=lambda s: None)
    assert res["correct"], res["check"]
    assert rec["serve_spans"] is None and rec["trace"] is None
    assert set(res["metrics"]) == {"latency_p50_ms", "setup_s"}


def test_family_without_module_fails_at_setup(root):
    with pytest.raises(FileNotFoundError,
                       match=r"bench/families/hybrid\.py is missing"):
        run.run_cell(root, "tiny-hybrid.open", SEED, SECONDS, False,
                     require_tpu=False, compile_cache=False,
                     log=lambda s: None)
