"""The whole harness on the CPU, from files that only add to the
benchmark: a throwaway configuration, two throwaway traffic mixes and a
throwaway per-layer metric, found by name from a copy of
``BENCHMARK.json`` with entries added. Then the same run with the timed
path broken underneath, once per fault a serving cell can have, each of
which must read ``correct: false``. The chip check is skipped here;
``main`` on a machine with no TPU prints no result and exits non-zero.
"""
import json
import pathlib
import shutil

import jax.numpy as jnp
import pytest

from bench import check, run, spec

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEED = 2 ** 31 + 77
SECONDS = 1.5

TINY = {"model": {"arch_id": "tiny", "family": "dense", "n_layers": 2,
                  "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
                  "head_dim": 32, "d_ff": 256, "vocab_size": 512,
                  "qk_norm": True, "rope_theta": 10000.0,
                  "activation": "swiglu", "tie_embeddings": True,
                  "norm_eps": 1e-6, "dtype": "bfloat16"},
        "serve": {"n_slots": 4, "max_seq": 512, "prefill_chunk": 32,
                  "store_budget_bytes": None, "temperature": 0.0},
        "reference": "dense",
        # the CPU program against float32 reads 0.0-0.02 here (bf16
        # logits of size 1-3), the fp8 control about 0.1, and every fault
        # below 0.2 or more
        "check": {"max_logit_gap": 0.05}}
MIX = {"prompt_len": {"64": 0.5, "128": 0.5},
       "output_len": {"4": 0.5, "8": 0.5}}
OPEN = dict(MIX, loop="open", rate_rps=12.0, prompts="catalog",
            catalog={"n_docs": 8, "zipf_s": 1.1, "prefill_store": 4},
            restores=True, why="throwaway")
CLOSED = dict(MIX, loop="closed", clients=6, prompts="unique",
              stratify_block=4, restores=False, why="throwaway")
METRIC = '''"""Decode calls the window made (a throwaway reader)."""


def read(record):
    return len(record["decode_calls"]) or None
'''


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout of the benchmark with throwaway entries added."""
    r = tmp_path_factory.mktemp("checkout")
    shutil.copytree(ROOT / "bench", r / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    (r / "bench/configs/tiny.json").write_text(json.dumps(TINY))
    (r / "bench/traffic/tiny-open.json").write_text(json.dumps(OPEN))
    (r / "bench/traffic/tiny-closed.json").write_text(json.dumps(CLOSED))
    (r / "bench/metrics/decode_calls.tiny.py").write_text(METRIC)
    b["configs"].append({"name": "tiny", "source": "throwaway",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "throwaway"})
    b["workloads"] += [
        {"name": "tiny.open", "config": "tiny", "traffic": "tiny-open",
         "chips": 1, "why": "throwaway"},
        {"name": "tiny.closed", "config": "tiny", "traffic": "tiny-closed",
         "chips": 1, "why": "throwaway"}]
    for m in b["end_to_end"] + b["per_layer"]:
        if "latency" in m["name"] or m["name"] == "prefix_hit_share":
            m["workloads"].append("tiny.open")
        if m["name"] == "tokens_per_s" or m["name"].endswith(".batch"):
            m["workloads"].append("tiny.closed")
    b["per_layer"].append({"name": "decode_calls.tiny", "unit": "calls",
                           "better": "higher", "source": "program_counter",
                           "layer": "scheduler", "moves": "tokens_per_s",
                           "workloads": ["tiny.closed"]})
    (r / "BENCHMARK.json").write_text(json.dumps(b))
    return r


def _run(root, cell, traced=False):
    return run.run_cell(root, cell, SEED, SECONDS, traced,
                        require_tpu=False, compile_cache=False,
                        log=lambda s: None)


def test_open_loop_cell_from_added_files(root):
    res = _run(root, "tiny.open")
    assert res["correct"], res["check"]
    bench = json.loads((root / "BENCHMARK.json").read_text())
    assert set(res["metrics"]) == {m["name"] for m in bench["end_to_end"]
                                   if "latency" in m["name"]} | {"setup_s"}
    assert res["attempted"] == 18 and res["failed"] == 0
    assert list(res)[-1] == "check"
    assert res["check"]["max_logit_gap"]["value"] < 0.05


def test_traced_closed_loop_reads_added_metric(root):
    res = _run(root, "tiny.closed", traced=True)
    assert res["correct"], res["check"]
    m = res["metrics"]
    assert m["decode_calls.tiny"]["value"] > 0
    assert 0 < m["batch_occupancy.batch"]["value"] <= 100
    # no TPU plane in a CPU trace and no peaks for a CPU: the device
    # metrics read nothing and are left out, never reported as 0
    for name in ("decode_step_ms.batch", "decode_roofline.batch",
                 "mfu.batch", "idle_share.batch"):
        assert name not in m


def test_control_reads_above_the_limit(root):
    """The control (the reference in fp8, in the program's place) reads
    ``correct: false`` through the harness's own comparison, on the
    sample on which the program passes."""
    rec = {}
    res = run.run_cell(root, "tiny.open", SEED + 1, SECONDS, False,
                       require_tpu=False, compile_cache=False, control=True,
                       record_out=rec, log=lambda s: None)
    limit = TINY["check"]["max_logit_gap"]
    assert not res["correct"], res["check"]
    assert res["check"]["max_logit_gap"]["value"] > limit
    assert all("chosen" not in p for p in rec["picks"])
    ref = spec.reference_module(root, TINY["reference"])
    prog = check.compare(ref, SEED + 1, TINY["model"], rec["picks"],
                         rec["done"], TINY["check"])
    assert check.passed(prog) and prog["max_logit_gap"]["value"] <= limit


def _broken_decode_keeps_state(self, params, cache, last_tokens, key):
    new_cache, nxt, key = _ORIG_DECODE(self, params, cache, last_tokens, key)
    return dict(new_cache, kv=cache["kv"]), nxt, key


def _broken_sampling(logits_row, key, temperature):
    return (jnp.argmax(logits_row, -1).astype(jnp.int32) + 1) % \
        logits_row.shape[-1]


def _broken_restore(self, req, slot, entry):
    _ORIG_RESTORE(self, req, slot, dict(entry, kv=jax_zeros_like(entry)))


def jax_zeros_like(entry):
    import jax
    import numpy as np
    return jax.tree_util.tree_map(np.zeros_like, entry["kv"])


_ORIG_DECODE = _ORIG_RESTORE = None


@pytest.mark.parametrize("fault", ["decode_keeps_state", "token_altered",
                                   "restore_loses_pages"])
def test_fault_reads_incorrect(root, monkeypatch, fault):
    global _ORIG_DECODE, _ORIG_RESTORE
    from repro.models import model as M
    from repro.serving.engine import ServingEngine
    _ORIG_DECODE = ServingEngine._decode_sample
    _ORIG_RESTORE = ServingEngine._apply_restore
    if fault == "decode_keeps_state":
        monkeypatch.setattr(ServingEngine, "_decode_sample",
                            _broken_decode_keeps_state)
    elif fault == "token_altered":
        monkeypatch.setattr(M, "sample_tokens", _broken_sampling)
    else:
        monkeypatch.setattr(ServingEngine, "_apply_restore", _broken_restore)
    res = _run(root, "tiny.open")
    assert not res["correct"], res["check"]
    limit = TINY["check"]["max_logit_gap"]
    assert res["check"]["max_logit_gap"]["value"] > limit


def test_sample_keeps_longest_restored_and_prefilled():
    done = [{"rid": i, "tokens": [1] * (4 + i % 3), "restored": i % 2 == 0,
             "prompt": (1,), "max_new": 4 + i % 3} for i in range(20)]
    picks = check.sample(done, 5, min_served=30)
    assert picks[0]["rid"] == 2 and len(picks[0]["tokens"]) == 6
    assert {p["restored"] for p in picks} == {True, False}
    assert sum(len(p["tokens"]) for p in picks) >= 30
    assert len({p["rid"] for p in picks}) == len(picks)
    assert check.sample([], 5) == []


def test_no_tpu_means_no_result(capsys):
    assert run.main(["--workload", "qwen3-1.7b.doc-reuse", "--seed", "1",
                     "--seconds", "1"]) == 2
    assert capsys.readouterr().out.strip() == ""
