"""Every metric file under ``bench/metrics`` on run records recorded on a
TPU v5e chip (``data/record-<cell>-<trace>.json``, one per cell and
kind of run), against the arithmetic written out again here."""
import json
import math
import pathlib

import numpy as np
import pytest

from bench import costs, spec

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).parent / "data"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
RECORDS = sorted(DATA.glob("record-*.json"))


def _load(path):
    return json.loads(path.read_text())


def _metrics():
    return [spec.load_cell(w["name"]).metrics
            for w in BENCH["workloads"]][0]


@pytest.mark.parametrize("metric", _metrics(), ids=lambda m: m.name)
def test_every_metric_has_a_reader(metric):
    assert callable(spec.metric_reader(ROOT, metric.name))


@pytest.mark.parametrize("path", RECORDS, ids=lambda p: p.stem)
def test_readers_on_recorded_runs(path):
    rec = _load(path)
    cell = spec.load_cell(rec["cell"])
    traced = rec["trace"] is not None
    for m in cell.reported("per_layer" if traced else "end_to_end"):
        v = spec.metric_reader(ROOT, m.name)(rec)
        assert v is not None and math.isfinite(v), m.name
        if m.unit == "%":
            assert 0.0 <= v <= 100.0, (m.name, v)
        assert v > 0 or m.name.startswith("idle_share"), (m.name, v)


def _one(loop, traced):
    for p in RECORDS:
        r = _load(p)
        if r["loop"] == loop and (r["trace"] is not None) == traced:
            return r
    pytest.skip(f"no recorded {loop} record with trace={traced}")


def _read(name, rec):
    return spec.metric_reader(ROOT, name)(rec)


def test_open_loop_end_to_end_arithmetic():
    r = _one("open", False)
    lat = np.asarray(r["latencies_s"]) * 1e3
    assert _read("latency_p50_ms", r) == pytest.approx(np.median(lat))
    assert _read("tokens_per_s", r) is None
    assert _read("setup_s", r) == r["setup_s"]


def test_closed_loop_arithmetic():
    r = _one("closed", True)
    assert _read("latency_p50_ms", r) is None
    t = r["trace"]
    assert _read("idle_share.batch", r) == pytest.approx(
        100 * (1 - t["busy_s"] / t["window_s"]))
    live = [len(v) for _, v in r["decode_calls"]]
    assert _read("batch_occupancy.batch", r) == pytest.approx(
        100 * sum(live) / (len(live) * r["n_slots"]))
    prog = {k: v for k, v in t["programs"].items() if "_decode_sample" in k}
    calls = sum(v["calls"] for v in prog.values())
    secs = sum(v["seconds"] for v in prog.values())
    assert _read("decode_step_ms.batch", r) == pytest.approx(
        1e3 * secs / calls)
    m, peaks = r["model"], r["peaks"]
    flops = sum(costs.decode_call(m, v)[0] for _, v in r["decode_calls"])
    flops += sum(costs.prefill_call(m, p0, n)
                 for _, p0, n in r["prefill_calls"])
    assert _read("mfu.batch", r) == pytest.approx(
        100 * flops / (r["window_s"] * peaks["bf16_flops"]))


def test_open_loop_store_and_hits():
    r = _one("open", True)
    c, q = r["counters"], r["requests"]
    assert _read("store_mib_per_req.serve", r) == pytest.approx(
        r["entry_bytes"] * (c["flushes"] + c["prefix_hits"])
        / q["completed"] / 2 ** 20)
    if r["cell"].endswith("doc-reuse"):
        assert _read("prefix_hit_share", r) == pytest.approx(
            100 * c["prefix_hits"] / q["admitted"])


def test_prefill_chunk_arithmetic():
    r = _load(DATA / "record-qwen3-1.7b.chat-unique-1.json")
    prog = {k: v for k, v in r["trace"]["programs"].items()
            if "_prefill_chunk_body" in k}
    calls = sum(v["calls"] for v in prog.values())
    secs = sum(v["seconds"] for v in prog.values())
    assert calls > 0
    assert _read("prefill_chunk_ms.serve", r) == pytest.approx(
        1e3 * secs / calls)
    assert _read("prefix_hit_share", r) == 0.0


@pytest.mark.parametrize("cell", ["qwen3-1.7b.doc-reuse",
                                  "qwen3-1.7b.chat-unique"])
def test_span_metrics_arithmetic(cell):
    r = _load(DATA / f"record-{cell}-1.json")
    by, t = r["serve_spans"]["by_name"], r["serve_spans"]["trace"]
    for metric, span in (("queue_wait_ms.serve", "serve.queue"),
                         ("flush_ms.serve", "serve.flush"),
                         ("restore_ms.serve", "serve.restore")):
        want = 1e3 * by[span]["seconds"] / by[span]["n"] \
            if span in by else None
        assert _read(metric, r) == (pytest.approx(want) if want else None)
    assert by["serve.flush"]["n"] == r["counters"]["flushes"]
    assert by.get("serve.restore", {"n": 0})["n"] == \
        r["counters"]["prefix_hits"]
    assert t["window_s"] == pytest.approx(r["trace"]["window_s"])
    assert _read("flush_idle_share.serve", r) == pytest.approx(
        100 * t["flush_idle_s"] / t["window_s"])
    assert 0 < t["flush_idle_s"] <= t["idle_s"]


def test_decode_cost_by_hand():
    m = json.loads((ROOT / "bench/configs/qwen3-1.7b.json").read_text())[
        "model"]
    flops, nbytes = costs.decode_call(m, [1, 4096])
    per_layer = 2048 * (2048 + 2 * 1024) + 2048 * 2048 + 3 * 2048 * 6144
    p = 28 * per_layer + 2048 * 151936
    assert flops == 2 * p * 2 + 4 * 28 * 16 * 128 * (1 + 4096)
    kv = 2 * 28 * 8 * 128 * 2
    assert nbytes == 2 * p + 4095 * kv + 2 * (kv + 2048 * 2)


def test_peaks_are_known_or_refused():
    from bench.peaks import peaks_for
    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks_for("cpu")
