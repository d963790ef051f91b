"""The reduction of the engine's own spans (``bench/spans.py``): on plain
data, on a small trace recorded on a TPU v5e chip
(``data/spans.xplane.pb``: a tiny engine serving a few requests, one of
them restored, with its span log on inside ``bench.trace_window``), and a
CPU run with both the benchmark's ``Recorder`` and the span log
installed, whose per-call records must agree."""
import pathlib

import pytest

from bench import spans, system, trace
from bench.tests.test_bench_harness import TINY

DATA = pathlib.Path(__file__).parent / "data"
MS = 1_000_000
READERS = {  # the readers of bench/spans.py (flush_copy_gbps is no metric)
    "queue_wait_ms.serve": lambda r: spans.mean_ms(r, "serve.queue"),
    "flush_ms.serve": lambda r: spans.mean_ms(r, "serve.flush"),
    "flush_copy_gbps.serve": spans.copy_gbps,
    "restore_ms.serve": lambda r: spans.mean_ms(r, "serve.restore"),
    "flush_idle_share.serve": spans.flush_idle_share}


def test_idle_labelled_by_innermost_span_and_flush_overlap():
    ops = [(0, 2 * MS), (6 * MS, 7 * MS), (9 * MS, 11 * MS)]
    host = [(1 * MS, 8 * MS, "serve.step"), (2 * MS, 5 * MS, "serve.flush"),
            (3 * MS, 4 * MS + MS // 2, "serve.flush.copy"),
            (0, 10 * MS, "bench.step")]          # not a serve.* span
    r = spans.reduce_planes(ops, host, (0, 10 * MS))
    assert r["window_s"] == pytest.approx(0.010)
    # idle: [2, 6) and [7, 9) ms
    assert r["idle_s"] == pytest.approx(0.006)
    assert r["idle_by_span"] == {
        "serve.step": pytest.approx(0.002),       # [5, 6) and [7, 8)
        "serve.flush": pytest.approx(0.0015),     # [2, 3) and [4.5, 5)
        "serve.flush.copy": pytest.approx(0.0015),
        "no span": pytest.approx(0.001)}          # [8, 9)
    assert r["flush_s"] == pytest.approx(0.003)
    assert r["flush_idle_s"] == pytest.approx(0.003)
    # the flush overlapping device work counts only where the device idles
    r = spans.reduce_planes(ops + [(4 * MS, 5 * MS)], host, (0, 10 * MS))
    assert r["flush_idle_s"] == pytest.approx(0.002)
    assert spans.flush_idle_share({"serve_spans": {"trace": r}}) == \
        pytest.approx(20.0)
    # no serve.* span: nothing to read
    assert spans.reduce_planes(ops, host[-1:], (0, 10 * MS)) is None


def test_records_reduce_to_counts_and_the_flush_split():
    recs = [("serve.step", 0, 200, None, None, {}),
            ("serve.flush", 10, 110, 0, 7, {"bytes": 1000}),
            ("serve.flush.copy", 40, 90, 1, None, {"bytes": 1000}),
            ("serve.queue", 0, 4_000_000, None, 7, {}),
            ("serve.queue", 0, 2_000_000, None, 8, {}),
            ("serve.restore", 300, None, None, 9, {"bytes": 5})]  # open
    red = spans.reduce_records(recs)
    assert red["by_name"]["serve.flush"] == {"n": 1, "seconds": 1e-7,
                                             "bytes": 1000}
    assert "serve.restore" not in red["by_name"]
    assert red["flush_split"] == {"n": 1, "wait_s": pytest.approx(3e-8),
                                  "copy_s": pytest.approx(5e-8),
                                  "insert_s": pytest.approx(2e-8)}
    rec = {"serve_spans": dict(red, trace=None)}
    assert spans.mean_ms(rec, "serve.queue") == pytest.approx(3.0)
    assert spans.copy_gbps(rec) == pytest.approx(1000 / 5e-8 / 1e9)
    assert spans.mean_ms(rec, "serve.restore") is None
    assert spans.flush_idle_share(rec) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_new_metrics_read_nothing_without_the_span_log(name):
    """A record without spans (a program without the log) reads None."""
    read = READERS[name]
    assert read({"trace": None}) is None
    assert read({"serve_spans": None}) is None


def test_recorded_tpu_trace_with_serve_spans():
    path = str(DATA / "spans.xplane.pb")
    r = spans.reduce_file(path)
    whole = trace.reduce_file(path)
    assert r["window_s"] == pytest.approx(whole["window_s"])
    assert r["idle_s"] == pytest.approx(whole["window_s"] - whole["busy_s"])
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["idle_s"])
    assert set(r["idle_by_span"]) <= {
        "serve.step", "serve.prefill", "serve.restore", "serve.decode",
        "serve.retire", "serve.flush", "serve.flush.copy", "no span"}
    assert "serve.flush.copy" in r["idle_by_span"]
    assert 0 < r["flush_idle_s"] <= min(r["flush_s"], r["idle_s"])
    from repro.serving.engine import DECODE_PROGRAM, PREFILL_PROGRAM
    progs = whole["programs"]
    assert any(f"jit_{DECODE_PROGRAM}" in p for p in progs)
    assert any(f"jit_{PREFILL_PROGRAM}" in p for p in progs)


def test_span_log_agrees_with_the_recorder(mesh_ctx):
    """The program's decode and prefill records equal the benchmark's, so
    ``decode_roofline`` and ``mfu`` can read either."""
    from repro.serving.engine import Request
    engine = system.build_engine(TINY, 5)
    rec = system.Recorder(engine)
    rec.on = True
    spans.start(engine)
    prompts = [list(range(1, 1 + n)) for n in (40, 70, 9)]
    for rid, p in enumerate(prompts + prompts[:1]):
        engine.submit(Request(rid=rid, prompt=p, max_new_tokens=5))
        if rid == len(prompts) - 1:      # the repeat comes back restored
            system.run_until_drained(engine)
    system.run_until_drained(engine)
    logged = engine.spans.records()
    live = [a["live"] for n, *_, a in logged if n == "serve.decode"]
    chunks = [c for n, *_, a in logged if n == "serve.prefill"
              for c in a["chunks"]]
    assert live == [v for _, v in rec.decode] and live
    assert chunks == [(p0, n) for _, p0, n in rec.prefill] and chunks
    assert engine.stats["prefix_hits"] == 1
    red = spans.reduce(engine)
    assert not engine.spans.on
    assert red["by_name"]["serve.flush"]["n"] == engine.stats["flushes"]
    assert red["flush_split"]["n"] == engine.stats["flushes"]
