"""The trace reduction: busy union, idle share, per-program time and
idle gaps labelled by the host span open over them, on plain data and
on a small trace recorded on a TPU v5e chip (``data/small.xplane.pb``:
a few calls of a jitted ``_decode_sample`` inside ``bench.*`` spans)."""
import pathlib

import pytest

from bench import readers, trace

DATA = pathlib.Path(__file__).parent / "data"


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]


def test_reduce_planes_busy_programs_and_gaps():
    ms = 1_000_000
    devices = {0: {"ops": [("a", 0, 2 * ms), ("b", 1 * ms, 3 * ms),
                           ("c", 6 * ms, 7 * ms), ("d", 9 * ms, 12 * ms)],
                   "modules": [("jit__decode_sample(3)", 0, 3 * ms),
                               ("jit__decode_sample(3)", 6 * ms, 7 * ms),
                               ("jit__prefill_chunk_body(9)", 9 * ms,
                                12 * ms)]}}
    spans = [(0, 10 * ms, trace.WINDOW_SPAN), (2 * ms, 9 * ms, "bench.step"),
             (3 * ms, 5 * ms, "bench.flush")]
    r = trace.reduce_planes(devices, spans)
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.003 + 0.001 + 0.001)
    assert r["programs"]["jit__decode_sample"] == {
        "calls": 2, "seconds": pytest.approx(0.004)}
    # the prefill call starts inside the window and is clipped to it
    assert r["programs"]["jit__prefill_chunk_body"]["seconds"] == \
        pytest.approx(0.001)
    assert r["idle_gaps"][0] == ["bench.flush", pytest.approx(0.003)]
    assert r["idle_gaps"][1] == ["bench.step", pytest.approx(0.002)]
    assert r["device_ops"][0][0] == "jit__decode_sample"
    assert readers.idle_share({"trace": r}) == pytest.approx(50.0)


def test_reduce_planes_without_events_reads_nothing():
    r = trace.reduce_planes({}, [])
    assert r["busy_s"] == 0.0
    assert readers.idle_share({"trace": r}) is None


def test_recorded_tpu_trace():
    r = trace.reduce_file(str(DATA / "small.xplane.pb"))
    assert 0 < r["busy_s"] <= r["window_s"]
    progs = r["programs"]
    name = next(k for k in progs if "_decode_sample" in k)
    assert progs[name]["calls"] == 5
    assert 0 < progs[name]["seconds"] <= r["window_s"]
    assert r["idle_gaps"] and all(g[1] > 0 for g in r["idle_gaps"])
    assert {g[0] for g in r["idle_gaps"]} <= {"bench.step", "bench.wait",
                                             "no span"}
