"""The serving engine's span log (``repro.serving.spans``): off it keeps
nothing and hands out one shared null context; on, its spans nest under
``serve.step``, a request's spans share its rid, and the counts match the
engine's own counters. The hot programs carry their stable names."""
import jax
import numpy as np

from repro.configs import registry
from repro.configs.base import MeshConfig, RunConfig, SHAPES
from repro.models import model as M
from repro.serving import engine as E
from repro.serving.engine import HostPageStore, Request, ServingEngine
from repro.serving.spans import SpanLog

PROMPT = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]


def _make(**kw):
    cfg = registry.smoke("qwen3-1.7b")
    rc = RunConfig(model=cfg, shape=SHAPES["decode_32k"], mesh=MeshConfig())
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    return ServingEngine(params, cfg, rc, n_slots=2, max_seq=32,
                         prefill_chunk=4, **kw)


def _drain(eng):
    while eng.queue or any(s is not None for s in eng.slots):
        eng.step()


def _serve(eng):
    """Three requests, the third a repeat of the first's prompt (restored
    from the host store), each retired and flushed."""
    for rid, prompt in ((1, PROMPT), (2, PROMPT[::-1])):
        eng.submit(Request(rid=rid, prompt=prompt, max_new_tokens=3))
    _drain(eng)
    eng.submit(Request(rid=3, prompt=PROMPT, max_new_tokens=3))
    _drain(eng)


def test_off_keeps_nothing_and_shares_one_null_context(mesh_ctx):
    eng = _make()
    assert not eng.spans.on
    a, b = eng.spans.span("serve.step"), eng.spans.span("x", rid=1, n=2)
    assert a is b
    with a as attrs:
        assert attrs is None
    _serve(eng)
    assert eng.spans.records() == []
    assert all(r._queued_ns is None for r in eng.finished)


def test_on_spans_nest_and_share_the_request_rid(mesh_ctx):
    eng = _make()
    eng.spans.start()
    _serve(eng)
    recs = eng.spans.records()
    assert all(t1 is not None and t1 >= t0 for _, t0, t1, *_ in recs)
    names = {r[0] for r in recs}
    assert names == {"serve.queue", "serve.step", "serve.prefill",
                     "serve.restore", "serve.decode", "serve.retire",
                     "serve.flush", "serve.flush.copy"}

    def outermost(i):
        while recs[i][3] is not None:
            i = recs[i][3]
        return recs[i][0]

    for i, (name, t0, t1, parent, rid, attrs) in enumerate(recs):
        if name in ("serve.queue", "serve.step"):
            assert parent is None, name
            continue
        assert outermost(i) == "serve.step", name
        p = recs[parent]
        assert p[1] <= t0 and t1 <= p[2], (name, p[0])
        if name == "serve.flush.copy":
            assert p[0] == "serve.flush" and attrs["bytes"] == p[5]["bytes"]
    by_rid = {}
    for name, *_, rid, _ in recs:
        if rid is not None:
            by_rid.setdefault(rid, set()).add(name)
    assert by_rid[1] == by_rid[2] == {"serve.queue", "serve.prefill",
                                     "serve.retire", "serve.flush"}
    assert by_rid[3] == {"serve.queue", "serve.restore", "serve.retire",
                         "serve.flush"}
    restore = next(r for r in recs if r[0] == "serve.restore")
    flush = next(r for r in recs if r[0] == "serve.flush")
    assert restore[5]["bytes"] == flush[5]["bytes"] > 0
    prefill = next(r for r in recs if r[0] == "serve.prefill")
    assert prefill[5]["chunks"] == [(0, 4), (4, 4), (8, 2)]


def test_on_counts_match_the_engine_counters(mesh_ctx):
    eng = _make()
    eng.spans.start()
    _serve(eng)
    count = {}
    for name, *_ in eng.spans.records():
        count[name] = count.get(name, 0) + 1
    st = eng.stats
    assert count["serve.flush"] == st["flushes"] == 3
    assert count["serve.flush.copy"] == st["flushes"]
    assert count["serve.restore"] == st["prefix_hits"] == 1
    assert count["serve.decode"] == st["decode_dispatches"]
    assert count["serve.queue"] == 3
    lives = [r[5]["live"] for r in eng.spans.records()
             if r[0] == "serve.decode"]
    assert all(1 <= len(v) <= 2 for v in lives)


def test_start_clears_and_stop_keeps(mesh_ctx):
    log = SpanLog()
    log.start()
    with log.span("a", rid=5) as attrs:
        attrs["k"] = 1
        with log.span("b"):
            pass
    log.add("q", 0, rid=5)
    log.stop()
    with log.span("c"):
        pass
    recs = log.records()
    assert [(r[0], r[3], r[4], r[5]) for r in recs] == [
        ("a", None, 5, {"k": 1}), ("b", 0, None, {}), ("q", None, 5, {})]
    log.start()
    assert log.records() == []


def test_store_times_the_copy_alone():
    log = SpanLog()
    store = HostPageStore(spans=log)
    kv = {"k": jax.numpy.ones((4, 8), jax.numpy.bfloat16)}
    log.start()
    assert store.put(1, {"kv": kv})
    (name, t0, t1, parent, rid, attrs), = log.records()
    assert name == "serve.flush.copy" and attrs["bytes"] == 64
    assert isinstance(store.pages[1]["kv"]["k"], np.ndarray)


def test_programs_carry_their_stable_names(mesh_ctx):
    eng = _make()
    text = eng._decode_fn.lower(eng.params, eng.cache, eng.last_tokens,
                                eng.key).as_text()
    assert f"jit_{E.DECODE_PROGRAM}" in text
    i32 = np.int32(0)
    text = eng._prefill_fn.lower(
        eng.params, eng.cache, np.zeros((1, 4), np.int32), i32, i32, i32,
        eng.last_tokens, eng.key, True).as_text()
    assert f"jit_{E.PREFILL_PROGRAM}" in text
    # the benchmark's readers find the programs by their private names
    assert "_decode_sample" in E.DECODE_PROGRAM
    assert "_prefill_chunk_body" in E.PREFILL_PROGRAM


def test_tokens_do_not_depend_on_the_log(mesh_ctx):
    ref, eng = _make(), _make()
    eng.spans.start()
    _serve(ref)
    _serve(eng)
    assert [r.generated for r in eng.finished] == \
        [r.generated for r in ref.finished]
