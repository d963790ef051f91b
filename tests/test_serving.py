"""Serving hot-path tests: staging ring semantics, chunked prefill,
on-device sampling determinism, tiered-store LRU, and the
retire -> QoS-gated flush -> prefix-restore round trip."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import MeshConfig, RunConfig, SHAPES
from repro.core import deterministic_store as ds
from repro.core.qos import DevLoad
from repro.models import model as M
from repro.serving.engine import HostPageStore, Request, ServingEngine

PROMPT = [1, 2, 3, 7, 9, 4, 2, 8, 1, 5, 6]


def _make(arch="qwen3-1.7b", **kw):
    cfg = registry.smoke(arch)
    rc = RunConfig(model=cfg, shape=SHAPES["decode_32k"], mesh=MeshConfig())
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    return ServingEngine(params, cfg, rc, **kw)


# ------------------------------------------------------------- StagingRing

def test_ring_wraparound_overwrites_oldest():
    item = jax.eval_shape(lambda: jnp.zeros((2,), jnp.float32))
    state = ds.ring_init(4, item)
    for i in range(6):                    # 6 writes into 4 slots: wraps
        state = ds.ring_write(state, jnp.int32(i),
                              jnp.full((2,), float(i)))
    hit, _ = ds.ring_lookup(state, jnp.int32(0))
    assert not bool(hit)                  # overwritten by write 4
    hit, _ = ds.ring_lookup(state, jnp.int32(1))
    assert not bool(hit)                  # overwritten by write 5
    for i in range(2, 6):
        hit, slot = ds.ring_lookup(state, jnp.int32(i))
        assert bool(hit)
        got = ds.read_through(state, jnp.int32(i), jnp.zeros((2,)))
        np.testing.assert_allclose(np.asarray(got), float(i))
    assert float(ds.ring_occupancy(state)) == 1.0


def test_ring_duplicate_key_latest_write_wins_after_wrap():
    item = jax.eval_shape(lambda: jnp.zeros((), jnp.float32))
    state = ds.ring_init(4, item)
    # writes: keys [1, 2, 3, 1, 9, 1] -> ring keys are [9, 1, 3, 1] with
    # head at 2; key 1 appears at slots 1 (newest) and 3 (older)
    for key, val in [(1, 10.0), (2, 20.0), (3, 30.0), (1, 40.0),
                     (9, 90.0), (1, 50.0)]:
        state = ds.ring_write(state, jnp.int32(key), jnp.float32(val))
    hit, slot = ds.ring_lookup(state, jnp.int32(1))
    assert bool(hit) and int(slot) == 1   # recency rank picks the newest
    got = ds.read_through(state, jnp.int32(1), jnp.float32(-1.0))
    assert float(got) == 50.0
    got = ds.read_through(state, jnp.int32(3), jnp.float32(-1.0))
    assert float(got) == 30.0
    got = ds.read_through(state, jnp.int32(2), jnp.float32(-1.0))
    assert float(got) == -1.0             # evicted -> backing value


# ----------------------------------------------------------- HostPageStore

def test_host_page_store_put_reports_admission():
    """put() returns whether the entry survived: budget pressure can evict
    an entry during its own insert (stage -> re-stage growing past the
    budget), and the caller must not index what already left."""
    kv = {"k": np.zeros((4, 64), np.float32)}   # 1 KiB per entry
    store = HostPageStore(budget_bytes=2 * kv["k"].nbytes)
    assert store.put(1, {"kv": kv, "pos": 5, "prompt": (1,)})
    big = {"k": np.zeros((16, 64), np.float32)}  # 4 KiB > budget
    assert not store.put(2, {"kv": big, "pos": 5, "prompt": (2,)})
    assert 2 not in store.pages and 1 not in store.pages  # LRU went first
    assert store.bytes == 0


def test_store_restage_evict_keeps_alias_index_bounded(mesh_ctx):
    """Regression for the stage -> re-stage -> evict ordering: when a
    flushed entry is evicted during its own put (or a re-staged rid
    replaces and then ages out), the prompt->rid alias map must not keep
    a dangling entry — on_evict fires before the sink used to re-add the
    alias, leaking one index entry per evicted prompt."""
    # budget below one smoke entry: every flush self-evicts on insert
    eng = _make(n_slots=1, max_seq=32, store_budget_bytes=1024)
    for rid in range(3):
        eng.submit(Request(rid=rid, prompt=[rid + 1, 2, 3],
                           max_new_tokens=2))
    eng.run(max_ticks=200)
    assert eng.store.evictions >= 3 and not eng.store.pages
    assert eng._prompt_index == {}              # the leak
    assert eng.store.bytes == 0

    # stage -> RE-stage (same rid+prompt retired twice) -> evict by a
    # later, larger working set: alias entries always point at live pages
    eng = _make(n_slots=1, max_seq=32, store_budget_bytes=60_000)
    for _ in range(2):                          # second pass re-stages rid 0
        eng.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=2))
        eng.run(max_ticks=100)
    assert 0 in eng.store.pages
    for rid in range(1, 5):                     # push rid 0 out via LRU
        eng.submit(Request(rid=rid, prompt=[rid + 1, 2, 3],
                           max_new_tokens=2))
        eng.run(max_ticks=100)
    assert eng.store.evictions >= 1
    live = set(eng.store.pages)
    assert set(eng._prompt_index.values()) <= live
    assert eng.store.bytes == sum(
        eng.store._entry_bytes(e) for e in eng.store.pages.values())
    assert eng.store.bytes <= 60_000


def test_submit_probe_refreshes_store_recency(mesh_ctx):
    """Regression: the enqueue-time SR probe read ``store.pages`` without
    touching recency, so a hot prefix — one a queued request was about to
    restore — could be evicted behind entries nobody was waiting for,
    wasting the MemSpecRd and forcing a full re-prefill. A confirmed
    probe must refresh LRU order so the prefix survives until admission.
    """
    from repro.core.tier import CxlTier, TierConfig

    # a tier makes submit() issue the enqueue-time SR probe; budget sized
    # to exactly the working set so any insertion evicts the LRU entry
    eng = _make(n_slots=1, max_seq=32,
                cxl_tier=CxlTier(TierConfig(media="dram")))
    for rid in range(3):
        eng.submit(Request(rid=rid, prompt=[rid + 1, 2, 3],
                           max_new_tokens=2))
    eng.run(max_ticks=200)
    assert set(eng.store.pages) == {0, 1, 2}
    per_entry = eng.store.bytes // 3
    eng.store.budget_bytes = 3 * per_entry

    # rid 0 is the LRU entry; a queued resubmit probes (and now touches)
    # it at submit time...
    eng.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=2))
    assert next(iter(eng.store.pages)) != 0    # probe refreshed recency
    # ...so a competing retirement evicts a genuinely cold entry instead
    eng.submit(Request(rid=7, prompt=[7, 7, 7], max_new_tokens=2))
    eng.run(max_ticks=200)
    assert 0 in eng.store.pages                # the hot prefix survived
    restored = [r for r in eng.finished if r.rid == 0 and r.restored]
    assert restored, "resubmit was not served via restore"


def test_host_page_store_lru_eviction_and_bytes():
    kv = {"k": np.zeros((4, 64), np.float32)}   # 1 KiB per entry
    store = HostPageStore(budget_bytes=3 * kv["k"].nbytes)
    for rid in range(3):
        store.put(rid, {"kv": kv, "pos": 5, "prompt": (rid,)})
    assert store.bytes == 3 * kv["k"].nbytes and not store.evictions
    store.get(0)                                # refresh rid 0's recency
    store.put(3, {"kv": kv, "pos": 5, "prompt": (3,)})
    assert store.evictions == 1
    assert 1 not in store.pages                 # LRU (not rid 0) evicted
    assert 0 in store.pages and 3 in store.pages
    assert store.bytes <= store.budget_bytes
    # re-put of an existing rid replaces, not duplicates
    store.put(3, {"kv": kv, "pos": 6, "prompt": (3,)})
    assert store.bytes == 3 * kv["k"].nbytes and store.evictions == 1


# ------------------------------------------------- chunked prefill parity

def test_prefill_step_cached_matches_sequential_decode(mesh_ctx):
    """The chunked cache-writing prefill must reproduce the per-token
    decode_step path: same KV cache, same final logits."""
    cfg = registry.smoke("qwen3-1.7b")
    rc = RunConfig(model=cfg, shape=SHAPES["decode_32k"], mesh=MeshConfig())
    params = M.init_model(jax.random.PRNGKey(1), cfg)
    import repro.parallel.sharding as shlib
    pspecs = shlib.param_specs(jax.eval_shape(lambda: params))
    toks = [5, 17, 3, 250, 9, 11, 41]

    seq = M.cache_init(cfg, rc, 1, max_seq=32)
    logits_seq = None
    for t in toks:
        logits_seq, seq = M.decode_step(params, cfg, rc,
                                        jnp.full((1, 1), t, jnp.int32),
                                        seq, pspecs)

    chunk = M.cache_init(cfg, rc, 1, max_seq=32)
    logits_chunk, chunk = M.prefill_step_cached(
        params, cfg, rc, jnp.asarray([toks], jnp.int32), chunk, pspecs)

    np.testing.assert_allclose(
        np.asarray(logits_seq.astype(jnp.float32))[0, -1],
        np.asarray(logits_chunk.astype(jnp.float32))[0, -1],
        atol=2e-2, rtol=2e-2)
    assert int(chunk["pos"][0]) == len(toks) == int(seq["pos"][0])
    np.testing.assert_allclose(
        np.asarray(chunk["kv"]["k"].astype(jnp.float32)),
        np.asarray(seq["kv"]["k"].astype(jnp.float32)),
        atol=2e-2, rtol=2e-2)


# --------------------------------- cache read in place, one write per call

MAX_SEQ = 512                 # two pages of 256: a boundary at 255 -> 256


def _gqa_model(group, dtype):
    cfg = dataclasses.replace(registry.smoke("qwen3-1.7b"), n_layers=2,
                              n_heads=2 * group, n_kv_heads=2, dtype=dtype)
    rc = RunConfig(model=cfg, shape=SHAPES["decode_32k"], mesh=MeshConfig())
    params = M.init_model(jax.random.PRNGKey(3), cfg)
    import repro.parallel.sharding as shlib
    return cfg, rc, params, shlib.param_specs(jax.eval_shape(lambda: params))


def _ref_call(params, cfg, tokens, k, v, pos):
    """Reference of one call: each layer writes the rows' new K/V with
    ``.at[].set`` and attends over the written rows with naive_attention.
    tokens [B, C]; k/v [L, B, S, Hkv, D]; row b's tokens at pos[b] + i.
    Returns (logits [B, C, V], k, v)."""
    from repro.models import attention as A
    from repro.models.layers import (embed_apply, mlp_apply, rmsnorm,
                                     unembed_apply)
    b, c = tokens.shape
    x = embed_apply(params["embed"], cfg, tokens)
    for l in range(cfg.n_layers):
        layer = jax.tree_util.tree_map(lambda a: a[l], params["blocks"])
        h = rmsnorm(layer["ln_attn"], x, cfg.norm_eps)
        positions = jnp.asarray(pos)[:, None] + jnp.arange(c)[None]
        q, kn, vn = A.qkv_project(layer["attn"], cfg, h, positions)
        rows = []
        for r in range(b):
            p = int(pos[r])
            k = k.at[l, r, p:p + c].set(kn[r].astype(k.dtype))
            v = v.at[l, r, p:p + c].set(vn[r].astype(v.dtype))
            # queries padded to the front so the causal mask lines up
            qr = jnp.zeros((1, p + c) + q.shape[2:], q.dtype)
            qr = qr.at[0, p:].set(q[r])
            rows.append(A.naive_attention(qr, k[l, r:r + 1, :p + c],
                                          v[l, r:r + 1, :p + c])[0, p:])
        o = jnp.stack(rows).reshape(b, c, cfg.q_dim)
        x = x + o @ layer["attn"]["wo"]
        x = x + mlp_apply(layer["mlp"], cfg,
                          rmsnorm(layer["ln_mlp"], x, cfg.norm_eps))
    x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
    return unembed_apply(params["embed"], cfg, x), k, v


def _noisy_cache(cfg, rc, rows, pos):
    """A cache whose every position holds noise, so a read past a row's
    length or of another row shows in the logits."""
    cache = M.cache_init(cfg, rc, rows, max_seq=MAX_SEQ)
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    for key, name in zip(ks, ("k", "v")):
        a = cache["kv"][name]
        cache["kv"][name] = jax.random.normal(key, a.shape).astype(a.dtype)
    cache["pos"] = jnp.asarray(pos, jnp.int32)
    return cache


def _flat(a):
    return a.reshape(a.shape[:2] + (-1,) + a.shape[4:])


def _check(got_logits, want_logits, cache, k, v, tol):
    np.testing.assert_allclose(np.asarray(got_logits, np.float32),
                               np.asarray(want_logits, np.float32),
                               atol=tol, rtol=tol)
    for name, want in (("k", k), ("v", v)):
        np.testing.assert_allclose(
            np.asarray(_flat(cache["kv"][name]), np.float32),
            np.asarray(want, np.float32), atol=tol, rtol=tol, err_msg=name)


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4),
                                       ("bfloat16", 5e-2)])
@pytest.mark.parametrize("group", [2, 16])
def test_decode_ticks_match_written_cache_reference(mesh_ctx, group, dtype,
                                                    tol):
    """Three decode ticks of rows at different positions: one mid-page,
    one stepping over the page boundary (255 -> 256), one ending at
    ``max_seq - 1``. After each tick the logits and the whole cache match
    a reference that writes the token first and attends over the cache."""
    cfg, rc, params, pspecs = _gqa_model(group, dtype)
    pos = [17, 254, MAX_SEQ - 3]
    cache = _noisy_cache(cfg, rc, 3, pos)
    k, v = _flat(cache["kv"]["k"]), _flat(cache["kv"]["v"])
    step = jax.jit(lambda p, t, c: M.decode_step(p, cfg, rc, t, c, pspecs))
    for i in range(3):
        toks = jnp.asarray([[5 + i], [40 + i], [200 + i]], jnp.int32)
        want, k, v = _ref_call(params, cfg, toks, k, v,
                               [p + i for p in pos])
        got, cache = step(params, toks, cache)
        _check(got, want, cache, k, v, tol)
        assert list(np.asarray(cache["pos"])) == [p + i + 1 for p in pos]


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4),
                                       ("bfloat16", 5e-2)])
@pytest.mark.parametrize("group", [2, 16])
@pytest.mark.parametrize("slot,pos0", [(1, 200), (2, MAX_SEQ - 128)])
def test_prefill_chunk_matches_written_cache_reference(mesh_ctx, group,
                                                       dtype, tol, slot,
                                                       pos0):
    """One 128-token chunk into one row of a three-row cache, across the
    page boundary or up to ``max_seq - 1``: the chunk's logits, and the
    whole cache (other rows untouched), match the reference."""
    cfg, rc, params, pspecs = _gqa_model(group, dtype)
    pos = [60, 90, 120]
    pos[slot] = pos0
    cache = _noisy_cache(cfg, rc, 3, pos)
    k, v = _flat(cache["kv"]["k"]), _flat(cache["kv"]["v"])
    toks = jax.random.randint(jax.random.PRNGKey(5), (1, 128), 1, 256)
    want, kr, vr = _ref_call(params, cfg, toks, k[:, slot:slot + 1],
                             v[:, slot:slot + 1], [pos0])
    k, v = k.at[:, slot:slot + 1].set(kr), v.at[:, slot:slot + 1].set(vr)
    got, cache = jax.jit(lambda p, t, c, s: M.prefill_step_cached(
        p, cfg, rc, t, c, pspecs, slot=s))(params, toks, cache, slot)
    _check(got, want, cache, k, v, tol)
    pos[slot] += 128
    assert list(np.asarray(cache["pos"])) == pos


def test_engine_chunked_prefill_matches_legacy_greedy(mesh_ctx):
    """Multi-chunk prefill + fused on-device sampling must emit the same
    greedy tokens as the pre-rewrite per-token host path."""
    legacy = _make(n_slots=2, max_seq=32, legacy_host_path=True)
    new = _make(n_slots=2, max_seq=32, prefill_chunk=4)
    for eng in (legacy, new):
        for rid in range(3):
            eng.submit(Request(rid=rid, prompt=PROMPT[: 11 - rid],
                               max_new_tokens=5))
        eng.run(max_ticks=200)
    legacy_out = {r.rid: r.generated for r in legacy.finished}
    new_out = {r.rid: r.generated for r in new.finished}
    assert legacy_out == new_out
    # the whole point: a handful of chunk dispatches, not one per token
    assert new.stats["prefill_dispatches"] < new.stats["prefill_tokens"]


def test_slot_reuse_prefill_isolated(mesh_ctx):
    """A request admitted into a reused slot must decode exactly as if it
    had the engine to itself (regression: the first prefill chunk used the
    slot's stale device pos left by the previous occupant)."""
    solo = _make(n_slots=1, max_seq=32)
    solo.submit(Request(rid=1, prompt=[9, 8, 7, 6, 5], max_new_tokens=4))
    ref = solo.run(max_ticks=60)[0].generated

    eng = _make(n_slots=1, max_seq=32)
    eng.submit(Request(rid=0, prompt=[1, 2, 3, 4, 5, 6, 7],
                       max_new_tokens=6))
    eng.submit(Request(rid=1, prompt=[9, 8, 7, 6, 5], max_new_tokens=4))
    outs = {r.rid: r.generated for r in eng.run(max_ticks=100)}
    assert outs[1] == ref


@pytest.mark.parametrize("arch", ["granite-moe-1b-a400m", "musicgen-large",
                                  "zamba2-2.7b"])
def test_engine_families_complete(mesh_ctx, arch):
    """Chunked (moe/audio) and scan-fallback (hybrid) families serve
    requests through the device-resident path."""
    eng = _make(arch, n_slots=2, max_seq=16, prefill_chunk=3)
    for rid in range(2):
        eng.submit(Request(rid=rid, prompt=[1 + rid, 2, 3, 4],
                           max_new_tokens=3))
    done = eng.run(max_ticks=60)
    assert len(done) == 2
    assert all(len(r.generated) == 3 for r in done)


# --------------------------------------------------- int8 KV token quality

def test_greedy_int8_kv_matches_baseline_token_for_token(mesh_ctx):
    """The serving-level accuracy gate: greedy decode with the int8 KV
    cache must reproduce the full-precision engine's tokens exactly on
    the smoke configs — quantization noise (0.5 ulp of a 127-step page
    grid) stays far below the greedy argmax margins."""
    outs = {}
    for mode in ("none", "int8"):
        eng = _make(n_slots=2, max_seq=32, prefill_chunk=4, kv_quant=mode)
        for rid in range(3):
            eng.submit(Request(rid=rid, prompt=PROMPT[: 11 - rid],
                               max_new_tokens=6))
        eng.run(max_ticks=200)
        outs[mode] = {r.rid: r.generated for r in eng.finished}
        assert len(outs[mode]) == 3
    assert outs["int8"] == outs["none"]


def test_temperature_int8_bounded_divergence(mesh_ctx):
    """Sampled decode under int8 KV: one borderline sample flipped by
    quantization noise legitimately forks the sequence from that point
    on, so exact identity is NOT the contract — the documented bound is
    the positional match fraction (serve_bench's kv_quant axis pins the
    same bound end-to-end; see docs/ARCHITECTURE.md "KV page format").
    Determinism still holds: same engine seed + mode => same tokens."""
    outs = {}
    for mode in ("none", "int8"):
        runs = []
        for _ in range(2):
            eng = _make(n_slots=2, max_seq=32, temperature=0.8, seed=7,
                        kv_quant=mode)
            for rid in range(3):
                eng.submit(Request(rid=rid, prompt=[5, 6, 7],
                                   max_new_tokens=6))
            eng.run(max_ticks=100)
            runs.append({r.rid: r.generated for r in eng.finished})
        assert runs[0] == runs[1]          # seeded sampling deterministic
        outs[mode] = runs[0]
    total = matched = 0
    for rid, a in outs["none"].items():
        b = outs["int8"][rid]
        total += max(len(a), len(b))
        matched += sum(x == y for x, y in zip(a, b))
    assert matched / total >= 0.5          # bounded, not exact (see above)


# ------------------------------------------------- sampling determinism

def test_temperature_sampling_deterministic_across_host_rng(mesh_ctx):
    """Same engine seed => same tokens, independent of host numpy RNG
    state/version (sampling runs on device via the jax PRNG)."""
    outs = []
    for np_seed in (123, 987654):
        np.random.seed(np_seed)           # must not influence anything
        eng = _make(n_slots=2, max_seq=32, temperature=0.8, seed=7)
        for rid in range(3):
            eng.submit(Request(rid=rid, prompt=[5, 6, 7],
                               max_new_tokens=6))
        eng.run(max_ticks=100)
        outs.append({r.rid: r.generated for r in eng.finished})
    assert outs[0] == outs[1]


# ------------------- retire -> QoS-gated flush -> prefix-restore round trip

def test_retire_flush_restore_round_trip(mesh_ctx):
    eng = _make(n_slots=2, max_seq=32, prefill_chunk=4)
    # congest the QoS controller: flushes stay suppressed every tick
    eng.qos.classify = lambda **kw: DevLoad.MODERATE
    eng.submit(Request(rid=42, prompt=PROMPT, max_new_tokens=4))
    done = eng.run(max_ticks=100)
    assert done[0].done
    original = done[0].generated
    assert not eng.store.pages              # flush was QoS-gated
    assert len(eng.flusher.pending) == 1    # pages parked in staging
    assert eng.flusher.suppressed > 0

    # a resubmit while the pages sit in staging is served from the
    # staging index (latest-write-wins read path), no prefill dispatches,
    # and reproduces the original greedy continuation
    pf = eng.stats["prefill_dispatches"]
    eng.submit(Request(rid=42, prompt=PROMPT, max_new_tokens=3))
    done = eng.run(max_ticks=100)
    assert done[-1].restored
    assert done[-1].generated == original[:3]
    assert eng.stats["prefill_dispatches"] == pf
    assert eng.stats["prefix_hits"] == 1

    # load clears -> the background flush drains staging into the store
    del eng.qos.classify                    # restore the real classifier
    eng.qos.update(DevLoad.LIGHT)
    assert eng.flusher.maybe_flush() >= 1
    assert 42 in eng.store.pages

    # ...and a later resubmit restores from the cold tier as well
    pf = eng.stats["prefill_dispatches"]
    eng.submit(Request(rid=42, prompt=PROMPT, max_new_tokens=2))
    done = eng.run(max_ticks=100)
    assert done[-1].restored
    assert eng.stats["prefill_dispatches"] == pf


def test_prefix_restore_requires_matching_prompt(mesh_ctx):
    """A rid collision with a different prompt must NOT restore pages."""
    eng = _make(n_slots=1, max_seq=32)
    eng.submit(Request(rid=7, prompt=[1, 2, 3], max_new_tokens=3))
    eng.run(max_ticks=60)
    pf = eng.stats["prefill_dispatches"]
    eng.submit(Request(rid=7, prompt=[9, 9, 9], max_new_tokens=3))
    done = eng.run(max_ticks=60)
    assert not done[-1].restored
    assert eng.stats["prefill_dispatches"] > pf


def test_engine_store_stats_surface(mesh_ctx):
    budget = 60_000       # smoke qwen3 slot pages are ~16-32 KiB: the
    eng = _make(n_slots=1, max_seq=32, store_budget_bytes=budget)
    # budget holds 1-3 entries, so 4 retirements must evict
    for rid in range(4):
        eng.submit(Request(rid=rid, prompt=[rid + 1, 2, 3],
                           max_new_tokens=2))
    eng.run(max_ticks=200)
    assert eng.stats["store_bytes"] == eng.store.bytes
    assert eng.stats["store_evictions"] == eng.store.evictions
    assert eng.store.bytes <= budget
    assert eng.store.evictions >= 1


def test_moe_routing_counters_match_the_programs(mesh_ctx):
    """The engine's routing counters are the counts its prefill and
    decode programs make, summed on the device and read at retirement:
    replayed here through the model's own steps on the served tokens."""
    eng = _make("granite-moe-1b-a400m", n_slots=1, max_seq=32,
                prefill_chunk=4)
    cfg = eng.cfg
    assert cfg.n_held_experts < cfg.n_experts
    prompt = PROMPT[:10]
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=6))
    out = eng.run(max_ticks=50)[0].generated

    cache = M.cache_init(cfg, eng.hot_rc, 1, max_seq=32)
    routed = touched = calls = 0
    for i in range(0, len(prompt), 4):
        _, cache, c = M.prefill_step_cached(
            eng.params, cfg, eng.hot_rc,
            jnp.asarray([prompt[i:i + 4]], jnp.int32), cache, eng.pspecs,
            routing=True)
        routed += int(c[0])
    for t in out[:-1]:
        _, cache, c = M.decode_step(eng.params, cfg, eng.hot_rc,
                                    jnp.full((1, 1), t, jnp.int32), cache,
                                    eng.pspecs, routing=True)
        routed, touched, calls = (routed + int(c[0]), touched + int(c[1]),
                                  calls + 1)
    st = eng.stats
    assert calls == st["decode_dispatches"] == 5
    assert (st["moe_local_routings"], st["moe_expert_touches"],
            st["moe_decode_calls_counted"]) == (routed, touched, calls)
    assert 0 < touched <= calls * cfg.n_layers * cfg.top_k
    # a dense model counts nothing
    dense = _make(n_slots=1, max_seq=32)
    dense.submit(Request(rid=0, prompt=prompt, max_new_tokens=3))
    dense.run(max_ticks=20)
    assert dense.stats["moe_local_routings"] == 0
