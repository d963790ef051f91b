"""Multi-rank (sharded) serving end to end: token identity against the
single-rank engine on the same seed/trace, per-rank and peer-lane page
traces replaying against the scalar oracle, cross-rank restores beating
N independent cold restores on shared prefixes, placement invariants
under churn (hypothesis), and the ``make_production_mesh(shape=...)``
override tests/benches rely on to build small meshes.

The sharded cases need forced host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=4`` — the tier-1 CI
job sets it); on a single-device interpreter they skip, never fail.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.sharded_tier import PEER_LINK_MEDIA, ShardedTier
from repro.core.tier import CxlTier, TierConfig
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.sim.engine import replay_page_trace

ENTRY = 32 << 10
N_DEVICES = len(jax.devices())

needs2 = pytest.mark.skipif(
    N_DEVICES < 2, reason="needs >= 2 devices (XLA_FLAGS="
    "--xla_force_host_platform_device_count=4)")
needs4 = pytest.mark.skipif(
    N_DEVICES < 4, reason="needs >= 4 devices (XLA_FLAGS="
    "--xla_force_host_platform_device_count=4)")


def _needs(n_ranks):
    if N_DEVICES < n_ranks:
        pytest.skip(f"needs >= {n_ranks} devices, have {N_DEVICES}")


# ------------------------------------------- mesh shape override (fix)

def test_production_mesh_shape_override_validation():
    """Bad explicit shapes fail fast with a ValueError, not deep in
    ``jax.make_mesh``."""
    for bad in ((0, 2), (2,), (1, 2, 3, 4), (1, -1)):
        with pytest.raises(ValueError, match="2- or 3-tuple"):
            make_production_mesh(shape=bad)


def test_production_mesh_shape_insufficient_devices():
    """Asking for more devices than the process has names the fix
    (the XLA_FLAGS device-count escape hatch) in the error."""
    with pytest.raises(RuntimeError, match="xla_force_host_platform"):
        make_production_mesh(shape=(1, N_DEVICES + 1))


@needs2
def test_production_mesh_small_shapes_build():
    """The ``shape=`` override builds small meshes with the production
    axis names — no XLA_FLAGS=...=512 dry-run env needed."""
    mesh = make_production_mesh(shape=(1, 2))
    assert mesh.axis_names == ("data", "model")
    assert mesh.devices.shape == (1, 2)
    mesh3 = make_production_mesh(shape=(1, 1, 2))
    assert mesh3.axis_names == ("pod", "data", "model")
    if N_DEVICES >= 4:
        assert make_production_mesh(shape=(2, 2)).devices.shape == (2, 2)


# ---------------------------------------------------- config plumbing

def test_serve_config_shard_knobs():
    from repro.serving.config import ServeConfig
    assert ServeConfig().resolved_mesh_shape == ()
    assert ServeConfig().n_ranks == 1
    assert ServeConfig(tp=2).resolved_mesh_shape == (1, 2)
    assert ServeConfig(tp=2).n_ranks == 2
    assert ServeConfig(mesh_shape=(2, 4)).n_ranks == 4
    assert ServeConfig(mesh_shape=(2, 4), tp=4).resolved_mesh_shape == \
        (2, 4)
    with pytest.raises(ValueError, match="conflicts with tp"):
        ServeConfig(mesh_shape=(1, 2), tp=4)
    with pytest.raises(ValueError, match="2- or 3-tuple"):
        ServeConfig(mesh_shape=(0, 2))
    with pytest.raises(ValueError, match="tp must be >= 1"):
        ServeConfig(tp=0)
    with pytest.raises(ValueError, match="legacy host path"):
        ServeConfig(tp=2, legacy_host_path=True)


def test_serve_config_builds_sharded_tier():
    from repro.serving.config import ServeConfig
    sc = ServeConfig(tp=2, tier_topology=("dram", "ssd-fast"))
    tier = sc.make_tier()
    assert isinstance(tier, ShardedTier) and tier.n_ranks == 2
    assert len(tier.ranks) == 2 and len(tier.peer) == 2
    assert isinstance(ServeConfig(tier_media="ssd-fast").make_tier(),
                      CxlTier)
    # fault schedule lands on rank 0's ports only
    sc = ServeConfig(tp=2, tier_topology=("dram", "ssd-fast"),
                     tier_faults=(("hot_remove", 1e6, 1),))
    tier = sc.make_tier()
    assert tier.ranks[0].cfg.faults is not None
    assert tier.ranks[1].cfg.faults is None


@needs2
def test_engine_rejects_indivisible_page_axis():
    """n_pages % n_ranks != 0 is a construction-time error that names
    the knob to turn, not a silent fall-back to unsharded attention."""
    eng = _build_engine(tp=2)           # kv_page_size=16 divides fine
    assert eng.stats["mesh_ranks"] == 2
    with pytest.raises(ValueError, match="divisible by the model axis"):
        _build_engine(tp=2, kv_page_size=256)   # 1 page, 2 ranks


# --------------------------------------------- sharded decode identity

def _build_engine(*, tp=1, kv_quant="none", kv_page_size=16, n_slots=2,
                  tier=False, faults=(), seed=0):
    from repro.configs import registry
    from repro.configs.base import MeshConfig, RunConfig, SHAPES
    from repro.models import model as M
    from repro.serving.config import ServeConfig
    from repro.serving.engine import ServingEngine

    cfg = registry.smoke("qwen3-1.7b")
    rc = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                   mesh=MeshConfig())
    rc = dataclasses.replace(rc, kv_page_size=kv_page_size)
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    kw = dict(n_slots=n_slots, max_seq=64, prefill_chunk=8, tp=tp,
              kv_quant=kv_quant, seed=seed)
    if tier or faults:
        kw.update(tier_topology=("dram", "ssd-fast"), cxl_async=True,
                  preempt_policy="recompute", tier_faults=tuple(faults))
    return ServingEngine(params, cfg, rc, config=ServeConfig(**kw))


def _greedy_tokens(eng, n_requests=3, max_new=8):
    from repro.serving.engine import Request
    handles = [eng.submit(Request(rid=i, prompt=[1 + i, 2, 3, 4 + i],
                                  max_new_tokens=max_new))
               for i in range(n_requests)]
    eng.run(max_ticks=600)
    return [h.result() for h in handles]


@pytest.fixture(scope="module")
def single_rank_tokens():
    """Greedy token streams from the 1-rank engine (host mesh), per
    kv_quant mode — the oracle every sharded run must reproduce."""
    out = {}
    with jax.set_mesh(make_host_mesh()):
        for kv_quant in ("none", "int8"):
            out[kv_quant] = _greedy_tokens(_build_engine(
                kv_quant=kv_quant))
    return out


@pytest.mark.parametrize("tp", [2, 4])
@pytest.mark.parametrize("kv_quant", ["none", "int8"])
def test_sharded_decode_token_identity(tp, kv_quant,
                                       single_rank_tokens):
    """N-way tensor-parallel decode is bit-identical to the single-rank
    engine on the same seed and request trace — greedy, both the bf16
    and the int8-quantized KV cache (scales sharded alongside pages)."""
    _needs(tp)
    eng = _build_engine(tp=tp, kv_quant=kv_quant)
    assert eng.stats["mesh_ranks"] == tp
    # the paged KV cache really is sharded over the model axis
    leaf = jax.tree_util.tree_leaves(eng.cache["kv"])[0]
    assert "model" in str(leaf.sharding.spec)
    toks = _greedy_tokens(eng)
    assert toks == single_rank_tokens[kv_quant]


@needs2
def test_sharded_engine_with_tier_token_identity():
    """Attaching the ShardedTier (flush/restore on the simulated clock)
    must not perturb the generated tokens."""
    eng = _build_engine(tp=2, tier=True)
    toks = _greedy_tokens(eng)
    with jax.set_mesh(make_host_mesh()):
        ref = _greedy_tokens(_build_engine(tier=True))
    assert toks == ref
    assert isinstance(eng.tier, ShardedTier)


_SHARDED_WRITE_SCRIPT = textwrap.dedent("""
    import contextlib, os, re
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses
    import jax, numpy as np
    from repro.configs import registry
    from repro.launch.mesh import make_host_mesh
    from repro.configs.base import MeshConfig, RunConfig, SHAPES
    from repro.models import model as M
    from repro.serving.config import ServeConfig
    from repro.serving.engine import ServingEngine

    cfg = registry.smoke("qwen3-1.7b")
    rc = dataclasses.replace(RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                                       mesh=MeshConfig()), kv_page_size=16)
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    caches = []
    for shape in ((2, 2), ()):
        host = contextlib.nullcontext() if shape else \\
            jax.set_mesh(make_host_mesh())
        with host:
            eng = ServingEngine(params, cfg, rc, config=ServeConfig(
                n_slots=2, max_seq=64, prefill_chunk=8, mesh_shape=shape))
            kv = eng.cache["kv"]
            noise = jax.random.normal(jax.random.PRNGKey(1), kv["k"].shape)
            cache = dict(eng.cache, pos=jax.device_put(
                np.asarray([5, 37], np.int32), eng.cache["pos"].sharding),
                kv={n: jax.device_put(noise.astype(a.dtype) * (i + 1),
                                      a.sharding)
                    for i, (n, a) in enumerate(kv.items())})
            before = jax.device_get(cache["kv"])     # donated below
            with eng._mesh_scope():
                if shape:
                    txt = eng._decode_fn.lower(
                        eng.params, cache, eng.last_tokens,
                        eng.key).compile().as_text()
                out = eng._decode_fn(eng.params, cache, eng.last_tokens,
                                     eng.key)
            caches.append(jax.device_get(out[0]["kv"]))
    # one layer of one rank's share of a cache leaf, in elements
    leaf = kv["k"].shape
    shard = int(np.prod(leaf[1:])) // 4
    hkv_d = ",".join(map(str, leaf[-2:]))
    gathered = []
    for line in txt.splitlines():
        m = re.search(r"= (.*?) (all-gather|all-to-all|collective-permute)"
                      r"(-start)?\\(", line)
        if m:
            for dims in re.findall(r"\\[([\\d,]*)\\]", m.group(1)):
                if dims.endswith(hkv_d):
                    gathered.append(int(np.prod([int(x) for x in
                                                 dims.split(",")])))
    assert gathered and max(gathered) < shard, (gathered, shard)
    # rows 0 and 1 write positions 5 (rank 0's pages) and 37 (rank 1's)
    written = np.zeros(leaf[:2] + (leaf[2] * leaf[3],), bool)
    written[:, 0, 5] = written[:, 1, 37] = True
    for n in caches[0]:
        a, b, x = (np.asarray(c[n], np.float32).reshape(written.shape
                                                        + leaf[-2:])
                   for c in caches + [before])
        np.testing.assert_array_equal(a[~written], x[~written], err_msg=n)
        np.testing.assert_array_equal(b[~written], x[~written], err_msg=n)
        # layer 0's new K/V are the same arithmetic on both meshes; later
        # layers' follow attention outputs combined across ranks
        np.testing.assert_array_equal(a[0][written[0]], b[0][written[0]])
        assert not np.array_equal(a[0][written[0]], x[0][written[0]]), n
        np.testing.assert_allclose(a[written], b[written], atol=5e-2,
                                   rtol=5e-2, err_msg=n)
    print("SHARDED_WRITE_OK", max(gathered), shard)
""")


def test_sharded_decode_writes_in_place_without_gathering_the_cache():
    """The decode program on a (2, 2) mesh (batch over "data", pages over
    "model") stores the new tokens with one scatter after the layer scan,
    partitioned by XLA: no collective in it moves a K/V buffer as large
    as one layer of one rank's pages (only the new tokens travel), and the
    cache it writes equals the single-device program's, position for
    position, with rows whose new token lands on either rank's pages."""
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _SHARDED_WRITE_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert "SHARDED_WRITE_OK" in res.stdout, res.stderr[-3000:]


# ------------------------------------------------ rank-trace replay

def _replay_rank(t: CxlTier) -> np.ndarray:
    return replay_page_trace(
        t.ops, media=t.cfg.media_name,
        topology=t.cfg.port_medias if t.cfg.tagged else None,
        sr=t.cfg.sr_enabled, ds=t.cfg.ds_enabled,
        req_bytes=t.cfg.req_bytes,
        dram_cache_bytes=t.cfg.dram_cache_bytes,
        max_inflight=t.cfg.max_inflight, faults=t.cfg.faults)


def _replay_peer(tier: ShardedTier, rank: int) -> np.ndarray:
    return replay_page_trace(
        tier.peer_ops[rank], media=tier.peer_media, sr=False, ds=False,
        req_bytes=tier.cfg.req_bytes,
        dram_cache_bytes=tier.cfg.dram_cache_bytes,
        max_inflight=tier.cfg.max_inflight)


def _assert_sharded_replay(tier: ShardedTier) -> None:
    """Every rank's port-tagged trace AND every peer-link lane's
    single-stream trace replay within 1% of the scalar oracle."""
    for r, t in enumerate(tier.ranks):
        if t.ops:
            np.testing.assert_allclose(np.asarray(t.op_ns),
                                       _replay_rank(t), rtol=0.01)
        if tier.peer_ops[r]:
            np.testing.assert_allclose(np.asarray(tier.peer_op_ns[r]),
                                       _replay_peer(tier, r), rtol=0.01)


def test_rank_tagged_traces_replay_against_oracle():
    """Direct tier-level churn: writes stripe to home ranks, restores
    cross the peer link, and all 2N traces (N rank topologies + N peer
    lanes) replay within 1%."""
    tier = ShardedTier(2, TierConfig(topology=("dram", "ssd-fast")))
    for i in range(8):
        tier.write_entry(i, ENTRY)
    owners = {tier._owner[i] for i in range(8)}
    assert owners == {0, 1}                  # hash striping uses both
    tier.advance(5e5)
    for i in range(8):
        tier.read_entry(i, ENTRY)
    tier.advance(5e5)
    for i in range(0, 8, 2):
        tier.free_entry(i)
    c = tier.counters
    assert c["peer_fetches"] == 8 and c["peer_bytes"] > 0
    assert c["mirror_writes"] == 8           # first share mirrors once
    _assert_sharded_replay(tier)


def test_async_rank_traces_replay_against_oracle():
    """The async path (handles spanning rank media + peer link) keeps
    every trace independently replayable too."""
    tier = ShardedTier(2, TierConfig(topology=("dram", "ssd-fast")))
    handles = [tier.write_entry_async(i, ENTRY) for i in range(6)]
    while not all(tier.poll(h) for h in handles):
        tier.advance(1e4)
    handles = [tier.read_entry_async(i, ENTRY) for i in range(6)]
    while not all(tier.poll(h) for h in handles):
        tier.advance(1e4)
    assert all(getattr(h, "rank", None) in (0, 1) for h in handles)
    assert tier.counters["peer_fetches"] == 6
    assert tier.inflight_ops() == 0
    _assert_sharded_replay(tier)


@needs2
def test_serving_rank_traces_replay_under_load(mesh_ctx):
    """End to end: a 2-rank engine with the ShardedTier under an
    open-loop trace completes everything, surfaces the shard telemetry,
    and every rank + peer-lane trace replays within 1%."""
    from repro.serving import loadgen
    from repro.serving.loadgen import LoadConfig
    eng = _build_engine(tp=2, n_slots=4, tier=True)
    lc = LoadConfig(n_arrivals=16, rate_rps=8000.0, arrival="bursty",
                    n_prompts=8, prompt_len_choices=(8, 16),
                    max_new_choices=(4, 8), seed=0)
    handles, depths = loadgen.drive_open_loop(eng, loadgen.make_trace(lc),
                                              max_ticks=4000)
    metrics = loadgen.summarize(eng, handles, depths, lc)
    assert metrics.completed == 16 and metrics.lost_requests == 0
    assert eng.stats["mesh_ranks"] == 2
    assert eng.stats["flushes"] > 0
    _assert_sharded_replay(eng.tier)


# ------------------------------------- shared-prefix restore economics

def test_cross_rank_restore_cheaper_than_n_cold_restores():
    """The tentpole placement claim: restoring a zipf-shared prefix on
    an N-rank tier (one home-rank media fetch + one peer-link hop)
    is strictly cheaper than N independent cold restores of the same
    pages — for both 2 and 4 ranks, and the advantage grows with N."""
    advantages = {}
    for n in (2, 4):
        sharded = ShardedTier(n, TierConfig(topology=("ssd-fast",),
                                            sr_enabled=False))
        sharded.write_entry("prefix", ENTRY)     # flushed ONCE
        assert sum(t.counters["writes"] for t in sharded.ranks) == 1
        sharded.advance(1e6)
        shared_ns = sharded.read_entry("prefix", ENTRY)
        assert not sharded.last_entry_failed
        # the baseline: every rank keeps its own copy on its own ports
        # and cold-restores it independently
        cold_ns = 0.0
        for _ in range(n):
            solo = CxlTier(TierConfig(topology=("ssd-fast",),
                                      sr_enabled=False))
            solo.write_entry("prefix", ENTRY)
            solo.advance(1e6)
            cold_ns += solo.read_entry("prefix", ENTRY)
        assert shared_ns < cold_ns
        advantages[n] = cold_ns / shared_ns
        # the restore's mirror is the only extra copy: home + 1 mirror,
        # never one duplicate per rank
        writes = sum(t.counters["writes"] for t in sharded.ranks)
        assert writes == 2
    assert advantages[4] > advantages[2]


def test_peer_link_charges_partial_bytes():
    """The link hop carries only the other ranks' shards:
    nbytes * (N-1)/N, not a full duplicate of the entry."""
    tier = ShardedTier(4, TierConfig(topology=("ssd-fast",),
                                     sr_enabled=False))
    tier.write_entry("k", ENTRY)
    tier.read_entry("k", ENTRY)
    assert tier.counters["peer_bytes"] == (ENTRY * 3) // 4


# ------------------------------- placement invariants (hypothesis)

def _check_never_stranded(tier: ShardedTier, live, freed) -> None:
    """Every live key is resolvable to a rank that actually holds it;
    every freed key is gone from every rank; recorded owners are
    consistent with the holder sets."""
    for key in live:
        assert tier.has_entry(key)
        owner = tier._resolve_owner(key)
        assert owner is not None
        assert tier.ranks[owner].has_entry(key)
        held = tier._holders.get(key)
        assert held and owner in held
        for r in held:
            assert tier.ranks[r].has_entry(key)
    for key in freed:
        assert not tier.has_entry(key)
        assert key not in tier._owner and key not in tier._holders


_CHURN = st.one_of(
    st.tuples(st.just("write"), st.integers(0, 5),
              st.sampled_from((100, 5_000, ENTRY))),
    st.tuples(st.just("read"), st.integers(0, 5), st.just(ENTRY)),
    st.tuples(st.just("free"), st.integers(0, 5), st.just(0)),
    st.tuples(st.just("advance"), st.just(0), st.just(0)),
)


@given(st.lists(_CHURN, min_size=1, max_size=40),
       st.sampled_from((2, 3, 4)))
@settings(max_examples=25, deadline=None)
def test_rank_striped_placement_never_strands_entry(actions, n_ranks):
    """Random admit/flush/free/advance churn never strands an entry: a
    key some rank holds is always resolvable (and readable) through the
    facade, re-flushes collapse stale mirrors, frees reach every copy —
    and all the traces still replay at the end."""
    tier = ShardedTier(n_ranks, TierConfig(topology=("dram", "ssd-fast")))
    live, freed = set(), set()
    for op, key, nbytes in actions:
        if op == "write":
            tier.write_entry(key, nbytes)
            assert not tier.last_entry_failed
            live.add(key)
            freed.discard(key)
        elif op == "read":
            tier.read_entry(key, nbytes)   # cold-read allocates (CxlTier
            assert not tier.last_entry_failed   # parity), so key is live
            live.add(key)
            freed.discard(key)
        elif op == "free":
            tier.free_entry(key)
            live.discard(key)
            freed.add(key)
        else:
            tier.advance(1e5)
        _check_never_stranded(tier, live, freed)
    _assert_sharded_replay(tier)


def test_sharded_tier_validation_and_snapshot():
    with pytest.raises(ValueError, match="n_ranks >= 2"):
        ShardedTier(1, TierConfig())
    with pytest.raises(ValueError, match="fault_rank"):
        ShardedTier(2, TierConfig(), fault_rank=5)
    tier = ShardedTier(2, TierConfig(topology=("dram", "ssd-fast")))
    tier.write_entry("a", ENTRY)
    tier.read_entry("a", ENTRY)
    snap = tier.snapshot()
    assert snap["n_ranks"] == 2 and snap["peer_fetches"] == 1
    # CxlTier-shaped: the serving stats line reads these unconditionally
    for key in ("media", "writes", "async_writes", "write_ns", "reads",
                "async_reads", "sr_hit_rate", "gc_events", "frees",
                "segment_reuses", "placement", "ports"):
        assert key in snap
    rows = tier.port_stats()
    assert [r["rank"] for r in rows] == [0, 0, 1, 1]
    assert PEER_LINK_MEDIA == "dram"
