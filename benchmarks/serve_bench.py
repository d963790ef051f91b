"""Serving hot-path benchmark: legacy host path vs device-resident engine.

Measures, for the same CPU config and request mix:

 * prefill tokens/sec  — prompt ingestion (per-token decode_step dispatches
   on the legacy path vs chunked in-graph cache writes on the new path)
 * decode tokens/sec   — steady-state continuous-batching throughput
   (per-tick logits transfer + host sampling vs fused on-device sampling)
 * p50/p99 tick latency over decode-only engine ticks
 * prefix reuse        — a resubmitted rid must be served via page restore
   with zero prefill dispatches (new path)

``--cxl-tier`` additionally sweeps the CXL-timed memory tier: media bins
(dram / ssd-fast / ssd-slow x SR on/off), the multi-root-port
**topology axis** (1-port baseline vs 2-/3-port heterogeneous topologies
x placement policy), and the **scheduler axis** (blocking vs
completion-based async restores; FIFO vs preempt+swap under slot
pressure). The same serving traffic is charged against the simulated
endpoints; per-restore stall / SR hit rate / per-port stats land in a
``cxl_tier`` section with acceptance gates that SR-on beats SR-off per
bin, that multi-port overlap strictly reduces aggregate restore stall vs
the 1-port baseline, that async restore strictly reduces aggregate stall
vs blocking on identical traffic, that preempt+swap completes strictly
more requests per simulated second than FIFO under pressure, and that
every (port-tagged, async) op trace replays within 1% of the scalar
oracle.

``--load`` adds the open-loop load axis (closed vs continuous vs
preempt+swap admission on one seeded bursty trace, gated on goodput)
and, nested under it, the **fault axis**: a mixed-family fleet (MoE /
hybrid / xLSTM) runs one identical arrival trace healthy and under one
identical endpoint-fault trace (transient + degrade + hot-remove),
gated on zero lost requests, faulted goodput within a bounded factor of
healthy, bounded retries, and fault-annotated replay within 1%.

Emits BENCH_serve.json with both sides + speedups so the perf trajectory
has a serving datapoint. Run:

  PYTHONPATH=src python benchmarks/serve_bench.py --smoke --cxl-tier \
      --load --out BENCH_serve.json
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

# the --shard axis compares 1-rank vs 2-/4-rank sharded serving, which
# needs >= 4 host devices; XLA only reads the flag at first jax init, so
# (like repro.launch.dryrun) it must be set before any jax import — main
# runs far too late
if "--shard" in sys.argv and \
        "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_"
                                 "count=4").strip()

import numpy as np


def _load_by_path(modname: str, relpath: str):
    """Load one repo module standalone, by file path.

    ``repro.serving.stats`` / ``repro.serving.loadgen`` keep their
    module-level imports stdlib+numpy-only precisely so this works in
    the jax-free docs CI job: loading them by path skips the ``repro``
    package ``__init__`` (which pulls jax), letting SCHEMA_KEYS below
    derive from the dataclass field lists — the single source of truth.
    """
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        modname, os.path.join(root, relpath))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod      # dataclasses resolves cls.__module__
    spec.loader.exec_module(mod)
    return mod


_STATS = _load_by_path("_serving_stats", "src/repro/serving/stats.py")
_LOADGEN = _load_by_path("_serving_loadgen", "src/repro/serving/loadgen.py")

# Canonical BENCH_serve.json schema, section by section. This is the
# single source of truth three consumers pin against:
#  * main() fails if the emitted JSON drifts from it (check_schema),
#  * tools/check_docs.py fails if the schema table in
#    docs/ARCHITECTURE.md drifts from it (the CI docs job),
#  * downstream artifact readers can import it.
# The engine_stats / load_config / load_scenario sections are *derived*
# from the owning dataclasses' field lists (repro.serving.stats /
# repro.serving.loadgen), so the engine's telemetry, the bench artifact
# and the docs tables cannot drift independently.
SCHEMA_KEYS = {
    "top": ("bench", "arch", "config", "legacy_host_path",
            "device_resident", "speedup", "acceptance", "cxl_tier",
            "load", "shard", "placement", "replay_gates"),
    "engine": ("prefill_tok_s", "decode_tok_s", "prefill_tok_s_best",
               "decode_tok_s_best", "prefill_tokens_per_run",
               "decode_tokens_per_run", "prefill_dispatches_per_run",
               "decode_dispatches_per_run", "p50_tick_ms", "p99_tick_ms",
               "runs", "store_bytes", "store_evictions"),
    "device_extra": ("resubmit_prefill_dispatches", "prefix_hits",
                     "prefix_hit_rate"),
    "cxl_tier": ("config", "media_bins", "topology", "scheduler",
                 "kv_quant", "acceptance"),
    "tier_scenario": ("restores", "restore_stall_ns_total",
                      "restore_stall_ns_per_restore", "sr_hit_rate",
                      "sr_prefetch_pages", "flush_write_ns_total",
                      "store_queue_occupancy", "flushes_deferred",
                      "gc_events", "trace_ops"),
    "topology_extra": ("ports", "promotions", "demotions",
                       "replay_within_1pct"),
    "scheduler": ("restore", "pressure"),
    "sched_scenario": ("completed", "sim_time_ns", "req_per_sim_s",
                       "restore_stall_ns_total", "restore_inflight_ns",
                       "overlap_ratio", "preemptions", "swap_out_bytes",
                       "swap_in_bytes", "inflight_peak", "prefix_hits",
                       "replay_within_1pct"),
    "kv_quant": ("config", "modes", "tokens", "acceptance"),
    "kvq_scenario": ("restores", "restore_stall_ns_total",
                     "restore_stall_ns_per_restore", "flush_write_ns_total",
                     "read_bytes", "write_bytes", "prefetch_bytes",
                     "store_bytes", "replay_within_1pct"),
    "engine_stats": _STATS.EngineStats.field_names(),
    "load": ("config", "batching", "scheduling", "fault", "acceptance"),
    "load_config": _LOADGEN.LoadConfig.field_names()
    + ("n_slots", "max_seq", "max_ticks"),
    "load_scenario": _STATS.LoadMetrics.field_names()
    + ("engine", "replay_within_1pct"),
    "fault": ("config", "fleet", "acceptance"),
    "fault_config_extra": ("fleet", "topology", "trace"),
    "shard": ("config", "ranks", "acceptance"),
    "shard_scenario": ("mesh_ranks", "completed", "lost_requests",
                       "prefix_hits", "restore_stall_ns_total",
                       "stall_ratio_vs_1rank", "tier_writes",
                       "peer_fetches", "peer_bytes", "peer_fetch_ns",
                       "mirror_writes", "rank_remaps",
                       "token_identity_vs_1rank", "replay_within_1pct"),
    "placement": ("config", "churn", "shared", "acceptance"),
    "placement_churn_scenario": ("restores", "restore_stall_ns_total",
                                 "promotions", "demotions",
                                 "replay_within_1pct"),
    "placement_shared_scenario": ("restores", "restore_stall_ns_total",
                                  "peer_bytes", "rehomes",
                                  "multi_source_reads",
                                  "replay_within_1pct"),
    "replay_gate": ("where", "engine", "ok", "wall_ratio"),
}


def check_schema(out) -> list:
    """Compare an emitted BENCH_serve.json dict against SCHEMA_KEYS.

    Returns a list of drift messages (empty when the artifact matches);
    every key set is compared exactly, both directions, so adding or
    removing an emitted key without updating SCHEMA_KEYS (and the docs
    table checked against it) fails the bench.
    """
    errs = []

    def diff(where, got, want):
        got, want = set(got), set(want)
        if got != want:
            errs.append(f"{where}: +{sorted(got - want)} "
                        f"-{sorted(want - got)}")

    top = set(SCHEMA_KEYS["top"])
    for optional in ("cxl_tier", "load", "shard", "placement",
                     "replay_gates"):
        if optional not in out:
            top.discard(optional)
    diff("top-level", out, top)
    if "legacy_host_path" in out:
        diff("legacy_host_path", out["legacy_host_path"],
             SCHEMA_KEYS["engine"])
    if "device_resident" in out:
        diff("device_resident", out["device_resident"],
             SCHEMA_KEYS["engine"] + SCHEMA_KEYS["device_extra"])
    tier = out.get("cxl_tier")
    if tier is not None:
        diff("cxl_tier", tier, SCHEMA_KEYS["cxl_tier"])
        for b, per in tier.get("media_bins", {}).items():
            for mode, scen in per.items():
                diff(f"media_bins[{b}][{mode}]", scen,
                     SCHEMA_KEYS["tier_scenario"])
        for t, per in tier.get("topology", {}).items():
            for mode, scen in per.items():
                diff(f"topology[{t}][{mode}]", scen,
                     SCHEMA_KEYS["tier_scenario"]
                     + SCHEMA_KEYS["topology_extra"])
        sched = tier.get("scheduler", {})
        diff("cxl_tier.scheduler", sched, SCHEMA_KEYS["scheduler"])
        for axis in ("restore", "pressure"):
            for mode, scen in sched.get(axis, {}).items():
                diff(f"scheduler[{axis}][{mode}]", scen,
                     SCHEMA_KEYS["sched_scenario"])
        kvq = tier.get("kv_quant")
        if kvq is not None:
            diff("cxl_tier.kv_quant", kvq, SCHEMA_KEYS["kv_quant"])
            for mode, scen in kvq.get("modes", {}).items():
                diff(f"kv_quant.modes[{mode}]", scen,
                     SCHEMA_KEYS["kvq_scenario"])
    load = out.get("load")
    if load is not None:
        load_keys = set(SCHEMA_KEYS["load"])
        if "fault" not in load:
            load_keys.discard("fault")
        diff("load", load, load_keys)
        diff("load.config", load.get("config", {}),
             SCHEMA_KEYS["load_config"])
        for axis in ("batching", "scheduling"):
            for mode, scen in load.get(axis, {}).items():
                diff(f"load[{axis}][{mode}]", scen,
                     SCHEMA_KEYS["load_scenario"])
                diff(f"load[{axis}][{mode}].engine", scen.get("engine", {}),
                     SCHEMA_KEYS["engine_stats"])
        fault = load.get("fault")
        if fault is not None:
            diff("load.fault", fault, SCHEMA_KEYS["fault"])
            diff("load.fault.config", fault.get("config", {}),
                 SCHEMA_KEYS["load_config"]
                 + SCHEMA_KEYS["fault_config_extra"])
            for arch, per in fault.get("fleet", {}).items():
                for mode, scen in per.items():
                    diff(f"load.fault[{arch}][{mode}]", scen,
                         SCHEMA_KEYS["load_scenario"])
                    diff(f"load.fault[{arch}][{mode}].engine",
                         scen.get("engine", {}),
                         SCHEMA_KEYS["engine_stats"])
    shard = out.get("shard")
    if shard is not None:
        diff("shard", shard, SCHEMA_KEYS["shard"])
        for mode, scen in shard.get("ranks", {}).items():
            diff(f"shard.ranks[{mode}]", scen,
                 SCHEMA_KEYS["shard_scenario"])
    placement = out.get("placement")
    if placement is not None:
        diff("placement", placement, SCHEMA_KEYS["placement"])
        for mode, scen in placement.get("churn", {}).items():
            diff(f"placement.churn[{mode}]", scen,
                 SCHEMA_KEYS["placement_churn_scenario"])
        for mode, scen in placement.get("shared", {}).items():
            diff(f"placement.shared[{mode}]", scen,
                 SCHEMA_KEYS["placement_shared_scenario"])
    for i, gate in enumerate(out.get("replay_gates", ())):
        diff(f"replay_gates[{i}]", gate, SCHEMA_KEYS["replay_gate"])
    return errs


def _build(arch: str, seed: int, vocab: int, dtype: str):
    import dataclasses

    import jax
    from repro.configs import registry
    from repro.configs.base import MeshConfig, RunConfig, SHAPES
    from repro.models import model as M

    cfg = registry.smoke(arch)
    if vocab:
        # the 256-token smoke vocab hides the per-tick [slots, V] logits
        # round-trip the rewrite removes; serve with a serving-scale vocab
        cfg = dataclasses.replace(cfg, vocab_size=vocab)
    if dtype:
        # on CPU bf16 matmuls are software-emulated, which inflates the
        # compute both engines share and buries the hot-path overheads this
        # bench isolates; default to the backend-native f32
        cfg = dataclasses.replace(cfg, dtype=dtype)
    rc = RunConfig(model=cfg, shape=SHAPES["decode_32k"], mesh=MeshConfig())
    params = M.init_model(jax.random.PRNGKey(seed), cfg)
    return cfg, rc, params


def _drive(eng, requests, *, max_ticks: int = 10_000):
    """Run the engine to drain, recording per-tick wall times and whether
    the tick performed any prefill work (admission)."""
    for req in requests:
        eng.submit(req)
    ticks = []
    while (eng.queue or any(s is not None for s in eng.slots)
           or eng.scheduler.busy()) and len(ticks) < max_ticks:
        pf0 = eng.stats["prefill_dispatches"] + eng.stats["prefix_hits"]
        t0 = time.perf_counter()
        eng.step()
        dt = time.perf_counter() - t0
        admitted = (eng.stats["prefill_dispatches"]
                    + eng.stats["prefix_hits"]) != pf0
        ticks.append((dt, admitted))
    eng.flusher.maybe_flush()
    return ticks


def _reset_stats(eng):
    for k, v in eng.stats.items():
        eng.stats[k] = [] if isinstance(v, list) else \
            0.0 if isinstance(v, float) else 0


def _timed_pass(eng, reqs, n_requests, max_new):
    """One timed pass; returns (metrics, steady decode tick times).

    Phases, with explicit sync at each boundary so async dispatch is
    billed where the work belongs (identical accounting for both
    engines):

      admit    — submit all requests, one step() admits + prefills every
                 slot and runs the first decode tick
      steady   — full-occupancy decode ticks, strictly before the first
                 retirement: the "decode tokens/sec" window
      probe    — a few ticks with an explicit sync after each, so p50/p99
                 tick latency means tick *completion* for both engines
                 (the device-resident path otherwise only enqueues work)
      drain    — the remaining ticks + retirements + flushes (untimed)
    """
    import jax

    assert len(reqs) == eng.n_slots, "steady window needs full occupancy"
    _reset_stats(eng)
    for req in reqs:
        eng.submit(req)
    eng.step()
    jax.block_until_ready(eng.last_tokens)
    prefill_t = max(eng.stats["prefill_time_s"], 1e-9)

    probe = min(16, max(max_new - 4, 0))
    steady = max(max_new - 3 - probe, 1)   # + probe: before any retirement
    t0 = time.perf_counter()
    for _ in range(steady):
        eng.step()
    jax.block_until_ready(eng.last_tokens)
    decode_t = max(time.perf_counter() - t0, 1e-9)
    decode_tokens = steady * n_requests

    tick_times = []
    for _ in range(probe):
        t1 = time.perf_counter()
        eng.step()
        jax.block_until_ready(eng.last_tokens)
        tick_times.append(time.perf_counter() - t1)

    _drive(eng, [])                    # drain: retires + flushes, untimed
    return ({
        "prefill_tokens": eng.stats["prefill_tokens"],
        "prefill_time_s": prefill_t,
        "prefill_tok_s": eng.stats["prefill_tokens"] / prefill_t,
        "prefill_dispatches": eng.stats["prefill_dispatches"],
        "decode_tokens": int(decode_tokens),
        "decode_time_s": decode_t,
        "decode_tok_s": decode_tokens / decode_t,
        "decode_dispatches": eng.stats["decode_dispatches"],
    }, tick_times)


def _summarize(runs, all_ticks, eng):
    """Median-of-N per phase over interleaved repeats: the engines share
    the box tick-for-tick, so the median is robust to interference
    outliers on either side (per-run numbers and the best are recorded
    too)."""
    best_p = max(r["prefill_tok_s"] for r in runs)
    best_d = max(r["decode_tok_s"] for r in runs)
    med_p = sorted(r["prefill_tok_s"] for r in runs)[len(runs) // 2]
    med_d = sorted(r["decode_tok_s"] for r in runs)[len(runs) // 2]
    decode_ticks = np.asarray(all_ticks) * 1e3
    return {
        "prefill_tok_s": round(med_p, 2),
        "decode_tok_s": round(med_d, 2),
        "prefill_tok_s_best": round(best_p, 2),
        "decode_tok_s_best": round(best_d, 2),
        "prefill_tokens_per_run": runs[0]["prefill_tokens"],
        "decode_tokens_per_run": runs[0]["decode_tokens"],
        "prefill_dispatches_per_run": runs[0]["prefill_dispatches"],
        "decode_dispatches_per_run": runs[0]["decode_dispatches"],
        "p50_tick_ms": round(float(np.percentile(decode_ticks, 50)), 4)
        if decode_ticks.size else None,
        "p99_tick_ms": round(float(np.percentile(decode_ticks, 99)), 4)
        if decode_ticks.size else None,
        "runs": [{k: (round(v, 6) if isinstance(v, float) else v)
                  for k, v in r.items()} for r in runs],
        "store_bytes": eng.stats["store_bytes"],
        "store_evictions": eng.stats["store_evictions"],
    }


def bench_pair(params, cfg, rc, *, n_slots: int, max_seq: int,
               prompt_len: int, max_new: int, n_requests: int,
               prefill_chunk: int, temperature: float, seed: int,
               repeats: int = 4):
    """Bench legacy + device-resident engines with interleaved repeats on
    identical prompt sets (noise on a shared box hits both sides alike)."""
    from repro.serving.engine import Request, ServingEngine

    engines = {
        "legacy_host_path": ServingEngine(
            params, cfg, rc, n_slots=n_slots, max_seq=max_seq,
            temperature=temperature, seed=seed,
            prefill_chunk=prefill_chunk, legacy_host_path=True,
            sync_prefill=True),
        "device_resident": ServingEngine(
            params, cfg, rc, n_slots=n_slots, max_seq=max_seq,
            temperature=temperature, seed=seed,
            prefill_chunk=prefill_chunk, sync_prefill=True),
    }
    rng = np.random.default_rng(seed)

    def batch(rid0):
        # fresh rids AND fresh prompts per repeat so the device-resident
        # engine can never serve a timed pass from retired pages
        prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
                   for _ in range(n_requests)]
        return lambda: [Request(rid=rid0 + i, prompt=p,
                                max_new_tokens=max_new)
                        for i, p in enumerate(prompts)]

    warm = batch(100_000)
    for eng in engines.values():
        _drive(eng, warm())      # compiles every hot-path trace

    runs = {k: [] for k in engines}
    ticks = {k: [] for k in engines}
    first_batch = None
    for rep in range(max(repeats, 1)):
        mk = batch(1000 * rep)
        if first_batch is None:
            first_batch = mk()
        for name, eng in engines.items():
            r, t = _timed_pass(eng, mk(), n_requests, max_new)
            runs[name].append(r)
            ticks[name].extend(t)

    out = {name: _summarize(runs[name], ticks[name], eng)
           for name, eng in engines.items()}

    # prefix-reuse probe: resubmit a timed rid + prompt to the new engine
    eng = engines["device_resident"]
    pf0 = eng.stats["prefill_dispatches"]
    hit0 = eng.stats["prefix_hits"]
    probe = first_batch[0]
    _drive(eng, [Request(rid=probe.rid, prompt=probe.prompt,
                         max_new_tokens=max_new)])
    dev = out["device_resident"]
    dev["resubmit_prefill_dispatches"] = (eng.stats["prefill_dispatches"]
                                          - pf0)
    dev["prefix_hits"] = eng.stats["prefix_hits"] - hit0
    dev["prefix_hit_rate"] = float(dev["prefix_hits"])
    return out


def _tier_scenario(params, cfg, rc, tier, prompts, *, n_slots, max_seq,
                   max_new, prefill_chunk, seed, step_ns, label):
    """Serve -> settle -> resubmit against one tier; return its metrics.

    Serve a batch (retire -> flush populates the tier), settle the
    staging ring into the cold tier (the EPs may defer flush admission
    around internal tasks), then resubmit the same prompts — every
    resubmit restores through a simulated cold-tier fetch whose stall is
    charged per request. Identical prompts across scenarios, so the only
    variables are the tier's topology/media/placement and the SR engine.
    """
    from repro.serving.engine import Request, ServingEngine

    eng = ServingEngine(params, cfg, rc, n_slots=n_slots, max_seq=max_seq,
                        temperature=0.0, seed=seed,
                        prefill_chunk=prefill_chunk, cxl_tier=tier)
    _drive(eng, [Request(rid=i, prompt=p, max_new_tokens=max_new)
                 for i, p in enumerate(prompts)])
    for _ in range(500):               # settle staging into the tier
        if not eng.flusher.pending:
            break
        tier.advance(step_ns)
        eng.stats["flushes"] += eng.flusher.maybe_flush()
    if eng.flusher.pending:
        # restores would hit the free staging path and the sweep would
        # measure the wrong regime — fail loudly instead
        sys.exit(f"FAIL: cxl-tier staging did not drain into the cold "
                 f"tier ({label}, {len(eng.flusher.pending)} pending)")
    _drive(eng, [Request(rid=1000 + i, prompt=p, max_new_tokens=max_new)
                 for i, p in enumerate(prompts)])
    snap = tier.snapshot()
    hits = eng.stats["prefix_hits"]
    return {
        "restores": hits,
        "restore_stall_ns_total":
            round(eng.stats["restore_stall_ns"], 1),
        "restore_stall_ns_per_restore":
            round(eng.stats["restore_stall_ns"] / max(hits, 1), 1),
        "sr_hit_rate": round(snap["sr_hit_rate"], 4),
        "sr_prefetch_pages": snap["prefetches"],
        "flush_write_ns_total": round(snap["write_ns"], 1),
        "store_queue_occupancy":
            round(eng.stats["tier_store_occupancy"], 4),
        "flushes_deferred": eng.stats["flushes_deferred"],
        "gc_events": snap["gc_events"],
        "trace_ops": snap["trace_ops"],
    }


# every replay gate priced this run: where it ran, which engine priced
# it, whether it held, and the scalar/vectorized wall-time ratio — main()
# emits the list as the artifact's "replay_gates" section
_REPLAY_GATES = []


def _trace_replay(ops, op_ns, *, media, topology=None, sr=True, ds=True,
                  req_bytes=256, dram_cache_bytes=64 << 10,
                  max_inflight=4, faults=None):
    """Price one recorded page trace; returns (ok, engine, wall_ratio).

    The scalar oracle (``replay_page_trace``) is always run — it is the
    ground truth the 1% gate compares against. When the trace is
    eligible for the vectorized closed form (DRAM-class media on every
    lane, no fault annotations — ``page_trace_closed_form`` rejects the
    rest), that engine prices the gate too and the ratio of the two
    wall times is recorded; ineligible traces fall back to the scalar
    pricing with ratio 1.0.
    """
    from repro.sim.engine import replay_page_trace

    t0 = time.perf_counter()
    oracle = replay_page_trace(
        ops, media=media, topology=topology, sr=sr, ds=ds,
        req_bytes=req_bytes, dram_cache_bytes=dram_cache_bytes,
        max_inflight=max_inflight, faults=faults)
    t_scalar = time.perf_counter() - t0
    engine, ratio, priced = "scalar", 1.0, oracle
    if faults is None:
        from repro.sim.vector import page_trace_closed_form
        try:
            t0 = time.perf_counter()
            priced = page_trace_closed_form(
                ops, topology if topology is not None else media,
                ds=ds, req_bytes=req_bytes, max_inflight=max_inflight)
            engine = "vectorized"
            ratio = t_scalar / max(time.perf_counter() - t0, 1e-9)
        except ValueError:
            priced = oracle
    ok = bool(np.allclose(np.asarray(op_ns), priced, rtol=0.01, atol=1e-6))
    if engine == "vectorized":
        # the closed form must itself sit on the oracle, not just on the
        # live charges — a drifting engine must not price gates
        ok = ok and bool(np.allclose(priced, oracle, rtol=0.01, atol=1e-6))
    return ok, engine, ratio


def _replay_gate(tier, where: str = "") -> bool:
    """Differential gate: replay every op trace the tier recorded within
    1% — the single rank trace of a ``CxlTier``, or every rank's
    port-tagged trace plus every peer-link lane of a ``ShardedTier``.
    Each priced trace appends a record to ``_REPLAY_GATES``."""
    tiers = getattr(tier, "ranks", [tier])
    ok = True
    for i, t in enumerate(tiers):
        if not t.ops:
            continue
        good, engine, ratio = _trace_replay(
            t.ops, t.op_ns, media=t.cfg.media_name,
            topology=t.cfg.port_medias if t.cfg.tagged else None,
            sr=t.cfg.sr_enabled, ds=t.cfg.ds_enabled,
            req_bytes=t.cfg.req_bytes,
            dram_cache_bytes=t.cfg.dram_cache_bytes,
            max_inflight=t.cfg.max_inflight, faults=t.cfg.faults)
        label = where if len(tiers) == 1 else f"{where}/rank{i}"
        _REPLAY_GATES.append({"where": label, "engine": engine,
                              "ok": good, "wall_ratio": round(ratio, 2)})
        ok &= good
    for r in range(getattr(tier, "n_ranks", 0)):
        if not tier.peer_ops[r]:
            continue
        good, engine, ratio = _trace_replay(
            tier.peer_ops[r], tier.peer_op_ns[r], media=tier.peer_media,
            sr=False, ds=False, req_bytes=tier.cfg.req_bytes,
            dram_cache_bytes=tier.cfg.dram_cache_bytes,
            max_inflight=tier.cfg.max_inflight)
        _REPLAY_GATES.append({"where": f"{where}/peer{r}", "engine": engine,
                              "ok": good, "wall_ratio": round(ratio, 2)})
        ok &= good
    return ok


# topology axis: 1-port baseline vs multi-port heterogeneous topologies
# (overlapping per-port lanes) x placement policy. Each scenario runs
# SR on and (for the striped set) SR off on identical traffic.
TOPOLOGIES = {
    "1-port": {"topology": ("ssd-fast",), "placement": "striped"},
    # homogeneous pair: same media as the baseline, so any stall
    # reduction is attributable to per-port overlap alone (the hetero
    # scenario below would also win just from the faster DRAM lane)
    "2-port-ssd": {"topology": ("ssd-fast", "ssd-fast"),
                   "placement": "striped"},
    "2-port-hetero": {"topology": ("dram", "ssd-fast"),
                      "placement": "striped"},
    "3-port-hetero": {"topology": ("dram", "ssd-fast", "ssd-slow"),
                      "placement": "striped"},
    "3-port-hashed": {"topology": ("dram", "ssd-fast", "ssd-slow"),
                      "placement": "hashed"},
    "3-port-hotness": {"topology": ("dram", "ssd-fast", "ssd-slow"),
                       "placement": "hotness"},
}


def _sched_metrics(eng, tier) -> dict:
    """Scheduler-axis metrics for one finished engine run."""
    sim_ns = max(tier.topo.now, 1e-9)
    return {
        "completed": len(eng.finished),
        "sim_time_ns": round(tier.topo.now, 1),
        "req_per_sim_s": round(len(eng.finished) / sim_ns * 1e9, 2),
        "restore_stall_ns_total": round(eng.stats["restore_stall_ns"], 1),
        "restore_inflight_ns": round(eng.stats["restore_inflight_ns"], 1),
        "overlap_ratio": round(eng.stats["restore_overlap_ratio"], 4),
        "preemptions": eng.stats["preemptions"],
        "swap_out_bytes": eng.stats["swap_out_bytes"],
        "swap_in_bytes": eng.stats["swap_in_bytes"],
        "inflight_peak": eng.stats["sched_inflight_peak"],
        "prefix_hits": eng.stats["prefix_hits"],
        "replay_within_1pct": _replay_gate(tier, "scheduler"),
    }


def bench_scheduler(params, cfg, rc, *, n_slots: int, max_seq: int,
                    prompt_len: int, max_new: int, prefill_chunk: int,
                    seed: int, step_ns: float = 100_000.0):
    """The async/preemption axis of the request-lifecycle scheduler.

    Axis 1 (``restore``) serves -> settles -> resubmits identical traffic
    with blocking vs completion-based async restores; the gate is that
    async mode's aggregate restore stall is strictly below blocking (the
    fetch overlaps decode instead of stalling the batch). Axis 2
    (``pressure``) pins long low-priority requests in every slot with a
    queue of short high-priority requests behind them, run for a fixed
    tick horizon under FIFO vs preempt+swap; the gate is that preemption
    completes strictly more requests per simulated second. Both gates
    also require every async op trace to replay within 1% of the scalar
    oracle. Returns ``(section, acceptance)``.
    """
    from repro.core.tier import CxlTier, TierConfig
    from repro.serving.engine import Request, ServingEngine

    rng = np.random.default_rng(seed)
    n_requests = n_slots * 2
    prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
               for _ in range(n_requests)]
    kw = dict(n_slots=n_slots, max_seq=max_seq, temperature=0.0,
              seed=seed, prefill_chunk=prefill_chunk)

    restore = {}
    for mode in ("blocking", "async"):
        tier = CxlTier(TierConfig(media="ssd-fast"))
        eng = ServingEngine(params, cfg, rc, cxl_tier=tier,
                            cxl_async=(mode == "async"), **kw)
        _drive(eng, [Request(rid=i, prompt=p, max_new_tokens=max_new)
                     for i, p in enumerate(prompts)])
        for _ in range(500):           # settle staging into the cold tier
            if not eng.flusher.pending:
                break
            tier.advance(step_ns)
            eng.flusher.maybe_flush()
        if eng.flusher.pending:
            sys.exit(f"FAIL: scheduler staging did not drain ({mode})")
        _drive(eng, [Request(rid=1000 + i, prompt=p, max_new_tokens=max_new)
                     for i, p in enumerate(prompts)])
        restore[mode] = _sched_metrics(eng, tier)

    # pressure scenario: every slot pinned by a long low-priority decode
    # (admitted and running before the short high-priority work arrives),
    # then a fixed simulated horizon too short for any long to finish —
    # FIFO pays for head-of-line blocking in completed requests, the
    # preempting scheduler swaps the longs out and serves the shorts
    long_new = min(6 * max_new, max_seq - 2 - prompt_len)
    horizon = max(long_new - 16, 2 * max_new)
    n_short = n_slots * 2
    long_prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
                    for _ in range(n_slots)]
    short_prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
                     for _ in range(n_short)]
    pressure = {}
    for mode, policy in (("fifo", "none"), ("preempt_swap", "swap")):
        tier = CxlTier(TierConfig(media="ssd-fast"))
        eng = ServingEngine(params, cfg, rc, cxl_tier=tier, cxl_async=True,
                            preempt_policy=policy, **kw)
        for i, p in enumerate(long_prompts):
            eng.submit(Request(rid=i, prompt=p, priority=0,
                               max_new_tokens=long_new))
        eng.step(); eng.step()      # longs admitted and decoding
        for i, p in enumerate(short_prompts):
            eng.submit(Request(rid=100 + i, prompt=p, priority=1,
                               max_new_tokens=4))
        eng.run(max_ticks=horizon)
        pressure[mode] = _sched_metrics(eng, tier)

    acceptance = {
        "sched_async_stall_below_blocking":
            restore["async"]["restore_stall_ns_total"]
            < restore["blocking"]["restore_stall_ns_total"],
        "sched_async_all_resubmits_restored":
            restore["async"]["prefix_hits"] == n_requests,
        "sched_preempt_swap_higher_throughput":
            pressure["preempt_swap"]["req_per_sim_s"]
            > pressure["fifo"]["req_per_sim_s"],
        "sched_preempt_swap_preempted":
            pressure["preempt_swap"]["preemptions"] >= 1
            and pressure["preempt_swap"]["swap_in_bytes"] > 0,
        "sched_replay_within_1pct": all(
            scen["replay_within_1pct"]
            for per in (restore, pressure) for scen in per.values()),
    }
    return {"restore": restore, "pressure": pressure}, acceptance


def bench_cxl_tier(params, cfg, rc, *, n_slots: int, max_seq: int,
                   prompt_len: int, max_new: int, prefill_chunk: int,
                   seed: int, step_ns: float = 100_000.0):
    """Sweep the CXL-timed tier: media bins x SR, then the topology axis.

    Section 1 (``media_bins``) is the single-port sweep (dram / ssd-fast
    / ssd-slow x SR on/off). Section 2 (``topology``) sweeps multi-root-
    port topologies x placement policy on the same traffic, with the
    acceptance gate that multi-port overlap strictly reduces aggregate
    restore stall vs the 1-port baseline, and that every port-tagged op
    trace replays within 1% of the scalar oracle.
    """
    from repro.core.tier import CxlTier, TierConfig

    rng = np.random.default_rng(seed)
    n_requests = n_slots * 2
    prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
               for _ in range(n_requests)]
    kw = dict(n_slots=n_slots, max_seq=max_seq, max_new=max_new,
              prefill_chunk=prefill_chunk, seed=seed, step_ns=step_ns)

    bins = {}
    for bin_name in ("dram", "ssd-fast", "ssd-slow"):
        per = {}
        for sr in (False, True):
            tier = CxlTier(TierConfig(media=bin_name, sr_enabled=sr))
            per["sr_on" if sr else "sr_off"] = _tier_scenario(
                params, cfg, rc, tier, prompts,
                label=f"{bin_name}/sr={sr}", **kw)
        bins[bin_name] = per

    topo = {}
    replay_within_1pct = True
    for name, spec in TOPOLOGIES.items():
        per = {}
        sr_modes = (False, True) if spec["placement"] == "striped" \
            else (True,)
        for sr in sr_modes:
            tier = CxlTier(TierConfig(topology=spec["topology"],
                                      placement=spec["placement"],
                                      sr_enabled=sr))
            res = _tier_scenario(params, cfg, rc, tier, prompts,
                                 label=f"{name}/sr={sr}", **kw)
            res["ports"] = [
                {k: p[k] for k in ("port", "media", "ep_reads",
                                   "ep_writes", "sr_hit_rate",
                                   "live_bytes", "gc_events")}
                for p in tier.port_stats()]
            res["promotions"] = tier.counters["promotions"]
            res["demotions"] = tier.counters["demotions"]
            res["replay_within_1pct"] = _replay_gate(
                tier, f"topology/{name}/sr={sr}")
            replay_within_1pct &= res["replay_within_1pct"]
            per["sr_on" if sr else "sr_off"] = res
        topo[name] = per

    acceptance = {
        f"sr_reduces_restore_stall[{b}]":
            bins[b]["sr_on"]["restore_stall_ns_total"]
            < bins[b]["sr_off"]["restore_stall_ns_total"]
        for b in ("ssd-fast", "ssd-slow")}
    acceptance["all_resubmits_restored"] = all(
        v["restores"] == n_requests
        for per in bins.values() for v in per.values()) and all(
        v["restores"] == n_requests
        for per in topo.values() for v in per.values())
    # the tentpole gates: per-port lanes overlapping inside each restore
    # must strictly beat the serialized single-port stream on the same
    # traffic. The homogeneous pair isolates overlap (identical media,
    # so only lane concurrency can reduce stall); the heterogeneous pair
    # is the paper's DRAM+SSD configuration (overlap + a faster lane).
    acceptance["multi_port_overlap_reduces_stall"] = (
        topo["2-port-ssd"]["sr_on"]["restore_stall_ns_total"]
        < topo["1-port"]["sr_on"]["restore_stall_ns_total"])
    acceptance["hetero_2port_beats_1port"] = (
        topo["2-port-hetero"]["sr_on"]["restore_stall_ns_total"]
        < topo["1-port"]["sr_on"]["restore_stall_ns_total"])
    acceptance["topology_replay_within_1pct"] = replay_within_1pct

    # the async/preemption axis: blocking vs async restores, FIFO vs
    # preempt+swap under pressure (gates merged into this acceptance)
    scheduler, sched_acceptance = bench_scheduler(
        params, cfg, rc, n_slots=n_slots, max_seq=max_seq,
        prompt_len=prompt_len, max_new=max_new,
        prefill_chunk=prefill_chunk, seed=seed, step_ns=step_ns)
    acceptance.update(sched_acceptance)
    return {
        "config": {"n_slots": n_slots, "n_requests": n_requests,
                   "prompt_len": prompt_len, "max_new_tokens": max_new,
                   "max_seq": max_seq, "tier_step_ns": step_ns,
                   "seed": seed},
        "media_bins": bins,
        "topology": topo,
        "scheduler": scheduler,
        "acceptance": acceptance,
    }


# Token-quality bound for the kv_quant axis: greedy decode with int8 KV
# should match bf16 token-for-token on the smoke configs; where int8
# rounding flips a near-tie logit the runs may diverge from that point,
# so the documented fallback gate is a positional match fraction over
# all generated tokens (see docs/ARCHITECTURE.md "KV page format").
KVQ_TOKEN_MATCH_MIN = 0.9


def bench_kv_quant(*, arch: str, vocab: int, n_slots: int, max_seq: int,
                   prompt_len: int, max_new: int, prefill_chunk: int,
                   seed: int, step_ns: float = 100_000.0):
    """The quantized-KV-page axis (``cxl_tier["kv_quant"]``).

    Runs the serve -> settle -> resubmit tier scenario twice on identical
    traffic against identical ``ssd-fast`` tiers: once with the bf16 page
    format (its own bf16 build — the ``--dtype`` default is the CPU-native
    f32, which would make "int8 vs bf16" a lie) and once with
    ``kv_quant="int8"``. Every flush/restore/SR fetch charges the tier the
    entry's actual byte count, so the int8 run's tier traffic is ~half.

    Acceptance gates (exit 1 from main on any failure):

     * int8 aggregate restore stall strictly below bf16,
     * flush+restore bytes ~ half of bf16 (ratio in [0.4, 0.6]; per-page
       fp32 scales add ~0.1% back),
     * greedy token identity vs bf16 — or the documented bounded-
       divergence fallback (match fraction >= ``KVQ_TOKEN_MATCH_MIN``),
     * both op traces replay within 1% of the scalar oracle.
    """
    from repro.core.tier import CxlTier, TierConfig
    from repro.serving.config import ServeConfig
    from repro.serving.engine import Request, ServingEngine

    cfg, rc, params = _build(arch, seed, vocab, "bfloat16")
    rng = np.random.default_rng(seed)
    n_requests = n_slots * 2
    prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
               for _ in range(n_requests)]

    def run_one(kv_quant: str):
        tier = CxlTier(TierConfig(media="ssd-fast"))
        eng = ServingEngine(params, cfg, rc, cxl_tier=tier,
                            config=ServeConfig(
                                n_slots=n_slots, max_seq=max_seq,
                                temperature=0.0, seed=seed,
                                prefill_chunk=prefill_chunk,
                                kv_quant=kv_quant))
        _drive(eng, [Request(rid=i, prompt=p, max_new_tokens=max_new)
                     for i, p in enumerate(prompts)])
        for _ in range(500):           # settle staging into the cold tier
            if not eng.flusher.pending:
                break
            tier.advance(step_ns)
            eng.flusher.maybe_flush()
        if eng.flusher.pending:
            sys.exit(f"FAIL: kv_quant staging did not drain ({kv_quant})")
        _drive(eng, [Request(rid=1000 + i, prompt=p, max_new_tokens=max_new)
                     for i, p in enumerate(prompts)])
        tokens = {r.rid: list(r.generated) for r in eng.finished}
        hits = eng.stats["prefix_hits"]
        scen = {
            "restores": hits,
            "restore_stall_ns_total":
                round(eng.stats["restore_stall_ns"], 1),
            "restore_stall_ns_per_restore":
                round(eng.stats["restore_stall_ns"] / max(hits, 1), 1),
            "flush_write_ns_total": round(tier.counters["write_ns"], 1),
            "read_bytes": tier.counters["read_bytes"],
            "write_bytes": tier.counters["write_bytes"],
            "prefetch_bytes": tier.counters["prefetch_bytes"],
            "store_bytes": eng.stats["store_bytes"],
            "replay_within_1pct": _replay_gate(tier, f"kv_quant/{kv_quant}"),
        }
        return scen, tokens

    bf16, tok_bf16 = run_one("none")
    int8, tok_int8 = run_one("int8")

    total = matched = 0
    identity = True
    for rid in sorted(tok_bf16):
        a = tok_bf16[rid]
        b = tok_int8.get(rid, [])
        if a != b:
            identity = False
        total += max(len(a), len(b))
        matched += sum(x == y for x, y in zip(a, b))
    match_fraction = matched / max(total, 1)

    def traffic(scen) -> int:
        return scen["read_bytes"] + scen["write_bytes"]

    bytes_ratio = traffic(int8) / max(traffic(bf16), 1)
    acceptance = {
        "kvq_restore_stall_strictly_below_bf16":
            int8["restore_stall_ns_total"] < bf16["restore_stall_ns_total"],
        "kvq_flush_restore_bytes_near_half": 0.4 <= bytes_ratio <= 0.6,
        "kvq_all_resubmits_restored":
            int8["restores"] == n_requests
            and bf16["restores"] == n_requests,
        "kvq_token_quality":
            identity or match_fraction >= KVQ_TOKEN_MATCH_MIN,
        "kvq_replay_within_1pct":
            bf16["replay_within_1pct"] and int8["replay_within_1pct"],
    }
    return {
        "config": {"arch": arch, "dtype": "bfloat16",
                   "n_slots": n_slots, "n_requests": n_requests,
                   "prompt_len": prompt_len, "max_new_tokens": max_new,
                   "max_seq": max_seq, "prefill_chunk": prefill_chunk,
                   "tier_step_ns": step_ns, "seed": seed,
                   "bytes_ratio_int8_vs_bf16": round(bytes_ratio, 4),
                   "token_match_min": KVQ_TOKEN_MATCH_MIN},
        "modes": {"bf16": bf16, "int8": int8},
        "tokens": {"identity": identity,
                   "match_fraction": round(match_fraction, 4),
                   "compared": total},
        "acceptance": acceptance,
    }


def bench_load(params, cfg, rc, *, prefill_chunk: int, seed: int,
               smoke: bool):
    """Open-loop continuous-batching load harness (the ``load`` section).

    A seeded open-loop arrival trace (bursty inter-arrival at ~1.25x the
    continuous engine's service capacity, zipf prompt popularity over a
    shared catalog, mixed prompt/output lengths, a high-priority
    interactive class) is generated once and played against three
    engines on the simulated clock:

     * ``batching``   — closed (wave) admission vs continuous
       admit-on-retire slot recycling, FIFO both;
     * ``scheduling`` — FIFO (= the continuous run) vs preempt+swap on
       the same trace.

    Each scenario emits the full ``LoadMetrics`` SLO summary (TTFT/TPOT
    p50/p99, goodput at the latency targets, queue-depth and restore-
    stall percentiles) plus the engine's typed stats and the tier-trace
    replay gate. Acceptance: continuous goodput strictly above closed on
    the identical trace, every arrival completed, percentiles emitted,
    preemption engaged, every trace replaying within 1% of the oracle.
    Returns the section dict (acceptance included).
    """
    from repro.core.tier import CxlTier, TierConfig
    from repro.serving.config import ServeConfig
    from repro.serving.engine import ServingEngine

    n_slots = 16 if smoke else 256
    max_seq = 64
    max_ticks = 4_000 if smoke else 40_000
    tick_s = 100_000.0 * 1e-9
    new_choices = (4, 8, 16)
    # offered rate: ~1.25x the continuous engine's mean service capacity
    # (slots retire every mean(max_new) ticks), so queues form — the
    # regime where admission policy and preemption actually matter
    mean_new = sum(new_choices) / len(new_choices)
    rate_rps = round(1.25 * n_slots / (mean_new * tick_s))
    lc = _LOADGEN.LoadConfig(
        n_arrivals=48 if smoke else 600,
        rate_rps=float(rate_rps),
        arrival="bursty",
        zipf_s=1.2,
        n_prompts=12 if smoke else 64,
        prompt_len_choices=(8, 16, 24),
        max_new_choices=new_choices,
        vocab=cfg.vocab_size,
        hi_prio_frac=0.25,
        seed=seed,
        slo_ttft_ms=2.0,
        slo_tpot_ms=0.2)
    trace = _LOADGEN.make_trace(lc)

    def run_one(admit_mode, policy):
        tier = CxlTier(TierConfig(media="ssd-fast"))
        eng = ServingEngine(params, cfg, rc, cxl_tier=tier,
                            config=ServeConfig(
                                n_slots=n_slots, max_seq=max_seq,
                                prefill_chunk=prefill_chunk, seed=seed,
                                cxl_async=True, admit_mode=admit_mode,
                                preempt_policy=policy))
        handles, depths = _LOADGEN.drive_open_loop(eng, trace,
                                                   max_ticks=max_ticks)
        res = _LOADGEN.summarize(eng, handles, depths, lc).as_dict()
        res["engine"] = eng.stats.as_dict()
        res["replay_within_1pct"] = _replay_gate(
            tier, f"load/{admit_mode}/{policy}")
        return res

    batching = {"closed": run_one("closed", "none"),
                "continuous": run_one("continuous", "none")}
    scheduling = {"fifo": batching["continuous"],
                  "preempt_swap": run_one("continuous", "swap")}
    scens = (batching["closed"], batching["continuous"],
             scheduling["preempt_swap"])
    acceptance = {
        "load_continuous_goodput_above_closed":
            batching["continuous"]["goodput_req_s"]
            > batching["closed"]["goodput_req_s"],
        "load_all_arrivals_completed": all(
            s["completed"] == lc.n_arrivals for s in scens),
        "load_ttft_percentiles_emitted": all(
            s["ttft_ms_p99"] > 0 and s["tpot_ms_p99"] > 0 for s in scens),
        "load_preempt_engaged":
            scheduling["preempt_swap"]["preemptions"] >= 1,
        "load_replay_within_1pct": all(
            s["replay_within_1pct"] for s in scens),
    }
    config = {k: getattr(lc, k) for k in lc.field_names()}
    config.update(n_slots=n_slots, max_seq=max_seq, max_ticks=max_ticks)
    return {"config": config, "batching": batching,
            "scheduling": scheduling, "acceptance": acceptance}


# fault axis: the mixed-family fleet (one member per KV family shape —
# paged-KV moe, hybrid mamba2, pure-ssm xlstm) driven through one
# identical failure trace on a 2-port tier: a transient-error window on
# port 0, then a latency spike on port 1, then port 1 hot-removed for
# good — against the identical healthy arrival trace.
FAULT_FLEET = ("granite-moe-1b-a400m", "zamba2-2.7b", "xlstm-125m")
FAULT_TOPOLOGY = ("dram", "ssd-fast")
FAULT_TRACE = (
    ("transient", 0.5e6, 0, 0.85, 6.0e6),   # flaky CXL.mem window
    ("degrade", 1.0e6, 1, 300.0, 8.0e6),    # backend latency spike
    ("hot_remove", 3.0e6, 1),               # then the endpoint dies
)


def bench_fault(*, prefill_chunk: int, seed: int, smoke: bool,
                vocab: int, dtype: str):
    """Fault-injection axis of the load section (``load["fault"]``).

    Each fleet member runs the same seeded open-loop arrival trace twice
    — healthy, and under ``FAULT_TRACE`` (transient window -> degrade ->
    hot-remove on a 2-port tier) with ``preempt_policy="recompute"`` so
    page loss always has a resume path. Acceptance (the degraded-mode
    SLO gates): every submitted request completes under faults
    (``lost_requests == 0``), degraded goodput stays within 0.25x the
    healthy run on the identical trace, transient retries stay inside
    the per-op budget and recoveries inside the per-request force-
    prefill bound (no livelock), the faulted runs actually exercised the
    fault machinery, and every trace — fault-annotated kinds included —
    replays within 1% of the scalar oracle.
    """
    from repro.serving.config import ServeConfig
    from repro.serving.engine import ServingEngine
    from repro.serving.scheduler import RECOVERY_PREFILL_AFTER
    from repro.sim.engine import MAX_OP_RETRIES

    n_slots = 8
    max_seq = 64
    max_ticks = 4_000 if smoke else 40_000
    lc = _LOADGEN.LoadConfig(
        n_arrivals=24 if smoke else 192,
        rate_rps=8000.0,
        arrival="bursty",
        zipf_s=1.2,
        n_prompts=8 if smoke else 32,
        prompt_len_choices=(8, 16),
        max_new_choices=(4, 8),
        vocab=vocab or 256,
        seed=seed,
        slo_ttft_ms=2.0,
        slo_tpot_ms=0.5)
    trace = _LOADGEN.make_trace(lc)

    def run_one(params, cfg, rc, faults):
        eng = ServingEngine(params, cfg, rc, config=ServeConfig(
            n_slots=n_slots, max_seq=max_seq,
            prefill_chunk=prefill_chunk, seed=seed,
            cxl_async=True, preempt_policy="recompute",
            tier_topology=FAULT_TOPOLOGY, tier_faults=faults,
            fault_seed=seed))
        handles, depths = _LOADGEN.drive_open_loop(eng, trace,
                                                   max_ticks=max_ticks)
        res = _LOADGEN.summarize(eng, handles, depths, lc).as_dict()
        res["engine"] = eng.stats.as_dict()
        res["replay_within_1pct"] = _replay_gate(eng.tier, "fault")
        return res

    fleet = {}
    for arch in FAULT_FLEET:
        cfg, rc, params = _build(arch, seed, vocab, dtype)
        fleet[arch] = {"healthy": run_one(params, cfg, rc, ()),
                       "faulted": run_one(params, cfg, rc, FAULT_TRACE)}

    def goodput_ratio(per) -> float:
        h, f = per["healthy"], per["faulted"]
        if h["goodput_req_s"] > 0:
            return f["goodput_req_s"] / h["goodput_req_s"]
        if h["throughput_req_s"] > 0:      # degenerate SLO: fall back to
            return (f["throughput_req_s"]  # raw completion rate
                    / h["throughput_req_s"])
        return 1.0

    faulted = [per["faulted"] for per in fleet.values()]
    acceptance = {
        "fault_zero_lost_requests": all(
            s["lost_requests"] == 0 for s in faulted),
        "fault_goodput_within_bound": all(
            goodput_ratio(per) >= 0.25 for per in fleet.values()),
        "fault_retries_bounded": all(
            s["engine"]["tier_fault_retries"]
            <= max(s["engine"]["tier_fault_ops"], 1) * (MAX_OP_RETRIES + 1)
            and s["recoveries"]
            <= lc.n_arrivals * (RECOVERY_PREFILL_AFTER + 1)
            for s in faulted),
        "fault_injection_engaged": any(
            s["engine"]["tier_fault_ops"] > 0
            or s["engine"]["tier_lost_entries"] > 0 for s in faulted),
        "fault_replay_within_1pct": all(
            s["replay_within_1pct"]
            for per in fleet.values() for s in per.values()),
    }
    config = {k: getattr(lc, k) for k in lc.field_names()}
    config.update(n_slots=n_slots, max_seq=max_seq, max_ticks=max_ticks,
                  fleet=list(FAULT_FLEET), topology=list(FAULT_TOPOLOGY),
                  trace=[list(e) for e in FAULT_TRACE])
    return {"config": config, "fleet": fleet, "acceptance": acceptance}


def bench_shard(*, arch: str, vocab: int, dtype: str, seed: int,
                smoke: bool, prefill_chunk: int = 8):
    """The shard axis (``shard`` section): 1-rank vs 2-/4-rank serving.

    One seeded open-loop arrival trace (bursty, zipf-shared prompt
    catalog — the shared-prefix regime) is played against the engine at
    every rank count on identical traffic: the 1-rank baseline runs a
    plain ``CxlTier`` under the host mesh; the sharded runs build a
    (1, N) mesh, shard params + the paged KV cache over the model axis
    and attach a ``ShardedTier`` (one port set per rank + peer-link
    lanes). Restores are blocking so the restore stall is a real,
    deterministic simulated cost.

    Acceptance gates (exit 1 from main on any failure):

     * greedy token identity — every rank count reproduces the 1-rank
       token streams exactly;
     * sublinear restore-stall scaling — aggregate restore stall at N
       ranks stays strictly below N x the 1-rank stall on the same
       traffic (a hot shared prefix is fetched from media once and
       fanned out over the peer link, not cold-restored N times);
     * the peer link actually engaged (fetches > 0) and flush traffic
       did not multiply with ranks;
     * zero lost requests everywhere, every arrival completed;
     * every rank + peer-lane trace replays within 1% of the oracle.
    """
    import dataclasses

    import jax
    from repro.core.sharded_tier import ShardedTier
    from repro.launch.mesh import make_host_mesh
    from repro.serving.config import ServeConfig
    from repro.serving.engine import ServingEngine

    n_devices = len(jax.devices())
    rank_counts = [1] + [n for n in (2, 4) if n <= n_devices]
    if len(rank_counts) < 2:
        sys.exit(f"FAIL: --shard needs >= 2 devices, have {n_devices} "
                 "(set XLA_FLAGS=--xla_force_host_platform_device_"
                 "count=4)")
    if 4 not in rank_counts:
        print(f"[shard] only {n_devices} devices: 4-rank point dropped",
              file=sys.stderr)

    cfg, rc, params = _build(arch, seed, vocab, dtype)
    # sharded decode needs the page axis divisible by every rank count
    max_seq = 64
    rc = dataclasses.replace(rc, kv_page_size=16)
    n_slots = 4
    lc = _LOADGEN.LoadConfig(
        n_arrivals=24 if smoke else 96,
        rate_rps=8000.0,
        arrival="bursty",
        zipf_s=1.2,
        n_prompts=8 if smoke else 24,
        prompt_len_choices=(8, 16),
        max_new_choices=(4, 8),
        vocab=cfg.vocab_size,
        seed=seed,
        slo_ttft_ms=2.0,
        slo_tpot_ms=0.5)
    trace = _LOADGEN.make_trace(lc)
    max_ticks = 4_000 if smoke else 16_000

    def run_one(n_ranks):
        sc = ServeConfig(n_slots=n_slots, max_seq=max_seq,
                         prefill_chunk=prefill_chunk, seed=seed,
                         tp=n_ranks if n_ranks > 1 else 1,
                         tier_topology=("dram", "ssd-fast"))
        eng = ServingEngine(params, cfg, rc, config=sc)
        handles, depths = _LOADGEN.drive_open_loop(eng, trace,
                                                   max_ticks=max_ticks)
        metrics = _LOADGEN.summarize(eng, handles, depths, lc)
        tokens = {r.rid: list(r.generated) for r in eng.finished}
        tier = eng.tier
        sharded = isinstance(tier, ShardedTier)
        c = tier.counters
        scen = {
            "mesh_ranks": eng.stats["mesh_ranks"],
            "completed": metrics.completed,
            "lost_requests": metrics.lost_requests,
            "prefix_hits": eng.stats["prefix_hits"],
            "restore_stall_ns_total":
                round(eng.stats["restore_stall_ns"], 1),
            "tier_writes": c["writes"] + c["async_writes"],
            "peer_fetches": c.get("peer_fetches", 0),
            "peer_bytes": c.get("peer_bytes", 0),
            "peer_fetch_ns": round(c.get("peer_fetch_ns", 0.0), 1),
            "mirror_writes": c.get("mirror_writes", 0),
            "rank_remaps": c.get("rank_remaps", 0),
            "replay_within_1pct": _replay_gate(tier, f"shard/{n_ranks}-rank"),
        }
        return scen, tokens

    ranks = {}
    tokens = {}
    with jax.set_mesh(make_host_mesh()):
        ranks["1-rank"], tokens[1] = run_one(1)
    for n in rank_counts[1:]:
        ranks[f"{n}-rank"], tokens[n] = run_one(n)

    base_stall = max(ranks["1-rank"]["restore_stall_ns_total"], 1e-9)
    for name, scen in ranks.items():
        n = scen["mesh_ranks"]
        scen["stall_ratio_vs_1rank"] = round(
            scen["restore_stall_ns_total"] / base_stall, 4)
        scen["token_identity_vs_1rank"] = tokens[n] == tokens[1]

    sharded = [s for s in ranks.values() if s["mesh_ranks"] > 1]
    acceptance = {
        "shard_token_identity": all(
            s["token_identity_vs_1rank"] for s in ranks.values()),
        "shard_restore_stall_sublinear": all(
            s["stall_ratio_vs_1rank"] < s["mesh_ranks"] for s in sharded)
        and ranks["1-rank"]["restore_stall_ns_total"] > 0,
        "shard_peer_link_engaged": all(
            s["peer_fetches"] > 0 for s in sharded),
        "shard_flush_traffic_bounded": all(
            s["tier_writes"] <= 2 * ranks["1-rank"]["tier_writes"]
            for s in sharded),
        "shard_zero_lost_requests": all(
            s["lost_requests"] == 0 and s["completed"] == lc.n_arrivals
            for s in ranks.values()),
        "shard_replay_within_1pct": all(
            s["replay_within_1pct"] for s in ranks.values()),
    }
    config = {k: getattr(lc, k) for k in lc.field_names()}
    config.update(n_slots=n_slots, max_seq=max_seq, max_ticks=max_ticks,
                  kv_page_size=rc.kv_page_size,
                  rank_counts=rank_counts, n_devices=n_devices,
                  topology=["dram", "ssd-fast"])
    return {"config": config, "ranks": ranks, "acceptance": acceptance}


def _zipf_churn_trace(seed: int, *, n_keys: int = 24, steps: int = 900,
                      phases: int = 3, alpha: float = 1.4,
                      nbytes: int = 32 << 10, flush_p: float = 0.06):
    """Phase-rotated zipf churn traffic for the placement axis.

    The zipf head rotates across the key space every ``steps/phases``
    ops, so yesterday's hot entries go cold — the regime where a plain
    promotion counter keeps thrashing the fast port while the learned
    mixture re-classifies. Returns ``("read"|"write", key, nbytes)``
    tuples; writes model the occasional re-flush of a mutated entry.
    """
    import random
    rng = random.Random(seed)
    trace = []
    w = [1.0 / (r + 1) ** alpha for r in range(n_keys)]
    for ph in range(phases):
        shift = ph * (n_keys // phases)
        ids = [(i + shift) % n_keys for i in range(n_keys)]
        for _ in range(steps // phases):
            k = ids[rng.choices(range(n_keys), weights=w)[0]]
            trace.append(("read", f"k{k}", nbytes))
            if rng.random() < flush_p:
                trace.append(("write", f"k{k}", nbytes))
    return trace


def _zipf_shared_trace(seed: int, *, n_ranks: int = 2, n_keys: int = 12,
                       steps: int = 600, alpha: float = 1.4,
                       nbytes: int = 32 << 10, affinity: float = 0.85,
                       flush_p: float = 0.08):
    """Zipf-shared multi-rank traffic: requester-rank-tagged restores.

    Each shared prefix has a dominant requester rank (``affinity`` of
    its restores come from it) that the blake2b hash home ignores —
    exactly what learned re-homing exploits. Returns
    ``("read"|"write", key, nbytes, req_rank)`` tuples (rank None on
    writes).
    """
    import random
    rng = random.Random(seed)
    dom = {k: rng.randrange(n_ranks) for k in range(n_keys)}
    w = [1.0 / (i + 1) ** alpha for i in range(n_keys)]
    trace = []
    for _ in range(steps):
        k = rng.choices(range(n_keys), weights=w)[0]
        r = dom[k] if rng.random() < affinity else rng.randrange(n_ranks)
        trace.append(("read", f"p{k}", nbytes, r))
        if rng.random() < flush_p:
            trace.append(("write", f"p{k}", nbytes, None))
    return trace


def bench_placement(*, seed: int, smoke: bool):
    """The placement axis (``placement`` section + the standalone
    BENCH_serve_placement.json artifact): the learned GMM placement
    policy (``repro.sim.policy``) vs the heuristics it replaces, on
    identical traces driven straight at the tiers.

     * **churn** — zipf-churn traffic (the hot set rotates every phase)
       against a 3-port heterogeneous ``CxlTier``:
       ``placement="learned"`` vs the ``hotness`` counter. Gate:
       learned strictly lowers aggregate restore stall.
     * **shared** — zipf-shared requester-tagged traffic against a
       2-rank ``ShardedTier``: learned cross-rank homing (re-home +
       multi-source restores) vs the plain blake2b hash home. Gates:
       learned strictly lowers aggregate peer bytes AND aggregate
       restore stall.

    Every tier trace must replay within 1% of the scalar oracle
    (``_replay_gate``, which also records the pricing engine and the
    wall-time ratio in the artifact's ``replay_gates`` section).
    """
    from repro.core.sharded_tier import ShardedTier
    from repro.core.tier import CxlTier, TierConfig

    steps = 300 if smoke else 900
    shared_steps = 240 if smoke else 600
    nb = 32 << 10
    topo3 = ("dram", "ssd-fast", "ssd-slow")
    topo2 = ("dram", "ssd-slow")

    churn_tr = _zipf_churn_trace(seed + 11, steps=steps, nbytes=nb)
    churn = {}
    for placement in ("hotness", "learned"):
        tier = CxlTier(TierConfig(topology=topo3, placement=placement))
        for k in sorted({k for _, k, _ in churn_tr}):
            tier.write_entry(k, nb)
        stall, reads = 0.0, 0
        for op, k, n in churn_tr:
            if op == "read":
                stall += tier.read_entry(k, n)
                reads += 1
            else:
                tier.write_entry(k, n)
            tier.advance(2000.0)
        c = tier.counters
        churn[placement] = {
            "restores": reads,
            "restore_stall_ns_total": round(stall, 1),
            "promotions": c["promotions"],
            "demotions": c["demotions"],
            "replay_within_1pct": _replay_gate(
                tier, f"placement/churn/{placement}"),
        }

    shared_tr = _zipf_shared_trace(seed + 17, steps=shared_steps,
                                   nbytes=nb)
    shared = {}
    for placement in ("hashed", "learned"):
        tier = ShardedTier(2, TierConfig(topology=topo2,
                                         placement=placement))
        for k in sorted({e[1] for e in shared_tr}):
            tier.write_entry(k, nb)
        stall, reads = 0.0, 0
        for op, k, n, r in shared_tr:
            if op == "read":
                stall += tier.read_entry(k, n, req_rank=r)
                reads += 1
            else:
                tier.write_entry(k, n)
            tier.advance(2000.0)
        c = tier.counters
        shared[placement] = {
            "restores": reads,
            "restore_stall_ns_total": round(stall, 1),
            "peer_bytes": c["peer_bytes"],
            "rehomes": c["rehomes"],
            "multi_source_reads": c["multi_source_reads"],
            "replay_within_1pct": _replay_gate(
                tier, f"placement/shared/{placement}"),
        }

    acceptance = {
        "learned_beats_hotness_on_churn_stall":
            churn["learned"]["restore_stall_ns_total"]
            < churn["hotness"]["restore_stall_ns_total"],
        "learned_home_beats_hash_home_stall":
            shared["learned"]["restore_stall_ns_total"]
            < shared["hashed"]["restore_stall_ns_total"],
        "learned_home_beats_hash_home_peer_bytes":
            shared["learned"]["peer_bytes"] < shared["hashed"]["peer_bytes"],
        "replay_within_1pct": all(
            s["replay_within_1pct"]
            for axis in (churn, shared) for s in axis.values()),
    }
    return {
        "config": {"seed": seed, "smoke": bool(smoke), "entry_bytes": nb,
                   "churn_steps": steps, "shared_steps": shared_steps,
                   "churn_topology": list(topo3),
                   "shared_topology": list(topo2), "shared_ranks": 2},
        "churn": churn,
        "shared": shared,
        "acceptance": acceptance,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--smoke", action="store_true",
                    help="small CI-sized matrix")
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--prompt-len", type=int, default=None)
    ap.add_argument("--max-new", type=int, default=None)
    ap.add_argument("--max-seq", type=int, default=None)
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--vocab", type=int, default=1024,
                    help="vocab override for the smoke config (0 keeps the "
                         "256-token smoke vocab)")
    ap.add_argument("--dtype", default="float32",
                    help="param dtype override ('' keeps the config dtype; "
                         "default float32 = CPU-native)")
    ap.add_argument("--temperature", type=float, default=0.7,
                    help="0 = greedy; default exercises the sampling path "
                         "the rewrite moves on-device")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--repeats", type=int, default=5,
                    help="interleaved timed repetitions per engine "
                         "(median reported; per-run numbers recorded)")
    ap.add_argument("--cxl-tier", action="store_true",
                    help="also sweep the CXL-timed tier (media bins "
                         "dram/ssd-fast/ssd-slow x SR on/off) and emit "
                         "a cxl_tier section")
    ap.add_argument("--load", action="store_true",
                    help="also run the open-loop continuous-batching load "
                         "harness (seeded bursty arrivals at ~1.25x "
                         "capacity; continuous-vs-closed and FIFO-vs-"
                         "preempt sweeps) and emit a load section")
    ap.add_argument("--shard", action="store_true",
                    help="also run the shard axis (1-rank vs 2-/4-rank "
                         "sharded serving on identical zipf traffic, "
                         "gated on token identity and sublinear restore-"
                         "stall scaling) and emit a shard section; "
                         "forces 4 host devices when XLA_FLAGS doesn't "
                         "already")
    ap.add_argument("--placement", action="store_true",
                    help="also run the placement axis (learned GMM "
                         "placement vs the hotness counter on zipf-churn "
                         "traffic; learned cross-rank homing vs the hash "
                         "home on zipf-shared 2-rank traffic) and emit a "
                         "placement section plus the standalone "
                         "--placement-out artifact")
    ap.add_argument("--placement-out", default="BENCH_serve_placement.json")
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args(argv)

    if args.smoke:
        defaults = dict(n_slots=4, prompt_len=48, max_new=72, max_seq=128)
    else:
        defaults = dict(n_slots=8, prompt_len=256, max_new=128, max_seq=512)
    n_slots = args.slots or defaults["n_slots"]
    prompt_len = args.prompt_len or defaults["prompt_len"]
    max_new = args.max_new or defaults["max_new"]
    max_seq = args.max_seq or defaults["max_seq"]
    if prompt_len + max_new + 1 >= max_seq:
        ap.error("prompt_len + max_new must fit max_seq (steady decode "
                 "window would hit the position bound)")

    import jax
    from repro.launch.compile_cache import enable_compilation_cache
    from repro.launch.mesh import make_host_mesh

    enable_compilation_cache()
    cfg, rc, params = _build(args.arch, args.seed, args.vocab, args.dtype)
    kw = dict(n_slots=n_slots, max_seq=max_seq, prompt_len=prompt_len,
              max_new=max_new, n_requests=n_slots,
              prefill_chunk=args.prefill_chunk,
              temperature=args.temperature, seed=args.seed,
              repeats=args.repeats)
    with jax.set_mesh(make_host_mesh()):
        pair = bench_pair(params, cfg, rc, **kw)
        cxl_tier = bench_cxl_tier(
            params, cfg, rc, n_slots=n_slots, max_seq=max_seq,
            prompt_len=prompt_len, max_new=min(max_new, 16),
            prefill_chunk=args.prefill_chunk, seed=args.seed) \
            if args.cxl_tier else None
        if cxl_tier is not None:
            cxl_tier["kv_quant"] = bench_kv_quant(
                arch=args.arch, vocab=args.vocab, n_slots=n_slots,
                max_seq=max_seq, prompt_len=prompt_len,
                max_new=min(max_new, 16),
                prefill_chunk=args.prefill_chunk, seed=args.seed)
        load = bench_load(params, cfg, rc, prefill_chunk=8,
                          seed=args.seed, smoke=bool(args.smoke)) \
            if args.load else None
        if load is not None:
            load["fault"] = bench_fault(
                prefill_chunk=8, seed=args.seed, smoke=bool(args.smoke),
                vocab=args.vocab, dtype=args.dtype)
    # outside the host-mesh context: the sharded runs build their own
    # (1, N) meshes; only the 1-rank baseline activates the host mesh
    shard = bench_shard(arch=args.arch, vocab=args.vocab,
                        dtype=args.dtype, seed=args.seed,
                        smoke=bool(args.smoke)) if args.shard else None
    placement = bench_placement(seed=args.seed, smoke=bool(args.smoke)) \
        if args.placement else None
    legacy = pair["legacy_host_path"]
    device = pair["device_resident"]

    speedup = {
        "prefill": round(device["prefill_tok_s"]
                         / max(legacy["prefill_tok_s"], 1e-9), 2),
        "decode": round(device["decode_tok_s"]
                        / max(legacy["decode_tok_s"], 1e-9), 2),
    }
    acceptance = {
        "prefill_ge_5x": speedup["prefill"] >= 5.0,
        "decode_ge_2x": speedup["decode"] >= 2.0,
        "prefix_restore_zero_prefill":
            device["resubmit_prefill_dispatches"] == 0
            and device["prefix_hits"] >= 1,
    }
    out = {
        "bench": "serve",
        "arch": args.arch,
        "config": {"n_slots": n_slots, "prompt_len": prompt_len,
                   "max_new_tokens": max_new, "max_seq": max_seq,
                   "prefill_chunk": args.prefill_chunk,
                   "vocab_size": cfg.vocab_size, "dtype": cfg.dtype,
                   "temperature": args.temperature, "seed": args.seed,
                   "smoke": bool(args.smoke),
                   "backend": jax.default_backend(),
                   "jax": jax.__version__},
        "legacy_host_path": legacy,
        "device_resident": device,
        "speedup": speedup,
        "acceptance": acceptance,
    }
    if cxl_tier is not None:
        out["cxl_tier"] = cxl_tier
    if load is not None:
        out["load"] = load
    if shard is not None:
        out["shard"] = shard
    if placement is not None:
        out["placement"] = placement
    if _REPLAY_GATES:
        out["replay_gates"] = _REPLAY_GATES
    schema_drift = check_schema(out)
    if schema_drift:
        print("FAIL: BENCH_serve.json schema drifted from "
              "serve_bench.SCHEMA_KEYS:\n  " + "\n  ".join(schema_drift),
              file=sys.stderr)
        return 1
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    if placement is not None:
        # the placement gates also ship standalone (CI extracts/uploads
        # this artifact and fails the job on any acceptance violation)
        with open(args.placement_out, "w") as f:
            json.dump({"bench": "serve_placement", **placement},
                      f, indent=2)
    summary = {"speedup": speedup, "acceptance": acceptance,
               "out": args.out}
    if cxl_tier is not None:
        summary["cxl_tier_acceptance"] = cxl_tier["acceptance"]
        summary["cxl_tier_restore_stall_ns_per_restore"] = {
            b: {k: v["restore_stall_ns_per_restore"]
                for k, v in per.items()}
            for b, per in cxl_tier["media_bins"].items()}
        summary["cxl_tier_topology_stall_ns"] = {
            t: per["sr_on"]["restore_stall_ns_total"]
            for t, per in cxl_tier["topology"].items()}
        summary["cxl_tier_scheduler"] = {
            "restore_stall_ns": {
                m: s["restore_stall_ns_total"]
                for m, s in cxl_tier["scheduler"]["restore"].items()},
            "pressure_req_per_sim_s": {
                m: s["req_per_sim_s"]
                for m, s in cxl_tier["scheduler"]["pressure"].items()}}
        kvq = cxl_tier["kv_quant"]
        summary["kv_quant_acceptance"] = kvq["acceptance"]
        summary["kv_quant_restore_stall_ns"] = {
            m: s["restore_stall_ns_total"]
            for m, s in kvq["modes"].items()}
        summary["kv_quant_tier_bytes"] = {
            m: s["read_bytes"] + s["write_bytes"]
            for m, s in kvq["modes"].items()}
        summary["kv_quant_token_match_fraction"] = \
            kvq["tokens"]["match_fraction"]
    if load is not None:
        summary["load_acceptance"] = load["acceptance"]
        summary["load_goodput_req_s"] = {
            "closed": load["batching"]["closed"]["goodput_req_s"],
            "continuous": load["batching"]["continuous"]["goodput_req_s"],
            "preempt_swap":
                load["scheduling"]["preempt_swap"]["goodput_req_s"]}
        summary["load_ttft_ms_p99"] = {
            "closed": load["batching"]["closed"]["ttft_ms_p99"],
            "continuous": load["batching"]["continuous"]["ttft_ms_p99"],
            "preempt_swap":
                load["scheduling"]["preempt_swap"]["ttft_ms_p99"]}
        fault = load["fault"]
        summary["fault_acceptance"] = fault["acceptance"]
        summary["fault_goodput_req_s"] = {
            arch: {m: per[m]["goodput_req_s"] for m in per}
            for arch, per in fault["fleet"].items()}
        summary["fault_recoveries"] = {
            arch: per["faulted"]["recoveries"]
            for arch, per in fault["fleet"].items()}
    if placement is not None:
        summary["placement_acceptance"] = placement["acceptance"]
        summary["placement_churn_stall_ns"] = {
            m: s_["restore_stall_ns_total"]
            for m, s_ in placement["churn"].items()}
        summary["placement_shared_stall_ns"] = {
            m: s_["restore_stall_ns_total"]
            for m, s_ in placement["shared"].items()}
        summary["placement_shared_peer_bytes"] = {
            m: s_["peer_bytes"] for m, s_ in placement["shared"].items()}
    if shard is not None:
        summary["shard_acceptance"] = shard["acceptance"]
        summary["shard_restore_stall_ns"] = {
            m: s["restore_stall_ns_total"]
            for m, s in shard["ranks"].items()}
        summary["shard_stall_ratio_vs_1rank"] = {
            m: s["stall_ratio_vs_1rank"]
            for m, s in shard["ranks"].items()}
        summary["shard_token_identity"] = {
            m: s["token_identity_vs_1rank"]
            for m, s in shard["ranks"].items()}
    print(json.dumps(summary, indent=2))
    if not acceptance["prefix_restore_zero_prefill"]:
        print("FAIL: resubmitted rid was not served via prefix restore",
              file=sys.stderr)
        return 1
    if cxl_tier is not None and not all(cxl_tier["acceptance"].values()):
        print("FAIL: cxl_tier acceptance "
              f"{cxl_tier['acceptance']}", file=sys.stderr)
        return 1
    if cxl_tier is not None \
            and not all(cxl_tier["kv_quant"]["acceptance"].values()):
        print("FAIL: kv_quant acceptance "
              f"{cxl_tier['kv_quant']['acceptance']}", file=sys.stderr)
        return 1
    if load is not None and not all(load["acceptance"].values()):
        print(f"FAIL: load acceptance {load['acceptance']}",
              file=sys.stderr)
        return 1
    if load is not None and "fault" in load \
            and not all(load["fault"]["acceptance"].values()):
        print("FAIL: fault acceptance "
              f"{load['fault']['acceptance']}", file=sys.stderr)
        return 1
    if shard is not None and not all(shard["acceptance"].values()):
        print(f"FAIL: shard acceptance {shard['acceptance']}",
              file=sys.stderr)
        return 1
    if placement is not None and not all(placement["acceptance"].values()):
        print(f"FAIL: placement acceptance {placement['acceptance']}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
