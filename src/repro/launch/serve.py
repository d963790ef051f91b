"""Serving driver: batched requests through the tiered paged engine.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --smoke \
      --requests 8

Every engine knob on the CLI is derived from
:class:`~repro.serving.config.ServeConfig` — the CLI defaults *are* the
dataclass defaults, and the engine is constructed from the assembled
config object rather than a loose keyword bag.

``--cxl-media`` attaches the CXL-timed memory tier: page flushes and
prefix restores are charged against the simulated endpoint and the
restore stall / SR hit rate are reported alongside throughput.
``--cxl-topology dram,ssd-fast`` attaches a multi-root-port tier
instead (``--cxl-placement`` picks striped / hashed / hotness /
learned — learned drives promotion by the GMM reuse classifier — and
``--cxl-heat-half-life-ns`` ages entry heat so cold entries demote) and
adds a per-port stats line. ``--cxl-async`` switches the tier to
completion-based async I/O (restores overlap decode instead of stalling
the batch) and ``--preempt-policy swap|recompute`` enables preemptive
scheduling under slot pressure; both add a scheduler stats line.

``--load`` switches from the closed submit-then-run loop to the
open-loop continuous-batching harness: a seeded arrival trace
(``--rate`` req/s, ``--arrival poisson|bursty``, zipf prompt
popularity) is played against the engine on the simulated clock and the
SLO summary (TTFT/TPOT p50/p99, goodput at the latency targets, queue
depth) is printed instead of wall-clock throughput.

``--fault-trace degrade|flaky|hot-remove|mix`` injects a named endpoint
fault preset into the attached tier (a deterministic
``FaultSchedule`` seeded by ``--fault-seed``) and prints a recovery
stats line: fault ops / retries / failures, entries and bytes lost to
hot-removed ports, and requests re-queued through RECOVERING.

``--tp N`` runs sharded: the engine builds a (1, N) mesh, shards params
and the paged KV cache over the model axis, and (with a tier attached)
splits the topology into one root-port set per rank with cross-rank
restores charged on a peer link. Faults then apply to rank 0's ports.
Without ``--tp`` the engine runs on one device: the first one JAX finds.
"""
from __future__ import annotations

import argparse
import contextlib
import time

import jax

from repro.configs import registry
from repro.configs.base import MeshConfig, RunConfig, SHAPES
from repro.launch.compile_cache import enable_compilation_cache
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.serving.config import ServeConfig
from repro.serving.engine import Request, ServingEngine

# single source of truth for the CLI defaults below
_DEF = ServeConfig()

# named endpoint-fault presets (--fault-trace); times are simulated ns
# into the run, sized for the smoke/open-loop horizons. ``port`` fields
# are resolved against the attached topology at config-build time: 0 is
# always valid, -1 means the last port.
FAULT_PRESETS = {
    "degrade": (("degrade", 1.0e6, -1, 300.0, 8.0e6),),
    "flaky": (("transient", 0.5e6, 0, 0.85, 6.0e6),),
    "hot-remove": (("hot_remove", 1.5e6, -1),),
    "mix": (("transient", 0.5e6, 0, 0.85, 6.0e6),
            ("degrade", 1.0e6, -1, 300.0, 8.0e6),
            ("hot_remove", 3.0e6, -1)),
}


def resolve_fault_preset(name: str, n_ports: int):
    """Resolve a named preset's relative port indices for a topology."""
    if name not in FAULT_PRESETS:
        raise ValueError(f"unknown fault preset {name!r} "
                         f"(choices: {sorted(FAULT_PRESETS)})")
    events = []
    for kind, t_ns, port, *rest in FAULT_PRESETS[name]:
        port = port % n_ports if n_ports else port
        if kind == "hot_remove" and n_ports < 2:
            raise ValueError("the hot-remove presets need a multi-port "
                             "tier (--cxl-topology with >= 2 ports): "
                             "removing the only port leaves no tier")
        events.append((kind, t_ns, port, *rest))
    return tuple(events)


def _print_closed(engine, finished, n_requests, dt):
    """Summarize one closed-loop run (wall-clock throughput and tier)."""
    tput = engine.stats["decode_tokens"] / dt if dt > 0 else 0.0
    print(f"[serve] {len(finished)}/{n_requests} requests, "
          f"{engine.stats['decode_tokens']} tokens in {dt:.1f}s "
          f"({tput:.1f} tok/s; {engine.stats['prefill_dispatches']} prefill"
          f" + {engine.stats['decode_dispatches']} decode dispatches, "
          f"{engine.stats['prefix_hits']} prefix hits), flushed pages for "
          f"{engine.stats['flushes']} requests, host tier holds "
          f"{len(engine.store.pages)} retired caches "
          f"({engine.store.bytes / 1024:.0f} KiB, "
          f"{engine.store.evictions} evictions)")


def _print_load(metrics, depths):
    """Summarize one open-loop run (SLO percentiles and goodput)."""
    m = metrics
    print(f"[serve] open-loop: {m.completed}/{m.arrivals} arrivals "
          f"completed in {m.sim_time_ms:.2f}ms simulated "
          f"({m.throughput_req_s:.0f} req/s; "
          f"{m.completed_in_slo} within SLO "
          f"ttft<={m.slo_ttft_ms}ms & tpot<={m.slo_tpot_ms}ms "
          f"-> goodput {m.goodput_req_s:.0f} req/s)")
    print(f"[serve]   TTFT p50/p99 {m.ttft_ms_p50:.3f}/"
          f"{m.ttft_ms_p99:.3f}ms, TPOT p50/p99 {m.tpot_ms_p50:.4f}/"
          f"{m.tpot_ms_p99:.4f}ms, queue depth p50/p99 "
          f"{m.queue_depth_p50:.0f}/{m.queue_depth_p99:.0f} "
          f"({len(depths)} samples), restore stall p50/p99 "
          f"{m.restore_stall_ms_p50:.3f}/{m.restore_stall_ms_p99:.3f}ms, "
          f"{m.preemptions} preemptions, {m.prefix_hits} prefix hits")


def _print_tier(engine, config):
    """Per-tier and per-port stats lines for an attached CXL tier."""
    tier = engine.tier
    snap = tier.snapshot()
    print(f"[serve] cxl tier ({snap['media']}, "
          f"SR {'on' if config.tier_sr else 'off'}): "
          f"{snap['writes'] + snap['async_writes']} page flushes "
          f"({snap['write_ns'] / 1e3:.0f}us held), "
          f"{snap['reads'] + snap['async_reads']} cold restores "
          f"stalling "
          f"{engine.stats['restore_stall_ns'] / 1e3:.0f}us total, "
          f"SR hit rate {snap['sr_hit_rate']:.2f}, "
          f"{engine.stats['flushes_deferred']} flush windows deferred "
          f"by the EP, {snap['gc_events']} internal tasks, "
          f"{snap['frees']} segment frees "
          f"({snap['segment_reuses']} reused)")
    if config.cxl_async or config.preempt_policy != "none":
        st = engine.stats
        print(f"[serve] scheduler (async "
              f"{'on' if config.cxl_async else 'off'}"
              f", policy {config.preempt_policy}, "
              f"admit {config.admit_mode}): "
              f"{st['preemptions']} preemptions, "
              f"{st['swap_out_bytes'] / 1024:.0f} KiB swapped out / "
              f"{st['swap_in_bytes'] / 1024:.0f} KiB back in, "
              f"restore overlap {st['restore_overlap_ratio']:.2f} "
              f"({st['restore_inflight_ns'] / 1e3:.0f}us in flight), "
              f"peak {st['sched_inflight_peak']} in-flight tier ops, "
              f"{st['sim_time_ns'] / 1e6:.2f}ms simulated")
    if config.tier_faults:
        st = engine.stats
        down = [p["port"] for p in tier.port_stats() if p["down"]]
        print(f"[serve] faults (seed {config.fault_seed}): "
              f"{st['tier_fault_ops']} ops crossed the fault path "
              f"({st['tier_fault_retries']} retries, "
              f"{st['tier_fault_failures']} exhausted the budget), "
              f"{st['tier_lost_entries']} entries / "
              f"{st['tier_lost_bytes'] / 1024:.0f} KiB lost to "
              f"hot-removed ports {down or '[]'}, "
              f"{st['recoveries']} requests recovered via RECOVERING")
    if tier.cfg.tagged:
        print(f"[serve] topology ({snap['placement']} placement, "
              f"{snap['promotions']} promotions / "
              f"{snap['demotions']} demotions):")
        for p in snap["ports"]:
            rank = f"rank {p['rank']} " if "rank" in p else ""
            print(f"[serve]   {rank}port {p['port']} ({p['media']}): "
                  f"{p['ep_reads']} EP reads, {p['ep_writes']} writes, "
                  f"SR hit rate {p['sr_hit_rate']:.2f}, "
                  f"{p['live_bytes'] / 1024:.0f} KiB live, "
                  f"devload {p['devload']}, "
                  f"staging {p['staging_occupancy']:.2f}, "
                  f"{p['inflight']} in flight")


def host_mesh_scope(config: ServeConfig):
    """The mesh context an engine built from ``config`` is driven under:
    the one-device host mesh when unsharded, none for ``tp > 1``."""
    if config.n_ranks > 1:
        return contextlib.nullcontext()
    return jax.set_mesh(make_host_mesh())


def build_engine(arch: str, *, smoke: bool,
                 config: ServeConfig) -> ServingEngine:
    """The engine ``serve`` drives: ``arch`` at full width (``smoke``:
    the reduced registry config) with seeded random weights.

    An unsharded engine runs on the mesh its caller activates: build and
    drive it under :func:`host_mesh_scope`. A ``tp > 1`` engine activates
    its own (1, tp) mesh around each dispatch.
    """
    cfg = registry.smoke(arch) if smoke else registry.get(arch)
    rc = RunConfig(model=cfg, shape=SHAPES["decode_32k"], mesh=MeshConfig())
    if config.n_ranks > 1:
        # sharded decode needs the page axis divisible by the model
        # axis: cap the page size so each slot has >= n_ranks pages
        import dataclasses as _dc
        page = min(rc.kv_page_size, max(config.max_seq // config.n_ranks,
                                        1))
        rc = _dc.replace(rc, kv_page_size=page)
    params = M.init_model(jax.random.PRNGKey(config.seed), cfg)
    return ServingEngine(params, cfg, rc, config=config)


def serve(arch: str, *, smoke: bool = True, n_requests: int = 8,
          max_new: int = 12, prompt_len: int = 6,
          config: ServeConfig = _DEF, load=None, max_ticks: int = 100_000):
    """Serve requests through the tiered engine built from ``config``.

    Closed mode (``load is None``): submits ``n_requests`` random
    prompts up front, runs to completion and reports wall-clock
    throughput plus per-request handle timings. Open-loop mode: ``load``
    is a :class:`~repro.serving.loadgen.LoadConfig`; its seeded arrival
    trace is played on the simulated clock (arrivals admitted as slots
    retire) and the SLO summary is printed. Every engine knob — slots,
    tier media/topology, async I/O, preemption, admission mode — comes
    from ``config``. Returns ``(engine, finished_requests)``.
    """
    with host_mesh_scope(config):
        engine = build_engine(arch, smoke=smoke, config=config)
        cfg = engine.cfg
        if load is not None:
            from repro.serving.loadgen import (drive_open_loop, make_trace,
                                               summarize)
            trace = make_trace(load)
            handles, depths = drive_open_loop(engine, trace,
                                              max_ticks=max_ticks)
            metrics = summarize(engine, handles, depths, load)
            finished = [h.request for h in handles if h.done()]
            _print_load(metrics, depths)
        else:
            import numpy as np
            rng = np.random.default_rng(config.seed)
            handles = []
            for rid in range(n_requests):
                prompt = rng.integers(1, cfg.vocab_size,
                                      prompt_len).tolist()
                handles.append(engine.submit(
                    Request(rid=rid, prompt=prompt,
                            max_new_tokens=max_new)))
            t0 = time.time()
            finished = engine.run()
            dt = time.time() - t0
            _print_closed(engine, finished, n_requests, dt)
            ttfts = [h.ttft_ns for h in handles if h.ttft_ns is not None]
            if ttfts:
                print(f"[serve]   per-request handles: "
                      f"{sum(1 for h in handles if h.done())} done, "
                      f"mean TTFT {sum(ttfts) / len(ttfts) / 1e6:.3f}ms "
                      f"simulated")
    if engine.tier is not None:
        _print_tier(engine, config)
    return engine, finished


def main() -> None:
    """CLI entry point; every engine default comes from ``ServeConfig``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=_DEF.n_slots)
    ap.add_argument("--max-seq", type=int, default=_DEF.max_seq)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--prefill-chunk", type=int, default=_DEF.prefill_chunk)
    ap.add_argument("--seed", type=int, default=_DEF.seed)
    ap.add_argument("--cxl-media", default=_DEF.tier_media,
                    help="attach the CXL-timed tier: dram / ssd-fast / "
                         "ssd-slow (or any sim media spec, e.g. znand@2)")
    ap.add_argument("--cxl-sr-off", action="store_true",
                    help="disable the speculative-read engine on the tier")
    ap.add_argument("--cxl-topology", default="",
                    help="multi-root-port tier: comma-separated per-port "
                         "media bins (e.g. 'dram,ssd-fast,ssd-slow'); "
                         "overrides --cxl-media")
    ap.add_argument("--cxl-placement", default=_DEF.tier_placement,
                    choices=["striped", "hashed", "hotness", "learned"],
                    help="entry placement across the topology's ports "
                         "(learned = GMM reuse classifier)")
    ap.add_argument("--cxl-heat-half-life-ns", type=float,
                    default=_DEF.tier_heat_half_life_ns,
                    help="entry-heat decay half-life in simulated ns "
                         "(0 = heat never decays); applies to the "
                         "hotness and learned placements")
    ap.add_argument("--kv-quant", default=_DEF.kv_quant,
                    choices=["none", "int8"],
                    help="KV page format: int8 stores per-page-scaled "
                         "int8 pages, halving every tier flush/restore/"
                         "swap/SR byte charge (decode math stays full "
                         "precision)")
    ap.add_argument("--cxl-async", action="store_true",
                    help="completion-based async tier I/O: restores no "
                         "longer stall the batch (the slot activates when "
                         "the fetch lands) and flushes run in background")
    ap.add_argument("--preempt-policy", default=_DEF.preempt_policy,
                    choices=["none", "swap", "recompute"],
                    help="preempt the lowest-priority slot under queue "
                         "pressure: swap its KV pages to the CXL tier "
                         "(swap) or drop and re-prefill on resume "
                         "(recompute)")
    ap.add_argument("--admit-mode", default=_DEF.admit_mode,
                    choices=["continuous", "closed"],
                    help="continuous = admit-on-retire slot recycling; "
                         "closed = wave batching (next wave only once "
                         "every slot drained)")
    ap.add_argument("--load", action="store_true",
                    help="open-loop mode: play a seeded arrival trace on "
                         "the simulated clock instead of submitting all "
                         "requests up front; prints the SLO summary")
    ap.add_argument("--rate", type=float, default=8000.0,
                    help="open-loop offered load, requests per simulated "
                         "second")
    ap.add_argument("--arrival", default="poisson",
                    choices=["poisson", "bursty"],
                    help="open-loop inter-arrival process")
    ap.add_argument("--arrivals", type=int, default=64,
                    help="open-loop trace length (number of requests)")
    ap.add_argument("--zipf-s", type=float, default=1.1,
                    help="zipf exponent for prompt popularity (prefix "
                         "reuse); larger = more skew")
    ap.add_argument("--fault-trace", default="",
                    choices=[""] + sorted(FAULT_PRESETS),
                    help="inject a named endpoint-fault preset into the "
                         "attached tier: degrade (one port at 300x media "
                         "latency), flaky (transient-error window with "
                         "bounded retries), hot-remove (a port dies "
                         "mid-run; its pages are lost and recovered), or "
                         "mix (all three)")
    ap.add_argument("--fault-seed", type=int, default=_DEF.fault_seed,
                    help="seed for the fault schedule's transient-error "
                         "draws (deterministic per (seed, port, attempt))")
    ap.add_argument("--tp", type=int, default=_DEF.tp,
                    help="tensor-parallel rank count: tp=N builds a "
                         "(1, N) mesh, shards params + the paged KV "
                         "cache over the model axis and gives the tier "
                         "one root-port set per rank (needs N devices, "
                         "e.g. XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N on CPU)")
    args = ap.parse_args()
    enable_compilation_cache()
    topology = tuple(m.strip() for m in
                     args.cxl_topology.split(",") if m.strip())
    tier_faults = ()
    if args.fault_trace:
        n_ports = len(topology) if topology else (1 if args.cxl_media
                                                  else 0)
        tier_faults = resolve_fault_preset(args.fault_trace, n_ports)
    config = ServeConfig(
        n_slots=args.slots, max_seq=args.max_seq,
        prefill_chunk=args.prefill_chunk, seed=args.seed,
        kv_quant=args.kv_quant,
        cxl_async=args.cxl_async, preempt_policy=args.preempt_policy,
        admit_mode=args.admit_mode, tier_media=args.cxl_media,
        tier_topology=topology,
        tier_placement=args.cxl_placement,
        tier_heat_half_life_ns=args.cxl_heat_half_life_ns,
        tier_sr=not args.cxl_sr_off,
        tier_faults=tier_faults, fault_seed=args.fault_seed, tp=args.tp)
    load = None
    if args.load:
        from repro.serving.loadgen import LoadConfig
        load = LoadConfig(n_arrivals=args.arrivals, rate_rps=args.rate,
                          arrival=args.arrival, zipf_s=args.zipf_s,
                          seed=args.seed)
    serve(args.arch, smoke=args.smoke, n_requests=args.requests,
          max_new=args.max_new, config=config, load=load)


if __name__ == "__main__":
    main()
