"""Smoke run of the serving path on a TPU chip: the quickest proof that the
system still starts there.

  python chip_smoke.py              # one chip
  python chip_smoke.py --chips 4    # tp=4 serving against one chip

One process drives every phase; nothing falls back to the CPU.

 (a) device   — platform, kind, count and jax version; anything but a TPU
                exits non-zero.
 (b) kernels  — every Pallas kernel under ``src/repro/kernels`` once, at
                published widths (``repro.kernels.widths``), against its
                ``ref.py`` oracle run at full f32 matmul precision.
 (c) serve    — qwen3-1.7b at full width from seeded random weights,
                built as ``repro.launch.serve`` builds it: 8 slots of 4096
                tokens, a ("dram", "ssd-fast") CXL tier with async I/O and
                a host store that keeps every retired entry. Four requests
                run and retire; then a repeat of the first one is restored
                from the host store while a fifth prompt prefills.
 (d) logits   — prefill-then-decode logits through the paged cache against
                the no-cache forward of the same tokens.

``--chips 4`` runs only (c) with ``tp=4`` and compares its cached logits
with the same computation on one chip.

Every number printed is a smoke reading, not a benchmark. The last line of
stdout is the JSON result; any failed phase raises and exits non-zero.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.kernels import widths  # noqa: E402
from repro.launch.compile_cache import enable_compilation_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.launch.serve import build_engine, host_mesh_scope  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.parallel import sharding as shlib  # noqa: E402
from repro.serving.config import ServeConfig  # noqa: E402
from repro.serving.engine import Request  # noqa: E402

ARCH = "qwen3-1.7b"
N_SLOTS, MAX_SEQ, PREFILL_CHUNK, MAX_NEW = 8, 4096, 128, 32
PROMPT_LENS = (256, 384, 256, 512)
# prefill-then-decode vs the no-cache forward (and tp=4 vs one chip): both
# sides run in bf16, and the TPU takes their f32 attention matmuls in bf16
# passes, so roundings of 2^-8 relative per op diverge across 28 layers of
# differently ordered sums (3.2e-2 measured on a v5e); a stale or
# misplaced cache entry moves the logits by their own magnitude
LOGITS_BOUND = 5e-2
PARITY_PREFIX, PARITY_DECODE = 256, 8
# engine ticks per run(): a 448 MiB entry striped over the dram and
# ssd-fast ports lands about 22,000 simulated 100 us ticks after its
# restore is issued; the slot idles (no device work) until then
MAX_TICKS = 100_000


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def phase_device(n_chips: int):
    devices = jax.devices()
    dev = devices[0]
    print(f"[device] platform {dev.platform}, kind {dev.device_kind}, "
          f"count {len(devices)}, jax {jax.__version__}", flush=True)
    check(dev.platform == "tpu", f"no TPU: JAX found {dev.platform!r}")
    check(len(devices) >= n_chips,
          f"--chips {n_chips} needs {n_chips} devices, have {len(devices)}")
    return devices


def phase_kernels(seed: int) -> None:
    key = jax.random.PRNGKey(seed)
    for case in widths.CASES:
        args = jax.jit(case.make_args)(key)
        out = jax.jit(functools.partial(case.kernel, interpret=False))(*args)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(case.ref)(*args)
        err = widths.rel_err(out, ref)
        print(f"[kernel] {case.name}: max err / max |ref| = {err:.3e} "
              f"(bound {widths.BOUND:.0e})", flush=True)
        check(err <= widths.BOUND, f"{case.name} error {err} > bound")


class CompileClock:
    """Backend compile events and seconds since construction."""

    def __init__(self):
        self.n, self.secs = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += duration


def _serve_config(tp: int):
    # store_budget_bytes=None: a retired 4096-token entry is 448 MiB, so a
    # finite default budget would evict entries as they land
    return ServeConfig(n_slots=N_SLOTS, max_seq=MAX_SEQ,
                       prefill_chunk=PREFILL_CHUNK, cxl_async=True,
                       tier_topology=("dram", "ssd-fast"),
                       store_budget_bytes=None, tp=tp)


def phase_serve(config, seed: int):
    """Serve the request mix; returns the engine."""
    clock = CompileClock()
    t0 = time.perf_counter()
    engine = build_engine(ARCH, smoke=False, config=config)
    tp = config.n_ranks
    cfg = engine.cfg
    n_params = sum(a.size for a in jax.tree_util.tree_leaves(engine.params))
    print(f"[serve] {ARCH} tp={tp}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.n_heads} heads ({cfg.n_kv_heads} KV) of "
          f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
          f"{n_params / 1e9:.3f}e9 params; {N_SLOTS} slots x {MAX_SEQ} "
          f"tokens, layer-scan unroll {engine.hot_rc.scan_unroll}",
          flush=True)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
               for n in PROMPT_LENS + (PROMPT_LENS[0],)]
    first = [engine.submit(Request(rid=i, prompt=p, max_new_tokens=MAX_NEW))
             for i, p in enumerate(prompts[:4])]
    engine.run(max_ticks=MAX_TICKS)
    # the repeat arrives after its original was flushed to the host store
    repeat = engine.submit(Request(rid=4, prompt=prompts[0],
                                   max_new_tokens=MAX_NEW))
    fresh = engine.submit(Request(rid=5, prompt=prompts[4],
                                  max_new_tokens=MAX_NEW))
    engine.run(max_ticks=MAX_TICKS)
    jax.block_until_ready(engine.cache)
    wall = time.perf_counter() - t0
    handles = first + [repeat, fresh]
    unfinished = [h.rid for h in handles
                  if not h.done() or len(h.result()) != MAX_NEW]
    n_done = len(handles) - len(unfinished)
    snap = engine.tier.snapshot()
    restores = snap["reads"] + snap["async_reads"]
    shapes = engine._decode_fn._cache_size() + \
        engine._prefill_fn._cache_size()
    peaks = [d.memory_stats()["peak_bytes_in_use"]
             for d in jax.devices()[:tp]]
    print(f"[serve] {n_done}/{len(handles)} requests finished with "
          f"{MAX_NEW} tokens; {engine.stats['prefix_hits']} prefix hits, "
          f"{restores} tier restores, {snap['writes'] + snap['async_writes']}"
          f" tier flushes, host store {engine.store.bytes / 2**20:.0f} MiB "
          f"in {len(engine.store.pages)} entries", flush=True)
    print(f"[serve] smoke reading: {wall:.1f}s wall from build to drained, "
          f"{clock.n} backend compiles in {clock.secs:.1f}s, {shapes} "
          f"compiled engine shapes, peak_bytes_in_use "
          f"{', '.join(f'{p / 2**30:.2f}' for p in peaks)} GiB", flush=True)
    check(not unfinished, f"requests {unfinished} did not finish")
    check(engine.stats["prefix_hits"] >= 1 and restores >= 1,
          "the repeated prompt was not restored from the tier")
    check(repeat.request.restored, "the repeat was prefilled, not restored")
    check(repeat.result() == first[0].result(),
          "the restored repeat diverged from its original's tokens")
    return engine


def cached_logits(engine, tokens, mesh=None, params=None):
    """Final logits of prefill (``PARITY_PREFIX`` tokens, in chunks) then
    one-token decodes through the paged cache; ``mesh`` shards the cache
    as the engine shards its own."""
    cfg, rc, pspecs = engine.cfg, engine.hot_rc, engine.pspecs
    params = engine.params if params is None else params
    cache = M.cache_init(cfg, rc, 1, max_seq=MAX_SEQ)
    if mesh is not None:
        cache = jax.device_put(cache, shlib.shardings_from_specs(
            mesh, M.cache_specs(cfg, rc, 1)))
    prefill = jax.jit(lambda p, c, t: M.prefill_step_cached(
        p, cfg, rc, t, c, pspecs), donate_argnums=(1,))
    decode = jax.jit(lambda p, c, t: M.decode_step(
        p, cfg, rc, t, c, pspecs), donate_argnums=(1,))
    for i in range(0, PARITY_PREFIX, PREFILL_CHUNK):
        _, cache = prefill(params, cache, tokens[:, i:i + PREFILL_CHUNK])
    for i in range(PARITY_PREFIX, tokens.shape[1]):
        logits, cache = decode(params, cache, tokens[:, i:i + 1])
    return np.asarray(logits, np.float32).reshape(-1)


def _parity_tokens(engine, seed: int):
    rng = np.random.default_rng(seed + 1)
    return rng.integers(1, engine.cfg.vocab_size,
                        (1, PARITY_PREFIX + PARITY_DECODE)).astype(np.int32)


def _report_logits(name: str, got, ref) -> None:
    err = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
    same = bool(np.argmax(got) == np.argmax(ref))
    print(f"[logits] {name}: max err / max |ref| = {err:.3e} (bound "
          f"{LOGITS_BOUND:.0e}), argmax {'agrees' if same else 'differs'}",
          flush=True)
    check(err <= LOGITS_BOUND, f"{name}: logits error {err} > bound")


def phase_parity(engine, seed: int) -> None:
    tokens = _parity_tokens(engine, seed)
    got = cached_logits(engine, tokens)
    fwd = jax.jit(lambda p, t: M.prefill_step(
        p, engine.cfg, engine.hot_rc, {"tokens": t}, engine.pspecs))
    ref = np.asarray(fwd(engine.params, tokens), np.float32).reshape(-1)
    _report_logits(f"prefill {PARITY_PREFIX} + decode {PARITY_DECODE} vs "
                   "no-cache forward", got, ref)


def phase_tp4_vs_one_chip(engine, seed: int) -> None:
    """Shard placement of the tp=4 engine, and its cached logits against
    the same computation on one chip."""

    def devices_of(a):
        return {s.device for s in a.addressable_shards}

    emb = engine.params["embed"]["embedding"]
    k = engine.cache["kv"]["k"]
    param_devs = set().union(*(devices_of(a) for a in
                               jax.tree_util.tree_leaves(engine.params)))
    print(f"[tp4] params on {len(param_devs)} devices; embedding shards "
          f"{[s.data.shape for s in emb.addressable_shards]}; cache K shards"
          f" {[s.data.shape for s in k.addressable_shards]} on "
          f"{len(devices_of(k))} devices", flush=True)
    check(len(param_devs) == 4 and len(devices_of(emb)) == 4
          and emb.addressable_shards[0].data.size * 4 == emb.size,
          "params are not split over four devices")
    check(len(devices_of(k)) == 4
          and k.addressable_shards[0].data.size * 4 == k.size,
          "the paged cache is not split over four devices")
    tokens = _parity_tokens(engine, seed)
    with jax.set_mesh(engine.mesh):
        got = cached_logits(engine, tokens, mesh=engine.mesh)
    # onto the one-device mesh itself: a plain device_put to a device
    # keeps the (1, 4) mesh in the arrays' types, and tracing refuses it
    one = make_host_mesh()
    params1 = jax.device_put(engine.params, NamedSharding(one, P()))
    with jax.set_mesh(one):
        ref = cached_logits(engine, tokens, params=params1)
    del params1
    _report_logits("tp=4 vs one chip, prefill + decode", got, ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = phase_device(args.chips)
    print(f"[device] compilation cache at {enable_compilation_cache()}",
          flush=True)
    config = _serve_config(args.chips)
    with host_mesh_scope(config):
        if args.chips == 1:
            phase_kernels(args.seed)
            engine = phase_serve(config, args.seed)
            phase_parity(engine, args.seed)
        else:
            engine = phase_serve(config, args.seed)
            phase_tp4_vs_one_chip(engine, args.seed)
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
