"""The system under test, as the benchmark drives it.

The engine is built as ``repro.launch.serve.build_engine`` builds one
(the same ``RunConfig`` and ``ServeConfig`` path), with the benchmark's
own seeded weights, made by the module of the configuration's model
family (``bench/families/<family>.py``). Set-up warms exactly the
programs a cell's traffic uses; :class:`Recorder` adds the benchmark's
spans and per-call records around the engine's calls into its layers,
in traced runs only.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from bench import spec

# jit module names of the engine's two hot programs, as the trace shows
# them ("jit_<function name>"); the program gives them no stable name yet
DECODE_PROGRAM = "_decode_sample"
PREFILL_PROGRAM = "_prefill_chunk_body"
WARM_RID = 10 ** 9          # request ids of set-up's own requests
FILL_RID = 2 * 10 ** 9


def mesh_scope(config: Dict):
    """The mesh context the engine of ``config`` is built and driven in."""
    from repro.launch.serve import host_mesh_scope
    return host_mesh_scope(spec.serve_config(config, 0))


def build_engine(config: Dict, seed: int, root=spec.ROOT):
    """The engine of a configuration file with seeded weights made by
    its family's module under ``root``; call it inside
    :func:`mesh_scope`."""
    from repro.configs.base import SHAPES, MeshConfig, RunConfig
    from repro.serving.engine import ServingEngine

    cfg = spec.model_config(config)
    rc = RunConfig(model=cfg, shape=SHAPES["decode_32k"], mesh=MeshConfig())
    m = config["model"]
    params = spec.family_module(root, m["family"]).program_params(seed, m)
    return ServingEngine(params, cfg, rc,
                         config=spec.serve_config(config, seed))


def has_work(engine) -> bool:
    """Queued, running or restoring requests remain."""
    return bool(engine.queue or any(s is not None for s in engine.slots)
                or engine.scheduler.busy())


def run_until_drained(engine, max_steps: int = 100_000) -> None:
    """Step until every submitted request is done."""
    for _ in range(max_steps):
        if not has_work(engine):
            return
        engine.step()
    raise RuntimeError("engine did not drain")


def _submit_all(engine, prompts, rid0: int, max_new: int):
    from repro.serving.engine import Request
    return [engine.submit(Request(rid=rid0 + i, prompt=list(p),
                                  max_new_tokens=max_new))
            for i, p in enumerate(prompts)]


def warm(engine, vocab: int, restores: bool) -> None:
    """Compile and run once every program the window uses: the decode
    tick, the prefill chunk with and without sampling, a flush from
    every slot and, where the traffic restores, a restore into every
    slot. Set-up's entries leave the store afterwards."""
    rng = np.random.default_rng(12345)
    n, c = engine.n_slots, engine.prefill_chunk
    prompts = [tuple(int(t) for t in rng.integers(1, vocab, 2 * c))
               for _ in range(n)]
    # max_new 2: one decode tick, so the step that retires also flushes
    _submit_all(engine, prompts, WARM_RID, 2)
    run_until_drained(engine)
    if restores:
        hs = _submit_all(engine, prompts, WARM_RID + n, 2)
        run_until_drained(engine)
        if not all(h.request.restored for h in hs):
            raise RuntimeError("warm-up's repeated prompts were prefilled, "
                               "not restored")
    for rid in [r for r in engine.store.pages if r >= WARM_RID]:
        engine.store.drop(rid)
    jax.block_until_ready(engine.cache)


def fill_store(engine, prompts) -> None:
    """Prefill ``prompts`` and retire them into the host store."""
    _submit_all(engine, prompts, FILL_RID, 2)
    run_until_drained(engine)
    jax.block_until_ready(engine.cache)


def counters(engine) -> Dict[str, float]:
    """Every numeric field of the engine's stats now."""
    return {k: v for k, v in engine.stats.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def entry_bytes(engine) -> int:
    """Bytes of one slot's K/V: what one flush or restore moves."""
    leaves = jax.tree_util.tree_leaves(engine.cache["kv"])
    return sum(a.size * a.dtype.itemsize for a in leaves) // engine.n_slots


class Recorder:
    """Spans and per-call records around the engine's calls into its
    layers. Installed in traced runs only; it changes nothing the
    engine computes."""

    SPANS = {"_prefill_slot": "bench.prefill", "_apply_restore":
             "bench.restore", "_retire": "bench.retire"}

    def __init__(self, engine):
        self.engine = engine
        self.decode: List[tuple] = []      # (t, [live context per slot])
        self.prefill: List[tuple] = []     # (t, pos0, n tokens)
        self.on = False
        self._wrap_programs()
        for name, label in self.SPANS.items():
            setattr(engine, name, _spanned(getattr(engine, name), label))
        engine.store.put = _spanned(engine.store.put, "bench.flush")

    def _wrap_programs(self):
        eng = self.engine
        decode, prefill = eng._decode_fn, eng._prefill_fn

        def decode_fn(*args):
            if self.on:
                live = [eng._pos_host[s] + 1
                        for s, r in enumerate(eng.slots) if r is not None]
                self.decode.append((time.perf_counter(), live))
            with jax.profiler.TraceAnnotation("bench.decode_dispatch"):
                return decode(*args)

        def prefill_fn(*args):
            if self.on:
                pos0, new_pos = int(args[4]), int(args[5])
                self.prefill.append((time.perf_counter(), pos0,
                                     new_pos - pos0))
            with jax.profiler.TraceAnnotation("bench.prefill_dispatch"):
                return prefill(*args)

        eng._decode_fn, eng._prefill_fn = decode_fn, prefill_fn


def _spanned(fn, label: str):
    def call(*args, **kwargs):
        with jax.profiler.TraceAnnotation(label):
            return fn(*args, **kwargs)
    return call


def span(label: str, on: bool):
    """A host span in the profiler's trace, or nothing."""
    return jax.profiler.TraceAnnotation(label) if on else \
        contextlib.nullcontext()


def peak_memory(n_chips: int) -> Optional[int]:
    """Peak bytes in use on the fullest of the first ``n_chips``."""
    peaks = []
    for d in jax.devices()[:n_chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def free_device_state() -> None:
    """Delete every array the process holds on its devices."""
    for a in jax.live_arrays():
        a.delete()
