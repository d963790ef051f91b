"""Continuous-batching serving engine over the paged, tiered KV cache.

The paper's storage-expansion loop, at request granularity:

 * slots — the engine runs a fixed decode batch; requests stream through
   slots (continuous batching). Each slot owns a page range of the
   distributed cache and its own position (per-slot `pos` vector).
 * tiered pages — a finished slot's pages are not dropped: they retire
   through the ``StagingRing`` (deterministic store: the release is
   immediate; the flush to the cold tier happens in the background, gated
   by the QoS controller exactly like Fig. 8) into the host-side page
   store, keyed by request id — prefix reuse fetches them back (the
   speculative-read path) instead of re-prefilling.
 * QoS — per-step telemetry drives the same DevLoad machine the training
   driver and the simulator use; under congestion flushes pause and the
   prefetch window narrows.
 * CXL timing — with a ``repro.core.tier.CxlTier`` attached, every page
   movement is charged against the simulated endpoint: restores stall for
   the demand fetch (hidden by the MemSpecRd issued at enqueue time),
   flushes ride the deterministic-store path, and the EP's announced
   state (DevLoad / internal tasks) gates the flusher's admission window.
   Per-request stalls land on ``Request.restore_stall_ns``; aggregates in
   ``engine.stats`` (restore_stall_ns, tier_sr_hit_rate,
   tier_store_occupancy, flushes_deferred).

The hot path is device-resident:

 * prefill — chunked multi-token ingestion (``models.model.
   prefill_step_cached``): each chunk is one jitted dispatch that slices
   the request's slot out of the batch cache, writes the chunk's K/V
   in-graph (``dynamic_update_slice``) and splices the slot back — no
   per-token dispatch, no host-side cache surgery.
 * decode — one jitted dispatch per tick that runs the page-sharded
   ``decode_step`` for every slot AND samples the next token on device
   (argmax, or inverse-CDF categorical sampling via the jax PRNG — see
   ``models.model.sample_tokens``). Last tokens, positions and the PRNG
   key stay device arrays across ticks; the host never calls
   ``block_until_ready`` or reads logits except when a slot retires.
 * prefix reuse — on admit, a request whose rid (or prompt) matches a
   retired entry in the staging index or the host page store restores its
   pages into the slot (the speculative-read fetch) with zero prefill
   dispatches.

Admission is owned by the request-lifecycle scheduler
(``repro.serving.scheduler``): requests move through an explicit state
machine (QUEUED -> RESTORING -> RUNNING -> PREEMPTED/SWAPPED ->
RETIRED). With ``cxl_async=True`` cold-tier restores are issued as
completion-based async ops — the slot sits RESTORING while the rest of
the batch decodes, hiding the media latency — and flushes become
background ops; ``preempt_policy`` ("swap"/"recompute") lets the
scheduler evict a low-priority slot to the CXL tier under pressure and
admit queued work instead of idling. The defaults (``cxl_async=False``,
``preempt_policy="none"``) reproduce the blocking greedy-FIFO engine
bit-for-bit.

``legacy_host_path=True`` preserves the pre-rewrite hot path (per-token
prefill dispatches, host softmax/numpy sampling, per-tick logits
transfer + sync) as the measured baseline for ``benchmarks/serve_bench``.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, RunConfig
from repro.core import deterministic_store as ds
from repro.core.qos import DevLoad, QoSController
from repro.core.tier import CxlTier
from repro.models import model as M
from repro.parallel import sharding as shlib
from repro.serving import scheduler as sched
from repro.serving.config import ServeConfig
from repro.serving.spans import SpanLog
from repro.serving.stats import EngineStats

# Names of the two jitted hot programs: a profile shows them as
# "jit_<name>", and the benchmark's trace reduction finds them by these.
DECODE_PROGRAM = "serve_decode_sample"
PREFILL_PROGRAM = "serve_prefill_chunk_body"


@dataclasses.dataclass
class Request:
    """One serving request: prompt tokens in, generated tokens out.

    ``restore_stall_ns`` is the simulated CXL demand-fetch stall (ns)
    charged when the request was served via a cold-tier prefix restore
    (0.0 otherwise or without an attached tier). ``priority`` orders
    admission (higher first, FIFO among equals) and marks preemption
    victims; ``state`` walks the scheduler's lifecycle (QUEUED ->
    RESTORING -> RUNNING -> PREEMPTED/SWAPPED -> RETIRED, see
    ``repro.serving.scheduler``).
    """

    rid: int
    prompt: List[int]
    max_new_tokens: int = 16
    priority: int = 0
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    slot: Optional[int] = None
    state: str = sched.QUEUED       # scheduler lifecycle state
    restored: bool = False          # served via prefix restore (no prefill)
    restore_stall_ns: float = 0.0   # simulated CXL fetch stall (cold-tier
                                    # restore through the CxlTier, else 0)
    recoveries: int = 0             # failed-fetch / page-loss re-queues
                                    # (RECOVERING transitions survived)
    # SLO timestamps on the engine's simulated clock (``engine.clock_ns``,
    # tier_step_ns per working tick plus open-loop idle jumps): stamped at
    # submit / first sampled token / retirement, read back through the
    # RequestHandle's ttft_ns / tpot_ns properties.
    arrival_ns: Optional[float] = None
    first_token_ns: Optional[float] = None
    finish_ns: Optional[float] = None
    # device-resident bookkeeping: the sampled-token handle plus this
    # request's tick range in the engine trace; the host only materializes
    # tokens at retirement (one [n_slots] transfer per tick, memoized
    # across co-retiring slots)
    _first_tok: Optional[jax.Array] = dataclasses.field(
        default=None, repr=False)
    _start_tick: int = 0
    _n_gen: int = 0                 # total generated tokens (stop check)
    _n_dec: int = 0                 # decode ticks participated (trace span)
    _queued_ns: Optional[int] = dataclasses.field(
        default=None, repr=False)   # submit time while the span log is on


class RequestHandle:
    """What ``ServingEngine.submit`` returns: one request's progress view.

    Callers poll :meth:`done` / read :meth:`result` instead of fishing
    retired ``Request`` objects out of ``run()``'s return list (which
    still returns them, as the deprecation shim for the old shape). The
    timing properties expose the per-request SLO measurements on the
    engine's simulated clock — the raw material ``loadgen.summarize``
    folds into TTFT/TPOT percentiles and goodput.
    """

    def __init__(self, request: Request, engine: "ServingEngine"):
        self._req = request
        self._engine = engine

    @property
    def rid(self) -> int:
        """The submitted request's id."""
        return self._req.rid

    @property
    def request(self) -> Request:
        """The underlying ``Request`` (escape hatch for tests/tools)."""
        return self._req

    def done(self) -> bool:
        """True once the request retired (its token stream is final)."""
        return self._req.done

    def result(self) -> List[int]:
        """The generated token stream; raises while still pending."""
        if not self._req.done:
            raise RuntimeError(f"request {self._req.rid} is still "
                               f"{self._req.state}; call done() first")
        return list(self._req.generated)

    def tokens(self) -> List[int]:
        """Tokens materialized so far (empty until retirement on the
        device-resident path — the stream lives on device mid-flight)."""
        return list(self._req.generated)

    @property
    def ttft_ns(self) -> Optional[float]:
        """Time to first token (simulated ns), None until it exists."""
        if self._req.first_token_ns is None or self._req.arrival_ns is None:
            return None
        return self._req.first_token_ns - self._req.arrival_ns

    @property
    def tpot_ns(self) -> Optional[float]:
        """Mean time per output token after the first (simulated ns)."""
        if self._req.finish_ns is None or self._req.first_token_ns is None:
            return None
        span = self._req.finish_ns - self._req.first_token_ns
        return span / max(len(self._req.generated) - 1, 1)

    @property
    def restore_stall_ns(self) -> float:
        """Simulated ns this request stalled on cold-tier fetches."""
        return self._req.restore_stall_ns

    @property
    def recoveries(self) -> int:
        """RECOVERING re-queues this request survived (failed tier
        fetches and pages lost to a hot-removed port; 0 without faults)."""
        return self._req.recoveries


# Families whose full per-request decode state lives in the paged "kv"
# leaves — the only ones prefix restore can reconstruct a slot from.
_RESTORABLE_FAMILIES = ("dense", "moe", "audio")


# Layer-scan unroll of the hot path. Unrolling saves per-layer loop
# overhead, which dominates a small model's step; but each unrolled layer
# keeps its own transients of its cache slice live, and a factor that does
# not divide the layer count slices the remainder out of every cache leaf
# and weight stack. Compiled for a v5e chip, the 28-layer qwen3-1.7b
# decode step at 8 slots x 4096 tokens (128 MiB of cache per layer) takes
# 3.88 GiB of temp at unroll 1, 4.13 at 2, 6.51 at 7 and 10.36 at 8.
_UNROLL_MAX = 8
_UNROLL_CACHE_BYTES = 128 << 20     # cache the unrolled layers may span


def layer_scan_unroll(cfg: ModelConfig, rc: RunConfig, n_slots: int,
                      max_seq: int) -> int:
    """Unroll factor of the hot path's layer scan.

    The largest divisor of the layer count, at most ``_UNROLL_MAX``, whose
    unrolled layers span at most ``_UNROLL_CACHE_BYTES`` of the batch
    cache; 1 when even one layer's cache is larger.
    """
    n = M.n_stacked(cfg)
    cache = M.cache_init(cfg, rc, n_slots, max_seq=max_seq, as_shape=True)
    per_layer = sum(a.size * a.dtype.itemsize
                    for a in jax.tree_util.tree_leaves(cache)) / n
    return max(u for u in range(1, min(n, _UNROLL_MAX) + 1)
               if u == 1 or (n % u == 0
                             and u * per_layer <= _UNROLL_CACHE_BYTES))


def _named(fn, name: str):
    """``fn`` under the function name ``name``, which jit gives the
    program it compiles."""
    def program(*args):
        return fn(*args)
    program.__name__ = program.__qualname__ = name
    return program


def _fsdp_axis_size() -> int:
    """Product of the pool-tier (FSDP) mesh axes under the active mesh."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return 1
    sizes = dict(mesh.shape)
    return sizes.get("data", 1) * sizes.get("pod", 1)


class HostPageStore:
    """Cold tier for retired KV pages (the SSD-EP analogue).

    LRU-bounded by ``budget_bytes``: inserts evict the least-recently-used
    entries until the store fits; ``get`` refreshes recency. ``bytes`` and
    ``evictions`` are surfaced through the engine stats. ``on_evict`` is
    called as ``on_evict(rid, entry, reason)`` for every dropped
    (``reason="evict"``) or replaced (``reason="replace"``) entry so side
    indexes (the engine's prompt->rid alias map) stay bounded too — and so
    the engine can release a truly evicted entry's CXL-tier segments
    without freeing the pages a replacement just rewrote. ``put`` reports
    whether the entry survived admission: budget pressure can evict an
    entry during its own insert (a re-staged rid growing past the budget,
    or any oversized entry), and indexing such an entry would leak — the
    eviction callback for it has already fired by the time ``put``
    returns.

    The device-to-host copy completes in the background (the
    deterministic store's drain): ``put`` starts the copy of the entry's
    device leaves and returns with the entry *pending*, its leaves still
    device arrays; admission and accounting are settled at once, from
    the same byte counts. At most one entry is pending: the next ``put``
    first waits for its copy and swaps its leaves for the host arrays
    (the finalize), as does :meth:`sync`. A pending entry that is
    dropped, evicted or replaced is discarded with its device buffers.
    ``flush_async`` counts the puts that returned pending,
    ``flush_wait_ms`` the host time spent finalizing. ``spans`` times the
    blocking part of each put's copy (``serve.flush.copy``: the previous
    entry's finalize and the start of this one's copy) while it is on.
    """

    def __init__(self, budget_bytes: Optional[int] = None, on_evict=None,
                 spans: Optional[SpanLog] = None):
        self.pages: "collections.OrderedDict[int, Dict]" = \
            collections.OrderedDict()
        self.budget_bytes = budget_bytes
        self.on_evict = on_evict
        self.spans = spans if spans is not None else SpanLog()
        self.bytes = 0
        self.evictions = 0
        self.flush_async = 0
        self.flush_wait_ms = 0.0
        self._pending: Optional[Dict] = None    # entry whose copy is in flight

    # one canonical pytree-size helper for the whole page path: the tier
    # charges the same byte counts this budget is accounted in
    _entry_bytes = staticmethod(CxlTier.entry_bytes)

    def put(self, rid: int, entry) -> bool:
        """Insert/replace; returns True iff ``rid`` survived admission."""
        if not isinstance(entry, dict) or "kv" not in entry:
            entry = {"kv": entry}      # bare-pytree compat (pre-entry API)
        entry = dict(entry)
        leaves = [a for a in jax.tree_util.tree_leaves(entry["kv"])
                  if isinstance(a, jax.Array)]
        with self.spans.span("serve.flush.copy") as attrs:
            if attrs is not None:
                attrs["bytes"] = self._entry_bytes(entry["kv"])
            self.sync()
            for a in leaves:
                a.copy_to_host_async()
        if rid in self.pages:
            self._remove(rid, "replace")
        self.pages[rid] = entry
        self.bytes += self._entry_bytes(entry)
        self._evict()
        if rid not in self.pages:
            return False
        if leaves:
            self._pending = entry
            self.flush_async += 1
        return True

    def sync(self) -> None:
        """Finalize the pending entry, if any: wait for its copy to land
        and hold its leaves as host arrays from then on."""
        entry, self._pending = self._pending, None
        if entry is not None:
            t0 = time.perf_counter()
            entry["kv"] = jax.tree_util.tree_map(np.asarray, entry["kv"])
            self.flush_wait_ms += (time.perf_counter() - t0) * 1e3

    def is_pending(self, entry) -> bool:
        """Whether ``entry``'s device-to-host copy is still in flight."""
        return entry is not None and entry is self._pending

    def get(self, rid: int):
        """Fetch ``rid``'s entry (refreshing LRU recency), else None."""
        entry = self.pages.get(rid)
        if entry is not None:
            self.pages.move_to_end(rid)
        return entry

    def drop(self, rid: int) -> bool:
        """Remove ``rid`` outright, regardless of budget or recency.

        The fault-recovery path uses this when the entry's tier copy was
        lost (port hot-removed) or keeps failing its fetch: the next
        lookup misses and the request prefills fresh. Fires ``on_evict``
        with ``reason="evict"`` like an LRU eviction (so side indexes and
        tier segments are released the same way); returns True iff the
        rid was present.
        """
        if rid not in self.pages:
            return False
        self._remove(rid, "evict")
        return True

    def _remove(self, rid: int, reason: str) -> None:
        old = self.pages.pop(rid)
        if old is self._pending:
            self._pending = None
        self.bytes -= self._entry_bytes(old)
        if reason == "evict":
            self.evictions += 1
        if self.on_evict is not None:
            self.on_evict(rid, old, reason)

    def _evict(self) -> None:
        if self.budget_bytes is None:
            return
        while self.bytes > self.budget_bytes and self.pages:
            self._remove(next(iter(self.pages)), "evict")


class ServingEngine:
    """Fixed-batch continuous batching with tiered page lifecycle."""

    def __init__(self, params, cfg: ModelConfig, rc: RunConfig, *,
                 config: Optional[ServeConfig] = None,
                 cxl_tier: Optional[CxlTier] = None, **knobs):
        """Build the engine from a :class:`ServeConfig`.

        ``config`` carries every knob (slot count, hot-path options,
        scheduler policy, declarative tier attachment); passing the old
        keyword knobs directly (``n_slots=...``, ``cxl_async=...``) still
        works — they construct the ServeConfig, with the same validation.
        ``cxl_tier`` injects a prebuilt tier (tests/benches that need to
        inspect the instance); otherwise ``config.make_tier()`` builds
        whatever the config declares.
        """
        if config is not None and knobs:
            raise TypeError("pass either config=ServeConfig(...) or the "
                            f"legacy keyword knobs, not both: "
                            f"{sorted(knobs)}")
        if config is None:
            config = ServeConfig(**knobs)
        self.serve_config = config
        # quantized KV pages: thread the knob into the RunConfig so
        # cache_init emits int8 pages + scales and every downstream tier
        # charge (flush/restore/swap/SR) sees the quantized byte counts
        if config.kv_quant != "none" and rc.kv_quant != config.kv_quant:
            rc = dataclasses.replace(rc, kv_quant=config.kv_quant)
        # sharded serving: build the (data, model) mesh the config asks
        # for and activate it around every jitted dispatch — params and
        # the paged KV cache shard over the model axis, and
        # paged_decode_attention's shard_map body engages (the page axis
        # carries the tensor parallelism; see models/attention.py)
        self.mesh = None
        mesh_shape = config.resolved_mesh_shape
        if mesh_shape:
            from repro.launch.mesh import make_production_mesh
            self.mesh = make_production_mesh(shape=mesh_shape)
            page = min(rc.kv_page_size, config.max_seq)
            n_pages = max(config.max_seq // page, 1)
            n_ranks = config.n_ranks
            if n_pages % n_ranks:
                raise ValueError(
                    f"sharded decode needs the page axis divisible by the "
                    f"model axis: {n_pages} pages (max_seq={config.max_seq},"
                    f" kv_page_size={rc.kv_page_size}) % {n_ranks} ranks "
                    "!= 0 — lower kv_page_size or adjust max_seq")
        self.params = params
        self.cfg = cfg
        self.rc = rc
        self.n_slots = config.n_slots
        self.max_seq = config.max_seq
        self.temperature = config.temperature
        self.prefill_chunk = max(1, min(config.prefill_chunk,
                                        config.max_seq))
        self.legacy = config.legacy_host_path
        self.sync_prefill = config.sync_prefill
        self.key = jax.random.PRNGKey(config.seed)
        n_slots, max_seq = config.n_slots, config.max_seq
        legacy_host_path = config.legacy_host_path
        self.pspecs = shlib.param_specs(
            jax.eval_shape(lambda: params), tier=rc.param_tier,
            multi_pod_fsdp=rc.mesh.multi_pod)
        # Device-resident hot path: when the pool tier is degenerate (the
        # FSDP axes have size 1, so the SR "gather" fetches nothing) the
        # infer-mode prefetch-buffer rotation is pure per-tick overhead —
        # drop it and unroll the layer scan (see layer_scan_unroll). The
        # legacy path keeps the caller's rc untouched (it is the measured
        # pre-rewrite baseline).
        self.hot_rc = rc
        with self._mesh_scope():
            fsdp_size = _fsdp_axis_size()
        if not legacy_host_path and rc.sr_prefetch_depth \
                and fsdp_size == 1:
            self.hot_rc = dataclasses.replace(
                rc, sr_prefetch_depth=0,
                scan_unroll=rc.scan_unroll or layer_scan_unroll(
                    cfg, rc, n_slots, max_seq))
        self.cache = M.cache_init(cfg, rc, n_slots, max_seq=max_seq)
        if self.mesh is not None:
            # place params and the paged cache onto the mesh: params via
            # the production sharding rules, cache leaves (pages + int8
            # scales) via cache_specs — the page axis lands on "model"
            self.params = jax.device_put(
                params, shlib.shardings_from_specs(self.mesh, self.pspecs))
            cspecs = M.cache_specs(cfg, self.hot_rc, n_slots)
            self.cache = jax.device_put(
                self.cache, shlib.shardings_from_specs(self.mesh, cspecs))
        self.slots: List[Optional[Request]] = [None] * n_slots
        self.queue: List[Request] = []
        self.finished: List[Request] = []
        self.qos = QoSController()
        # CXL-timed tier: every page movement below is charged against the
        # simulated endpoint (restore stall, flush cost, SR prefetch), and
        # the EP's announced state gates the flusher's admission window.
        self.tier = cxl_tier if cxl_tier is not None else config.make_tier()
        self.tier_step_ns = config.tier_step_ns
        self.cxl_async = bool(config.cxl_async)
        self._restorable = cfg.family in _RESTORABLE_FAMILIES
        # the engine's simulated clock: tier_step_ns per working tick plus
        # explicit open-loop idle jumps (advance_time). All per-request
        # SLO timestamps (arrival/first-token/finish) land on it.
        self.clock_ns = 0.0
        # outstanding async background writes (flush / swap-out): their
        # TierHandles are polled each tick and drained at run()'s horizon
        # so end-of-run in-flight depth is consistent.
        self._async_writes: List = []
        # request-lifecycle scheduler: admission, async restore
        # activation and preemption decisions live there; with async off
        # and preempt_policy="none" it reproduces the old greedy-FIFO
        # blocking admission exactly.
        self.scheduler = sched.RequestScheduler(
            self, async_restore=self.cxl_async,
            preempt_policy=config.preempt_policy,
            admit_mode=config.admit_mode)
        # spans inside the engine, off until started (repro.serving.spans)
        self.spans = SpanLog()
        self.store = HostPageStore(budget_bytes=config.store_budget_bytes,
                                   on_evict=self._drop_prompt_alias,
                                   spans=self.spans)
        self._prompt_index: Dict[Tuple[int, ...], int] = {}
        self.flusher = ds.StagingFlusher(
            sink=self._store_sink, qos=self.qos,
            admit=self.tier.admit_store if self.tier is not None else None)
        # device-resident tick state (new path)
        self.last_tokens = jnp.zeros((n_slots,), jnp.int32)
        self._pos_host = [0] * n_slots      # mirror of cache["pos"]
        self._tick = 0                      # decode ticks executed
        self._trace: Dict[int, jax.Array] = {}      # tick -> [n_slots] toks
        self._trace_np: Dict[int, np.ndarray] = {}  # memoized transfers
        # MoE routing counts the programs made since the last retirement,
        # summed on the device: (token-routings on held experts, held
        # experts touched in decode, decode calls); read at retirement
        self._routing = (jnp.zeros((3,), jnp.int32) if cfg.family == "moe"
                         else None)
        # jitted hot-path entry points (traced lazily on first use). The
        # batch cache is donated: nothing on the host ever re-reads an old
        # cache, and aliasing in/out buffers saves a full cache copy per
        # tick (last_tokens/key are NOT donated — the token trace keeps
        # handles to old tick outputs until retirement).
        self.step_fn = jax.jit(self._step)                  # legacy decode
        self._decode_fn = jax.jit(_named(self._decode_sample, DECODE_PROGRAM),
                                  donate_argnums=(1,))
        self._prefill_fn = jax.jit(
            _named(self._prefill_chunk_body, PREFILL_PROGRAM),
            donate_argnums=(1,), static_argnums=(8,))
        # typed stats: field list = schema (see repro.serving.stats). The
        # mapping protocol keeps every stats["..."] call site unchanged,
        # and a typo'd key raises KeyError instead of silently growing
        # the bench schema.
        self.stats = EngineStats()
        self.stats["mesh_ranks"] = (config.n_ranks if self.mesh is not None
                                    else 1)

    # ----------------------------------------------------------- step fns
    def _mesh_scope(self):
        """Context activating the engine's mesh (no-op when unsharded).

        jax's ``set_mesh`` is a lexical context manager, so the engine
        scopes it around every jitted dispatch: tracing then sees the
        (data, model) mesh and the page-sharded decode takes the
        shard_map path with a real model axis.
        """
        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.mesh)

    def _step(self, params, cache, tokens):
        return M.decode_step(params, self.cfg, self.rc, tokens, cache,
                             self.pspecs)

    def _decode_sample(self, params, cache, last_tokens, key):
        """One fused decode tick: step every slot + sample on device."""
        if self.cfg.family == "audio":
            toks = jnp.broadcast_to(
                last_tokens[:, None, None],
                (self.n_slots, self.cfg.n_codebooks, 1))
        else:
            toks = last_tokens[:, None]
        logits, cache, counts = M.decode_step(
            params, self.cfg, self.hot_rc, toks, cache, self.pspecs,
            routing=True)
        row = M.last_token_logits(logits)
        if self.temperature > 0:
            key, sub = jax.random.split(key)
            nxt = M.sample_tokens(row, sub, self.temperature)
        else:
            nxt = M.sample_tokens(row, None, 0.0)
        if counts is None:
            return cache, nxt, key
        return cache, nxt, key, jnp.concatenate(
            [counts, jnp.ones((1,), jnp.int32)])

    def _prefill_chunk_body(self, params, cache, tokens, slot, pos0,
                            new_pos, last_tokens, key, sample):
        """One prefill chunk for one slot, entirely in-graph.

        Slices the slot out of the batch cache, pins the slot position to
        the chunk start (a reused slot's device pos is stale — decode
        advances every row each tick), runs the chunked cache-writing
        prefill, and splices the slot back (dynamic_update_slice along
        each leaf's batch axis). Only the final chunk (``sample=True``,
        static) samples the last-position token on device — one PRNG
        split per request, so sampled streams do not depend on the chunk
        size. Other slots never observe the prefill (continuous-batching
        isolation). A model with experts adds the chunk's routing counts
        as the last output of either form.
        """
        baxes = self._batch_axes()
        cache1 = jax.tree_util.tree_map(
            lambda a, ax: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=ax),
            cache, baxes)
        cache1["pos"] = jnp.full((1,), pos0, jnp.int32)
        logits, cache1, counts = M.prefill_step_cached(
            params, self.cfg, self.hot_rc, tokens, cache1, self.pspecs,
            routing=True)
        routing = () if counts is None else (
            jnp.concatenate([counts[:1], jnp.zeros((2,), jnp.int32)]),)
        cache1["pos"] = jnp.full((1,), new_pos, jnp.int32)
        cache = jax.tree_util.tree_map(
            lambda a, a1, ax: jax.lax.dynamic_update_slice_in_dim(
                a, a1.astype(a.dtype), slot, axis=ax),
            cache, cache1, baxes)
        if not sample:
            return (cache, *routing)
        row = M.last_token_logits(logits)            # [1, V]
        if self.temperature > 0:
            key, sub = jax.random.split(key)
            tok = M.sample_tokens(row, sub, self.temperature)[0]
        else:
            tok = M.sample_tokens(row, None, 0.0)[0]
        last_tokens = last_tokens.at[slot].set(tok)
        return (cache, last_tokens, tok, key, *routing)

    # ------------------------------------------------------------ admit
    def submit(self, req: Request, *,
               arrival_ns: Optional[float] = None) -> RequestHandle:
        """Enqueue a request (admission happens on a later tick).

        Returns a :class:`RequestHandle` the caller polls for completion
        and per-request SLO timings. ``arrival_ns`` backdates the arrival
        timestamp onto the simulated clock (the open-loop driver submits
        a trace whose arrival times were generated ahead of the run);
        default is the engine clock at submit time.
        """
        req.arrival_ns = (self.clock_ns if arrival_ns is None
                          else float(arrival_ns))
        # Speculative read at enqueue time: if this request's pages sit in
        # the cold tier, pre-share the addresses with the EP (MemSpecRd)
        # now — admission happens ticks later, so the fill runs ahead of
        # the demand fetch the restore will stall on.
        if self.tier is not None and not self.legacy \
                and self.cfg.family in _RESTORABLE_FAMILIES:
            key = self._store_key(req.rid, tuple(req.prompt))
            if key is not None:
                self.tier.speculative_read(
                    key, CxlTier.entry_bytes(self.store.pages[key]))
        if self.spans.on:
            req._queued_ns = time.perf_counter_ns()
        self.queue.append(req)
        return RequestHandle(req, self)

    def _batch_axes(self):
        """Locate each cache leaf's batch axis (differencing two shapes)."""
        if not hasattr(self, "_baxes"):
            a = M.cache_init(self.cfg, self.rc, 2, max_seq=self.max_seq,
                             as_shape=True)
            b = M.cache_init(self.cfg, self.rc, 3, max_seq=self.max_seq,
                             as_shape=True)
            self._baxes = jax.tree_util.tree_map(
                lambda x, y: next(i for i, (p, q) in
                                  enumerate(zip(x.shape, y.shape))
                                  if p != q), a, b)
        return self._baxes

    def _prefill_slot(self, req: Request, slot: int,
                      tokens: Optional[List[int]] = None) -> None:
        """Chunked device-resident prefill: one dispatch per chunk.

        ``tokens`` overrides the ingested sequence (default: the
        request's prompt) — the recompute-resume path feeds the prompt
        plus the already-generated prefix through the same chunked path.
        """
        prompt = list(req.prompt) if tokens is None else list(tokens)
        if len(prompt) + 1 > self.max_seq:
            raise ValueError(f"prompt ({len(prompt)} tokens) does not fit "
                             f"a {self.max_seq}-token slot")
        c = self.prefill_chunk
        chunks = [prompt[i:i + c] for i in range(0, len(prompt), c)]
        pos0, tok = 0, None
        with self.spans.span("serve.prefill", req.rid) as attrs:
            if attrs is not None:
                attrs["chunks"] = [(i * c, len(ch))
                                   for i, ch in enumerate(chunks)]
            for i, chunk in enumerate(chunks):
                arr = np.asarray(chunk, np.int32)[None]          # [1, c]
                if self.cfg.family == "audio":
                    arr = np.broadcast_to(
                        arr[:, None],
                        (1, self.cfg.n_codebooks, len(chunk))).copy()
                final = i == len(chunks) - 1
                with self._mesh_scope():
                    out = self._prefill_fn(
                        self.params, self.cache, jnp.asarray(arr), slot,
                        pos0, pos0 + len(chunk), self.last_tokens, self.key,
                        final)
                if final:
                    self.cache, self.last_tokens, tok, self.key, *routing = out
                else:
                    self.cache, *routing = out
                self._add_routing(routing)
                pos0 += len(chunk)
                self.stats["prefill_dispatches"] += 1
            self.stats["prefill_tokens"] += len(prompt)
            self._pos_host[slot] = len(prompt)
            req._first_tok = tok
            req._start_tick = self._tick
            req._n_gen = 1
            req._n_dec = 0
            if req.first_token_ns is None:
                req.first_token_ns = self.clock_ns
            self.stats["decode_tokens"] += 1
            if self.sync_prefill:
                tok.block_until_ready()

    def _prefill_slot_legacy(self, req: Request, slot: int) -> None:
        """Pre-rewrite path: one decode_step dispatch per prompt token on a
        mini cache, host-side splice, host argmax. Kept as the serve_bench
        baseline."""
        mini = M.cache_init(self.cfg, self.rc, 1, max_seq=self.max_seq)
        logits = None
        for t in req.prompt:
            tok = (jnp.full((1, self.cfg.n_codebooks, 1), t, jnp.int32)
                   if self.cfg.family == "audio"
                   else jnp.full((1, 1), t, jnp.int32))
            with self._mesh_scope():
                logits, mini = self.step_fn(self.params, mini, tok)
            self.stats["prefill_tokens"] += 1
            self.stats["prefill_dispatches"] += 1

        def splice(dst, src, axis):
            idx = [slice(None)] * dst.ndim
            idx[axis] = slot
            src_idx = [slice(None)] * src.ndim
            src_idx[axis] = 0
            return dst.at[tuple(idx)].set(src[tuple(src_idx)].astype(
                dst.dtype))

        self.cache = jax.tree_util.tree_map(splice, self.cache, mini,
                                            self._batch_axes())
        if logits is not None:
            row = np.asarray(logits.astype(jnp.float32)).reshape(
                -1, logits.shape[-1])[-1]
            req.generated.append(int(row.argmax()))
            if req.first_token_ns is None:
                req.first_token_ns = self.clock_ns
            self.stats["decode_tokens"] += 1

    # ----------------------------------------------------- prefix restore
    def _store_key(self, rid: int, prompt: Tuple[int, ...]) -> Optional[int]:
        """Cold-tier key holding pages for (rid, prompt), else None.

        A *confirmed* hit refreshes the entry's LRU recency (via
        ``store.get``): the queued request will demand-fetch exactly
        those pages at admission, ticks from now — without the touch a
        hot, about-to-be-restored prefix could age out behind entries no
        one is waiting for, turning the queued SR into a wasted prefetch
        and the restore into a full re-prefill. Mismatched probes still
        read ``store.pages`` directly and leave recency alone."""
        entry = self.store.pages.get(rid)
        if entry is not None and entry.get("prompt") == prompt:
            self.store.get(rid)
            return rid
        alias = self._prompt_index.get(prompt)
        if alias is not None:
            entry = self.store.pages.get(alias)
            if entry is not None and entry.get("prompt") == prompt:
                self.store.get(alias)
                return alias
        return None

    def _lookup_pages(self, rid: int, prompt: Tuple[int, ...]):
        """Staging index first (latest-write-wins, the deterministic-store
        read path), then the cold tier; rid match first, then prompt.

        Returns ``(entry, store_key, source)``: source "staging" is the
        read-through path (reserved GPU memory — no CXL fetch to charge),
        source "store" is a cold-tier hit whose demand fetch the restore
        stalls on (charged against the CxlTier when one is attached)."""
        for _, entry in reversed(self.flusher.pending):
            if isinstance(entry, dict) and entry.get("prompt") == prompt:
                return entry, None, "staging"
        entry = self.store.get(rid)
        if entry is not None and entry.get("prompt") == prompt:
            return entry, rid, "store"
        alias = self._prompt_index.get(prompt)
        if alias is not None and alias != rid:
            entry = self.store.get(alias)
            if entry is not None and entry.get("prompt") == prompt:
                return entry, alias, "store"
        return None, None, None

    def _restore_lookup(self, req: Request):
        """Restorable (entry, store_key, source) for ``req``, else None.

        Pure lookup — no timing is charged; the scheduler decides whether
        the fetch is blocking or issued asynchronously."""
        if not self._restorable:
            return None
        entry, key, source = self._lookup_pages(req.rid, tuple(req.prompt))
        if entry is None or "pos" not in entry or "first_token" not in entry:
            return None
        if int(entry["pos"]) >= self.max_seq - 1:
            return None                       # no room left to decode into
        return entry, key, source

    def _apply_restore(self, req: Request, slot: int, entry) -> None:
        """Rebuild the slot from a retired entry (the data half of the
        speculative-read fetch; any simulated stall was already charged).

        The stored entry captures the *post-prefill* state — pages plus
        the prompt's first sampled token at pos=len(prompt) — so a
        restored request reproduces the prompt-conditioned continuation
        (greedy-identical to a fresh prefill) rather than extending the
        previous generation.
        """
        with self.spans.span("serve.restore", req.rid) as attrs:
            if attrs is not None:
                attrs["bytes"] = CxlTier.entry_bytes(entry)
            if self.store.is_pending(entry):
                # its leaves are still the device arrays: no transfer
                self.stats["flush_pending_restores"] += 1
            first = int(entry["first_token"])
            kv = jax.tree_util.tree_map(jnp.asarray, entry["kv"])
            self.cache["kv"] = jax.tree_util.tree_map(
                lambda a, h: a.at[:, slot].set(h.astype(a.dtype)),
                self.cache["kv"], kv)
            self.cache["pos"] = self.cache["pos"].at[slot].set(
                int(entry["pos"]))
            self.last_tokens = self.last_tokens.at[slot].set(first)
            self._pos_host[slot] = int(entry["pos"])
            req.restored = True
            req._first_tok = None
            req._start_tick = self._tick
            req.generated = req.generated + [first]
            req._n_gen = 1
            req._n_dec = 0
            if req.first_token_ns is None:
                req.first_token_ns = self.clock_ns

    # -------------------------------------------------- preemption state
    def _capture_slot_kv(self, slot: int):
        """This slot's KV pages as a host-free pytree view (or None)."""
        if "kv" not in self.cache:
            return None
        return jax.tree_util.tree_map(
            lambda a: a[:, slot] if a.ndim > 1 else a[slot],
            self.cache["kv"])

    def _capture_swap_entry(self, req: Request, slot: int) -> Dict:
        """Snapshot a running slot's mid-decode state for swap-out:
        pages, current position and the last sampled token — everything a
        swap-in needs to continue the stream bit-for-bit (greedy)."""
        kv = self._capture_slot_kv(slot)
        if kv is not None:
            kv = jax.tree_util.tree_map(np.asarray, kv)
        return {"kv": kv, "pos": self._pos_host[slot],
                "last_token": req.generated[-1] if req.generated else 0,
                "prompt": tuple(req.prompt)}

    def _apply_swap_in(self, req: Request, slot: int, entry) -> None:
        """Resume a swapped-out request: pages, position and last token
        back into the slot; decode continues where it was preempted."""
        kv = jax.tree_util.tree_map(jnp.asarray, entry["kv"])
        self.cache["kv"] = jax.tree_util.tree_map(
            lambda a, h: a.at[:, slot].set(h.astype(a.dtype)),
            self.cache["kv"], kv)
        pos = int(entry["pos"])
        self.cache["pos"] = self.cache["pos"].at[slot].set(pos)
        self.last_tokens = self.last_tokens.at[slot].set(
            int(entry["last_token"]))
        self._pos_host[slot] = pos
        req._first_tok = None
        req._start_tick = self._tick
        req._n_gen = len(req.generated)
        req._n_dec = 0

    def _recompute_resume(self, req: Request, slot: int) -> None:
        """Resume a recompute-preempted request by re-prefilling the
        prompt plus the already-generated prefix (pages were dropped at
        preemption — the compute-for-capacity trade of the policy flag).

        The chunked prefill re-derives the KV for every consumed token;
        its re-sampled final token is discarded — the stream already
        holds it (``generated[-1]``), which becomes the next decode
        input, so the greedy continuation is unchanged.
        """
        if not req.generated:             # preempted pre-prefill: fresh
            self._prefill_slot(req, slot)
            return
        fed = list(req.prompt) + req.generated[:-1]
        self._prefill_slot(req, slot, tokens=fed)
        req._first_tok = None             # drop the re-sampled duplicate
        self.stats["decode_tokens"] -= 1
        req._n_gen = len(req.generated)
        self.last_tokens = self.last_tokens.at[slot].set(
            int(req.generated[-1]))

    # ----------------------------------------------------------- advance
    def _advance(self) -> None:
        """One fused decode+sample dispatch; tokens stay on device."""
        with self.spans.span("serve.decode") as attrs, self._mesh_scope():
            if attrs is not None:
                attrs["live"] = [self._pos_host[s] + 1
                                 for s, r in enumerate(self.slots)
                                 if r is not None]
            self.cache, self.last_tokens, self.key, *routing = \
                self._decode_fn(self.params, self.cache, self.last_tokens,
                                self.key)
            self._add_routing(routing)
        self.stats["steps"] += 1
        self.stats["decode_dispatches"] += 1
        self._trace[self._tick] = self.last_tokens
        self._tick += 1
        for slot, req in enumerate(self.slots):
            self._pos_host[slot] += 1     # decode_step advances every row
            if req is None:
                continue
            req._n_gen += 1
            req._n_dec += 1
            self.stats["decode_tokens"] += 1

    def _advance_legacy(self) -> Dict[int, int]:
        """Pre-rewrite tick: full logits to host, numpy-RNG sampling."""
        toks = np.zeros((self.n_slots, 1), np.int32)
        if self.cfg.family == "audio":
            toks = np.zeros((self.n_slots, self.cfg.n_codebooks, 1),
                            np.int32)
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            last = req.generated[-1] if req.generated else 0
            if self.cfg.family == "audio":
                toks[slot, :, 0] = last
            else:
                toks[slot, 0] = last
        with self._mesh_scope():
            logits, self.cache = self.step_fn(self.params, self.cache,
                                              jnp.asarray(toks))
        logits.block_until_ready()
        self.stats["steps"] += 1
        self.stats["decode_dispatches"] += 1
        out: Dict[int, int] = {}
        lg = np.asarray(logits.astype(jnp.float32))
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            row = lg[slot, -1] if lg.ndim == 3 else lg[slot, 0, -1]
            if self.temperature > 0:
                self.key, sub = jax.random.split(self.key)
                row = row / self.temperature
                p = np.exp(row - row.max())
                p /= p.sum()
                tok = int(np.random.default_rng(
                    int(jax.random.randint(sub, (), 0, 2**31 - 1))
                ).choice(len(p), p=p))
            else:
                tok = int(row.argmax())
            out[slot] = tok
        return out

    # -------------------------------------------------------------- run
    def _materialize_tokens(self, req: Request, slot: int) -> None:
        """Pull the request's sampled tokens off the device trace into
        ``req.generated`` (retirement and swap-out both need the stream
        on the host); resets the trace span so a resumed request appends
        cleanly."""
        toks: List[int] = []
        if req._first_tok is not None:
            toks.append(int(np.asarray(req._first_tok)))
        for t in range(req._start_tick, req._start_tick + req._n_dec):
            toks.append(int(self._tok_tick(t)[slot]))
        req.generated = req.generated + toks
        req._first_tok = None
        req._start_tick = self._tick
        req._n_dec = 0

    def _retire(self, slot: int) -> None:
        """Deterministic store: release the slot immediately; its pages
        flush to the host tier in the background. The only host transfers
        on the hot path happen here: the request's sampled tokens and its
        retiring pages."""
        with self.spans.span("serve.retire", self.slots[slot].rid):
            req = self.slots[slot]
            req.done = True
            req.state = sched.RETIRED
            req.finish_ns = self.clock_ns
            if not self.legacy:
                self._materialize_tokens(req, slot)
                self._read_routing()
            kv_slot = self._capture_slot_kv(slot)
            if kv_slot is not None and req.generated:
                # snapshot the post-prefill state: pages + the prompt's first
                # sampled token at pos=len(prompt). Pages beyond the prompt
                # are masked by pos and overwritten as a restored slot decodes.
                self.flusher.stage(req.rid, {
                    "kv": kv_slot, "pos": len(req.prompt),
                    "first_token": req.generated[0],
                    "prompt": tuple(req.prompt)})
            self.finished.append(req)
            self.slots[slot] = None

    def _add_routing(self, routing) -> None:
        """Sum a program's routing counts (its outputs after the usual
        ones: none without experts) on the device, with no transfer."""
        for counts in routing:
            self._routing = self._routing + counts

    def _read_routing(self) -> None:
        """Fold the device's routing counts into the stats. Called at
        retirement, whose token transfer has already waited for the
        programs that made them, so this adds no wait of its own."""
        if self._routing is None:
            return
        routed, touched, calls = (int(v) for v in np.asarray(self._routing))
        self._routing = jnp.zeros_like(self._routing)
        self.stats["moe_local_routings"] += routed
        self.stats["moe_expert_touches"] += touched
        self.stats["moe_decode_calls_counted"] += calls

    def _tok_tick(self, t: int) -> np.ndarray:
        """Materialize one tick's [n_slots] sampled tokens, memoized so
        co-retiring slots share a single transfer."""
        arr = self._trace_np.get(t)
        if arr is None:
            arr = np.asarray(self._trace[t])
            self._trace_np[t] = arr
        return arr

    def _prune_trace(self) -> None:
        """Drop trace entries no live request can still need."""
        starts = [r._start_tick for r in self.slots if r is not None]
        if not starts:
            self._trace.clear()
            self._trace_np.clear()
            return
        low = min(starts)
        for t in [t for t in self._trace if t < low]:
            self._trace.pop(t, None)
            self._trace_np.pop(t, None)

    def _drop_prompt_alias(self, rid: int, entry, reason: str) -> None:
        """Keep side state in lockstep with store evictions.

        Drops the prompt->rid alias for the departing entry and — only
        for true LRU evictions (``reason="evict"``) — releases the
        entry's CXL-tier segments for reuse. A ``"replace"`` fires while
        the same rid's fresh pages are being re-inserted (the flush
        already rewrote the tier segments in place), so freeing there
        would tear down ranges that are still live.
        """
        if isinstance(entry, dict):
            prompt = entry.get("prompt")
            if prompt is not None and self._prompt_index.get(prompt) == rid:
                del self._prompt_index[prompt]
        if reason == "evict" and self.tier is not None:
            self.tier.free_entry(rid)

    def _store_sink(self, rid: int, entry) -> None:
        with self.spans.span("serve.flush", rid) as attrs:
            if attrs is not None:
                attrs["bytes"] = CxlTier.entry_bytes(entry)
            if self.tier is not None:
                # the background drain: page writes ride the deterministic-
                # store path (GPU-speed completion, divert under congestion).
                # In async mode the flush is a background op — the writer is
                # held only for the issue-slot wait and the media work
                # completes on the port cursors as simulated time passes.
                nbytes = CxlTier.entry_bytes(entry)
                if self.cxl_async:
                    handle = self.tier.write_entry_async(rid, nbytes)
                    self._async_writes.append(handle)
                    self.stats["tier_write_ns"] += handle.issue_wait_ns
                    self.scheduler._note_inflight_peak()
                else:
                    self.stats["tier_write_ns"] += self.tier.write_entry(
                        rid, nbytes)
            kept = self.store.put(rid, entry)
            # alias only entries that survived admission: budget pressure can
            # evict an entry during its own put (oversized, or a re-staged rid
            # growing past the budget), and its on_evict has already fired —
            # indexing it afterwards would leak a dangling prompt alias
            if kept and isinstance(entry, dict) and "prompt" in entry:
                self._prompt_index[entry["prompt"]] = rid

    def _n_generated(self, req: Request) -> int:
        return len(req.generated) if self.legacy else req._n_gen

    def _check_done(self, slot: int) -> None:
        req = self.slots[slot]
        pos = (int(np.asarray(self.cache["pos"])[slot]) if self.legacy
               else self._pos_host[slot])
        if (self._n_generated(req) >= req.max_new_tokens
                or pos >= self.max_seq - 1):
            self._retire(slot)

    def step(self) -> None:
        """One engine tick: schedule (activate/preempt/admit), decode,
        retire, background-flush.

        A slot whose restore is still in flight does not stall the
        batch: the other slots keep decoding and the slot activates on
        the tick its completion lands. Only when *every* occupied slot
        is awaiting a fetch does the tick idle — that simulated time is
        exposed stall, accounted against the overlap ratio."""
        with self.spans.span("serve.step"):
            self.scheduler.begin_tick()
            for slot in range(self.n_slots):
                if self.slots[slot] is not None:
                    # prefill/restore may already satisfy
                    self._check_done(slot)
            active = any(s is not None for s in self.slots)
            if not active and not self.scheduler.busy():
                return
            if not active:
                # all occupied slots are RESTORING: the batch idles this tick
                # while simulated time (below) brings the completions closer
                self.scheduler.note_blocked_tick(self.tier_step_ns)
            elif self.legacy:
                sampled = self._advance_legacy()
                for slot, tok in sampled.items():
                    req = self.slots[slot]
                    req.generated.append(tok)
                    self.stats["decode_tokens"] += 1
                    self._check_done(slot)
            else:
                self._advance()
                for slot in range(self.n_slots):
                    if self.slots[slot] is not None:
                        self._check_done(slot)
            if not self.legacy:
                self._prune_trace()
            # QoS: occupancy = queue pressure; flushes gated by DevLoad
            occ = len(self.flusher.pending) / max(self.n_slots * 2, 1)
            dl = self.qos.classify(occupancy=min(occ, 1.0), service_ratio=1.0)
            self.qos.update(dl)
            self.stats["flushes"] += self.flusher.maybe_flush()
            self._tier_tick()
            self._store_stats()

    def _tier_tick(self) -> None:
        """Advance simulated time one engine tick and surface tier +
        scheduler state.

        With a multi-port tier attached this is also the blocking-op
        drain barrier: per-port clocks (which skew freely within a tick)
        realign, while async op handles keep riding the service cursors
        until simulated time reaches their completions. All surfaced
        telemetry is live and cheap — ``tier.port_stats()`` updates its
        per-port dicts in place, so reading it every tick costs no
        allocation churn and no drain."""
        self.clock_ns += self.tier_step_ns
        self.stats["clock_ns"] = self.clock_ns
        self.stats["flush_backlog"] = len(self.flusher.pending)
        ss = self.scheduler.stats
        self.stats["preemptions"] = ss["preemptions"]
        self.stats["swap_out_bytes"] = ss["swap_out_bytes"]
        self.stats["swap_in_bytes"] = ss["swap_in_bytes"]
        self.stats["restore_inflight_ns"] = ss["restore_inflight_ns"]
        infl = ss["restore_inflight_ns"]
        self.stats["restore_overlap_ratio"] = max(
            0.0, 1.0 - ss["restore_exposed_ns"] / infl) if infl > 0 else 0.0
        self.stats["sched_inflight_peak"] = ss["inflight_peak"]
        self.stats["recoveries"] = ss["recoveries"]
        if self.tier is None:
            return
        self.tier.advance(self.tier_step_ns)
        self._fault_sweep()
        if self._async_writes:      # retire completed background flushes
            self._async_writes = [h for h in self._async_writes
                                  if not self.tier.poll(h)]
        self.stats["sim_time_ns"] = self.tier.topo.now
        self.stats["sched_inflight_ops"] = self.tier.inflight_ops()
        self.stats["tier_sr_hit_rate"] = self.tier.sr_hit_rate()
        self.stats["tier_store_occupancy"] = self.tier.store_occupancy()
        self.stats["tier_ports"] = self.tier.port_stats()
        self.stats["flushes_deferred"] = self.flusher.deferred
        tc = self.tier.counters
        self.stats["tier_promotions"] = tc["promotions"]
        self.stats["tier_demotions"] = tc["demotions"]
        self.stats["tier_migrate_ns"] = tc["migrate_ns"]
        self.stats["tier_fault_ops"] = tc["fault_ops"]
        self.stats["tier_lost_entries"] = tc["lost_entries"]
        self.stats["tier_lost_bytes"] = tc["lost_bytes"]
        self.stats["tier_fault_retries"] = sum(
            p.fault_retries for p in self.tier.topo.ports)
        self.stats["tier_fault_failures"] = sum(
            p.fault_failures for p in self.tier.topo.ports)
        self.stats["tier_ports_down"] = len(self.tier.topo.ports_down())
        if "peer_fetches" in tc:        # ShardedTier: cross-rank telemetry
            self.stats["tier_peer_fetches"] = tc["peer_fetches"]
            self.stats["tier_peer_bytes"] = tc["peer_bytes"]
            self.stats["tier_peer_fetch_ns"] = tc["peer_fetch_ns"]
            self.stats["tier_rank_remaps"] = tc["rank_remaps"]
            self.stats["tier_peer_recoveries"] = tc["peer_recoveries"]
            self.stats["tier_rehomes"] = tc["rehomes"]
            self.stats["tier_multi_source_reads"] = tc["multi_source_reads"]

    def _fault_sweep(self) -> None:
        """Fold newly-fired tier faults into serving state.

        ``tier.advance`` already invalidated every entry on a
        hot-removed port; this drains the lost keys and repairs the
        serving side: a lost store entry's host copy is dropped (the
        next lookup misses and prefills fresh — the tier copy it would
        restore from is gone), and a lost swap payload is downgraded to
        a recompute marker (only the token stream survives; resume rides
        the ``preempt_policy="recompute"`` re-prefill path). Runs after
        every simulated-time advance and always before the next tick's
        admissions, so a recovering request can never re-admit against a
        dead copy.
        """
        if self.tier is None:
            return
        for key in self.tier.take_lost_keys():
            if isinstance(key, tuple) and len(key) == 2 \
                    and key[0] == "swap":
                rid = key[1]
                if rid in self.scheduler.swapped:
                    self.scheduler.swapped[rid] = {"recompute": True}
            else:
                self.store.drop(key)

    def advance_time(self, dt_ns: float) -> None:
        """Jump the simulated clock across an idle window (no decode work).

        The open-loop driver calls this when the engine is drained but
        the next arrival is still in the future: the engine clock and the
        tier both see the gap (background flushes complete, QoS ladders
        and GC windows stay live), without charging any decode ticks.
        """
        if dt_ns <= 0:
            return
        self.clock_ns += float(dt_ns)
        self.stats["clock_ns"] = self.clock_ns
        if self.tier is not None:
            self.tier.advance(float(dt_ns))
            self._fault_sweep()
            if self._async_writes:
                self._async_writes = [h for h in self._async_writes
                                      if not self.tier.poll(h)]
            self.stats["sim_time_ns"] = self.tier.topo.now
            self.stats["sched_inflight_ops"] = self.tier.inflight_ops()
        self.stats["flushes"] += self.flusher.maybe_flush()

    def _drain_async(self, guard_ticks: int = 10_000) -> None:
        """Tick simulated time until every outstanding async tier op
        lands: in-flight restores activate (and their slots settle) and
        background flush/swap writes retire their ``TierHandle``s — so
        end-of-run stats (``restore_inflight_ns``, per-port ``inflight``
        depth) are consistent wherever the horizon fell."""
        if self.tier is None:
            return
        ticks = 0
        while (self.scheduler.busy() or self.tier.inflight_ops() > 0) \
                and ticks < guard_ticks:
            self.tier.advance(self.tier_step_ns)
            self.clock_ns += self.tier_step_ns
            self._fault_sweep()
            self.scheduler.drain()
            if self._async_writes:
                self._async_writes = [h for h in self._async_writes
                                      if not self.tier.poll(h)]
            ticks += 1

    def run(self, max_ticks: int = 1000) -> List[Request]:
        """Tick until the queue, slots and in-flight restores drain (or
        ``max_ticks``); returns the finished requests in retirement
        order (the pre-``RequestHandle`` return shape, kept as a shim —
        new callers read their handles instead).

        Whatever the horizon, outstanding async tier ops are drained
        before returning: pending flushes/swap writes complete on the
        simulated clock and in-flight restores land (their requests
        settle into slots; they still need decode ticks to finish); the
        host store's last device-to-host copy lands too."""
        ticks = 0
        while (self.queue or any(s is not None for s in self.slots)
               or self.scheduler.busy()) and ticks < max_ticks:
            self.step()
            ticks += 1
        self.flusher.maybe_flush()
        self.store.sync()
        self._drain_async()
        self._tier_tick()
        self._store_stats()
        return self.finished

    def _store_stats(self) -> None:
        st, store = self.stats, self.store
        st["store_bytes"] = store.bytes
        st["store_evictions"] = store.evictions
        st["flush_async"] = store.flush_async
        st["flush_wait_ms"] = store.flush_wait_ms
