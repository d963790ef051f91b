"""Run one cell of the serving benchmark and print its result.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: check the device (a TPU whose kind is in ``bench/peaks.py``,
as many chips as the cell asks for, else exit 2 with no result); build
the engine of the cell's configuration with seeded weights; warm every
program the cell's traffic uses and fill the host store where the
traffic says so (all of it ``setup_s``); drive the traffic for
``--seconds`` on the wall clock; free the program's device state and
compare a sample of what the window served with the plain float32
reference; print the cell's end-to-end metrics (``--trace 0``) or its
per-layer metrics read from a profile of the window's last seconds
(``--trace 1``) as one JSON line, the last on stdout. The numbers
compared and their limits are the last lines on stderr and the last key
of the JSON line.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()      # set-up is timed from the process's start

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Optional  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import (check, drive, spans, spec, system, trace,  # noqa: E402
                   traffic)
from bench.peaks import peaks_for  # noqa: E402

TRACE_SECONDS = 8.0            # profiled tail of the window (--trace 1)


class NoChip(RuntimeError):
    """The machine lacks what the cell needs: no result is printed."""


class CompileClock:
    """Programs made ready since construction: ``n`` counts every
    backend compile request, ``hits`` those the persistent cache served,
    so ``n - hits`` were compiled."""

    def __init__(self):
        import jax
        self.n = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    @property
    def compiled(self) -> int:
        return self.n - self.hits


def check_device(chips: int):
    """The first device, if it is a TPU of a known kind and the machine
    has ``chips`` of them; else :class:`NoChip`."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoChip(f"no TPU: JAX found {d.platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    try:
        peaks_for(d.device_kind)
    except KeyError as e:
        raise NoChip(str(e)) from None
    return d


def enable_cache() -> str:
    """JAX's persistent compile cache at the checkout's fixed path, for
    every program however quick to compile."""
    import jax
    from repro.launch.compile_cache import enable_compilation_cache
    where = enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


class Tracer:
    """Profiles the last ``TRACE_SECONDS`` of the window (``--trace 1``).
    From the window's start it keeps the ``Recorder``'s records and the
    engine's span log (``bench/spans.py``), whose records cover the whole
    window and whose ``serve.*`` spans the profile shows beside the
    device ops."""

    def __init__(self, seconds: float, recorder):
        import jax
        self.jax = jax
        self.start_at = max(0.0, seconds - TRACE_SECONDS)
        self.recorder = recorder
        self.dir = None
        self.t = None                   # host clock at its start and end
        self._span = None

    def __call__(self, now: float) -> None:
        if not self.recorder.on:
            self.recorder.on = True
            spans.start(self.recorder.engine)
        if self.dir is None and now >= self.start_at:
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            self.jax.profiler.start_trace(self.dir)
            self._span = self.jax.profiler.TraceAnnotation(trace.WINDOW_SPAN)
            self._span.__enter__()
            self.t = [time.perf_counter(), None]

    def stop(self, n_devices: int):
        """The trace's reduction (None where the profile never started)
        and :func:`spans.reduce` of the engine's span log over it."""
        if self.dir is None:
            return None, spans.reduce(self.recorder.engine)
        self._span.__exit__(None, None, None)
        self.t[1] = time.perf_counter()
        self.jax.profiler.stop_trace()
        try:
            xplane = trace.find_xplane(self.dir)
            red = trace.reduce_file(xplane, n_devices)
            serve = spans.reduce(self.recorder.engine,
                                 spans.reduce_file(xplane))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        red["host_t0"], red["host_t1"] = self.t
        return red, serve


def run_cell(root: pathlib.Path, workload: str, seed: int, seconds: float,
             traced: bool, *, require_tpu: bool = True,
             compile_cache: bool = True, t0: float = _T0,
             log=print, rate: Optional[float] = None,
             control: bool = False, record_out: Optional[dict] = None
             ) -> dict:
    """Run one cell; returns the result object (``check`` last).

    Tools only: ``rate`` overrides an open loop's rate (the knee sweep);
    ``control`` puts the fp8 control in the program's place in the
    comparison, so the result reads ``correct: false`` where the limit
    is sound; the run's record, with the sample and the finished
    requests, is copied into ``record_out``."""
    import jax
    cell = spec.load_cell(workload, root)
    if rate is not None:
        cell = dataclasses.replace(cell, traffic=dict(cell.traffic,
                                                      rate_rps=rate))
    dev = check_device(cell.chips) if require_tpu else jax.devices()[0]
    peaks = peaks_for(dev.device_kind) if require_tpu else None
    log(f"[bench] {cell.name}: {dev.platform} {dev.device_kind!r} x "
        f"{len(jax.devices())}, jax {jax.__version__}, seed {seed}")
    if compile_cache:
        log(f"[bench] compile cache {enable_cache()}")
    clock = CompileClock()
    config, m = cell.config, cell.config["model"]
    plan = traffic.build(cell.traffic, seed, seconds, m["vocab_size"])
    phases = [("start", time.perf_counter() - t0)]
    with system.mesh_scope(config):
        engine = system.build_engine(config, seed, root)
        phases.append(("engine", time.perf_counter() - t0))
        system.warm(engine, m["vocab_size"],
                    restores=bool(cell.traffic.get("restores")))
        phases.append(("warm", time.perf_counter() - t0))
        if plan.store_fill:
            system.fill_store(engine, plan.store_fill)
        recorder = system.Recorder(engine) if traced else None
        tracer = Tracer(seconds, recorder) if traced else None
        setup_s = time.perf_counter() - t0
        phases.append(("fill", setup_s))
        log("[bench] set-up phases ended at (s): " + ", ".join(
            f"{k} {v:.3f}" for k, v in phases))
        log(f"[bench] set-up {setup_s:.3f} s: {clock.n} programs made "
            f"ready, {clock.hits} from the compile cache, {clock.compiled} "
            f"compiled; "
            f"store {engine.store.bytes / 2**20:.0f} MiB in "
            f"{len(engine.store.pages)} entries")
        n0 = clock.n
        loop = drive.open_loop if plan.loop == "open" else drive.closed_loop
        win = loop(engine, plan, seconds, traced=traced, on_time=tracer)
        in_window = clock.n - n0
        mem = system.peak_memory(cell.chips)
        traced_red, serve_spans = tracer.stop(cell.chips) if tracer else \
            (None, None)
        done = check.finished(win)
        admitted = sum(1 for h in win.handles
                       if h is not None and h.request.state != "QUEUED")
        record = {
            "cell": cell.name, "root": str(root), "loop": plan.loop,
            "model": m,
            "n_slots": engine.n_slots, "window_s": win.seconds,
            "setup_s": setup_s, "latencies_s": win.latencies_s,
            "tokens": win.tokens, "counters": win.counters,
            "requests": {"submitted": len(win.requests),
                         "admitted": admitted, "completed": len(done)},
            "entry_bytes": system.entry_bytes(engine), "peaks": peaks,
            "decode_calls": recorder.decode if recorder else [],
            "prefill_calls": recorder.prefill if recorder else [],
            "programs": {"decode": system.DECODE_PROGRAM,
                         "prefill": system.PREFILL_PROGRAM},
            "trace": traced_red, "serve_spans": serve_spans,
            "queue_depth": win.queue_depth}
        win_depth = win.queue_depth
        engine = recorder = win = tracer = None
    gc.collect()
    system.free_device_state()
    n_lat = len(record["latencies_s"])
    log(f"[bench] window {record['window_s']:.3f} s: "
        f"{record['requests']['submitted']} submitted, "
        f"{record['requests']['completed']} done, {n_lat} latency samples "
        f"({n_lat - math.ceil(0.9 * n_lat)} beyond p90), "
        f"{record['tokens']} closed-loop tokens; counters not 0 "
        f"{ {k: v for k, v in record['counters'].items() if v} }")
    log(f"[bench] programs made ready inside the window: {in_window}; "
        f"queue depth, first and second half: {_halves(win_depth)}")
    picks = check.sample(done, seed)
    log(f"[bench] comparing {len(picks)} requests, "
        f"{sum(len(p['tokens']) for p in picks)} served tokens, "
        f"{sum(p['restored'] for p in picks)} restored")
    t_ref = time.perf_counter()
    ref = spec.reference_module(root, config["reference"])
    if record_out is not None:
        record_out.update(record, in_window_compiles=in_window,
                          picks=picks, done=done)
    if control:
        picks = check.control(ref, seed, m, picks)
    checks = check.compare(ref, seed, m, picks, done, config["check"])
    log(f"[bench] reference took {time.perf_counter() - t_ref:.3f} s")
    kind = "per_layer" if traced else "end_to_end"
    metrics = {}
    for metric in cell.reported(kind):
        v = spec.metric_reader(root, metric.name)(record)
        if v is not None:
            metrics[metric.name] = {"value": float(v), "unit": metric.unit}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    result = {"correct": check.passed(checks),
              "attempted": record["requests"]["submitted"],
              "failed": checks["short_outputs"]["value"],
              "metrics": metrics, "device": device}
    if traced and record["trace"]:
        t = record["trace"]
        device["busy_s"], device["window_s"] = t["busy_s"], t["window_s"]
        result["breakdown"] = {"device_ops": t["device_ops"],
                               "idle_gaps": t["idle_gaps"]}
    result["check"] = checks
    return result


def _halves(depth) -> str:
    """Mean queue depth over the first and the second half of the steps
    (a growing backlog shows as a larger second half)."""
    if not depth:
        return "no steps"
    half = len(depth) // 2 or 1
    a = [d for _, d in depth[:half]]
    b = [d for _, d in depth[half:]] or a
    return f"{sum(a) / len(a):.2f} / {sum(b) / len(b):.2f}"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace),
                          log=lambda s: print(s, flush=True))
    except NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 2
    for name, c in result["check"].items():
        print(f"check {name} {c['value']} {c['rule']} {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
