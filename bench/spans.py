"""The serving engine's own spans (``repro.serving.spans``), reduced for
the per-layer metrics.

Two sources, each on its own clock:

* the engine's records (``engine.spans.records()``), kept from the
  window's start in traced runs, on ``time.perf_counter_ns``: count,
  seconds and bytes by span name, and each flush split into the wait for
  its pages, the copy and the insert (around its ``serve.flush.copy``);
* the ``serve.*`` host spans of the traced window's ``.xplane.pb``, on
  the clock of the device ops: each stretch of the window in which the
  first device runs no op is given to the innermost ``serve.*`` span
  open over it ("no span" where none is), and the part of it in which
  the host is inside ``serve.flush`` is summed apart.

A program without the span log reduces to None, and its metrics read
nothing. A traced run of ``bench/run.py`` starts the log (:func:`start`)
when the ``Recorder`` turns on, at the window's start, keeps
:func:`reduce_file` of the window's ``.xplane.pb`` before the trace
directory is deleted, and puts :func:`reduce` into its record under
``serve_spans``, where the readers at the end find it. Untraced runs
leave the log off.
"""
from __future__ import annotations

import bisect
import collections
from typing import Dict, List, Optional, Tuple

from bench import trace

PREFIX = "serve."
FLUSH, COPY = "serve.flush", "serve.flush.copy"
NO_SPAN = "no span"


def start(engine) -> None:
    """Start the engine's span log, where it has one that is off."""
    log = getattr(engine, "spans", None)
    if log is not None and not log.on:
        log.start()


def reduce_records(records) -> Dict:
    """By span name: ``n``, ``seconds`` and ``bytes`` (of the spans that
    carry bytes); ``flush_split``: seconds of the flushes' wait, copy and
    insert, summed over the flushes whose copy was timed."""
    by: Dict[str, Dict] = {}
    for name, t0, t1, _, _, attrs in records:
        if t1 is None:
            continue
        s = by.setdefault(name, {"n": 0, "seconds": 0.0, "bytes": 0})
        s["n"] += 1
        s["seconds"] += (t1 - t0) / 1e9
        s["bytes"] += int(attrs.get("bytes", 0))
    split = {"n": 0, "wait_s": 0.0, "copy_s": 0.0, "insert_s": 0.0}
    for name, t0, t1, parent, _, _ in records:
        if name != COPY or t1 is None or parent is None:
            continue
        pname, p0, p1 = records[parent][:3]
        if pname != FLUSH or p1 is None:
            continue
        split["n"] += 1
        split["wait_s"] += (t0 - p0) / 1e9
        split["copy_s"] += (t1 - t0) / 1e9
        split["insert_s"] += (p1 - t1) / 1e9
    return {"by_name": by, "flush_split": split}


def _gaps(busy: List[Tuple[int, int]], lo: int, hi: int):
    out, t = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    return out


def _intersect(a, b):
    """Overlap of two sorted lists of disjoint [start, end) intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _by_innermost(idle, spans) -> Dict[str, float]:
    """Seconds of ``idle`` under each innermost span (the open span that
    started last; of two starting together, the shorter)."""
    spans = sorted(spans)
    cuts = sorted({t for s, e, _ in spans for t in (s, e)})
    by: Dict[str, float] = collections.defaultdict(float)
    active: List[Tuple[int, int, str]] = []
    k = 0
    for a, b in idle:
        pts = [a] + cuts[bisect.bisect_right(cuts, a):
                         bisect.bisect_left(cuts, b)] + [b]
        for s, e in zip(pts, pts[1:]):
            while k < len(spans) and spans[k][0] <= s:
                active.append(spans[k])
                k += 1
            active = [x for x in active if x[1] > s]
            name = max(active, key=lambda x: (x[0], -x[1]))[2] \
                if active else NO_SPAN
            by[name] += (e - s) / 1e9
    return dict(by)


def reduce_planes(ops: List[Tuple[int, int]],
                  spans: List[Tuple[int, int, str]],
                  window: Optional[Tuple[int, int]] = None) -> Optional[Dict]:
    """The trace's part over plain data: ``ops`` are the first device's
    (start_ns, end_ns), ``spans`` the host's ``serve.*`` (start_ns,
    end_ns, name), ``window`` the traced window (else the ops' extent).
    None where the trace holds no ``serve.*`` span."""
    spans = [x for x in spans if x[2].startswith(PREFIX)]
    if not spans or not (ops or window):
        return None
    lo, hi = window or (min(s for s, _ in ops), max(e for _, e in ops))
    spans = [(max(s, lo), min(e, hi), n) for s, e, n in spans
             if e > lo and s < hi]
    idle = _gaps(trace.union(trace._clip(ops, lo, hi)), lo, hi)
    flush = trace.union([(s, e) for s, e, n in spans if n == FLUSH])
    by = _by_innermost(idle, spans)
    return {"window_s": (hi - lo) / 1e9,
            "idle_s": sum(e - s for s, e in idle) / 1e9,
            "flush_s": sum(e - s for s, e in flush) / 1e9,
            "flush_idle_s": sum(e - s for s, e in _intersect(idle, flush))
            / 1e9,
            "idle_by_span": dict(sorted(by.items(), key=lambda kv: -kv[1]))}


def reduce_file(path: str) -> Optional[Dict]:
    """:func:`reduce_planes` of a recorded ``.xplane.pb``: the ops of
    device 0, the host's ``serve.*`` spans, the window span. None where
    the trace holds no device plane (a CPU run)."""
    from jax.profiler import ProfileData
    ops, spans, win, device = [], [], [], False
    for plane in ProfileData.from_file(path).planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) == 0:
            device = True
            ops = [(s, e) for _, s, e in trace._events(plane, trace.OPS_LINE)]
        elif plane.name == trace.HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    s = int(ev.start_ns)
                    e = s + int(ev.duration_ns)
                    if ev.name.startswith(PREFIX):
                        spans.append((s, e, ev.name))
                    elif ev.name == trace.WINDOW_SPAN:
                        win.append((s, e))
    if not device:
        return None
    window = (min(s for s, _ in win), max(e for _, e in win)) if win else None
    return reduce_planes(ops, spans, window)


def reduce(engine, serve_trace: Optional[Dict] = None) -> Optional[Dict]:
    """What a traced run keeps of the spans: the engine's records (the
    log is stopped here) and ``serve_trace``, the :func:`reduce_file` of
    its trace. None where the program keeps no spans."""
    log = getattr(engine, "spans", None)
    if log is None:
        return None
    log.stop()
    return dict(reduce_records(log.records()), trace=serve_trace)


# --- readers of the per-layer metrics, from a record's ``serve_spans`` -----

def mean_ms(record: Dict, name: str) -> Optional[float]:
    """Mean length of the span ``name`` over the window, ms."""
    red = record.get("serve_spans")
    s = red["by_name"].get(name) if red else None
    return 1e3 * s["seconds"] / s["n"] if s and s["n"] else None


def copy_gbps(record: Dict) -> Optional[float]:
    """Bytes the flushes copied to the host over the seconds their copies
    took, summed over the window, GB/s."""
    red = record.get("serve_spans")
    s = red["by_name"].get(COPY) if red else None
    if not s or s["seconds"] <= 0 or not s["bytes"]:
        return None
    return s["bytes"] / s["seconds"] / 1e9


def flush_idle_share(record: Dict) -> Optional[float]:
    """Share of the traced window in which the first device runs no op
    while the host is inside ``serve.flush``, %."""
    red = record.get("serve_spans")
    t = red.get("trace") if red else None
    if not t or t["flush_s"] <= 0 or t["window_s"] <= 0:
        return None
    return 100.0 * t["flush_idle_s"] / t["window_s"]
