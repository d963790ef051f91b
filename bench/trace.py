"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

* Busy time: the union of the intervals in which an operation ran on a
  device (the device plane's ``XLA Ops`` line), inside the traced
  window, averaged over the devices used.
* Program time: the device time of each jitted program (the ``XLA
  Modules`` line), by name, with its count of calls.
* Idle gaps: the stretches of the window with no operation on the
  first device, each labelled with the innermost ``bench.*`` host span
  open at its middle (what the host was doing).

The window is the host span ``WINDOW_SPAN`` when the trace holds one,
else the extent of the device events (ops and programs).
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.trace_window"
TOP = 10


def find_xplane(directory: str) -> str:
    """The one ``.xplane.pb`` a trace into ``directory`` wrote."""
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under "
                                f"{directory}, found {found}")
    return found[0]


def program_name(event_name: str) -> str:
    """``jit__decode_sample(17)`` -> ``jit__decode_sample``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge [start, end) intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _events(plane, line_name):
    for line in plane.lines:
        if line.name == line_name:
            for e in line.events:
                yield e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)


def _label(spans, t: int) -> str:
    best: Optional[Tuple[int, str]] = None
    for s, e, name in spans:
        if s <= t < e and name != WINDOW_SPAN and (best is None
                                                   or s >= best[0]):
            best = (s, name)
    return best[1] if best else "no span"


def reduce_planes(devices: Dict[int, Dict[str, list]],
                  spans: List[Tuple[int, int, str]]) -> Dict:
    """The reduction over plain data: ``devices`` maps a device index to
    its ``ops`` and ``modules`` lists of (name, start_ns, end_ns);
    ``spans`` lists the host's (start_ns, end_ns, name)."""
    win = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if win:
        lo, hi = min(s for s, _ in win), max(e for _, e in win)
    else:
        every = [(s, e) for d in devices.values()
                 for _, s, e in d["ops"] + d["modules"]]
        if not every:
            return {"window_s": 0.0, "busy_s": 0.0, "programs": {},
                    "idle_gaps": [], "device_ops": []}
        lo, hi = min(s for s, _ in every), max(e for _, e in every)
    busy, programs = [], collections.defaultdict(lambda: [0, 0.0])
    first_busy: List[Tuple[int, int]] = []
    for idx in sorted(devices):
        iv = union(_clip([(s, e) for _, s, e in devices[idx]["ops"]], lo, hi))
        busy.append(sum(e - s for s, e in iv) / 1e9)
        if not first_busy:
            first_busy = iv
        for name, s, e in devices[idx]["modules"]:
            if s >= lo and s < hi:
                p = programs[program_name(name)]
                p[0] += 1
                p[1] += (min(e, hi) - s) / 1e9
    gaps, t = [], lo
    for s, e in first_busy + [(hi, hi)]:
        if s > t:
            gaps.append((_label(spans, (s + t) // 2), (s - t) / 1e9))
        t = max(t, e)
    gaps.sort(key=lambda g: -g[1])
    ranked = sorted(programs.items(), key=lambda kv: -kv[1][1])
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy) / len(busy) if busy else 0.0,
            "programs": {k: {"calls": v[0], "seconds": v[1]}
                         for k, v in programs.items()},
            "idle_gaps": [[n, s] for n, s in gaps[:TOP]],
            "device_ops": [[k, v[1]] for k, v in ranked[:TOP]]}


def reduce_file(path: str, n_devices: int = 1) -> Dict:
    """:func:`reduce_planes` of a recorded ``.xplane.pb``, over the
    first ``n_devices`` TPU devices."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) < n_devices:
            devices[int(m.group(1))] = {
                "ops": list(_events(plane, OPS_LINE)),
                "modules": list(_events(plane, MODULES_LINE))}
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = int(e.start_ns)
                        spans.append((s, s + int(e.duration_ns), e.name))
    return reduce_planes(devices, spans)
