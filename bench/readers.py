"""Arithmetic the metric files under ``bench/metrics/`` share.

Each reader takes the run's record (built in ``bench/run.py``) and
returns one number, or None where the record holds nothing to read:
then the metric is left out of the result line. A call's operations and
bytes come from the module of the record's model family, under the
record's ``root`` (the checkout that ran it; this one where the record
does not say).
"""
from __future__ import annotations

import pathlib
from typing import Dict, Optional

import numpy as np

from bench import costs, spec


def family(record: Dict):
    """The module of the record's model family (its costs)."""
    root = pathlib.Path(record.get("root") or spec.ROOT)
    return spec.family_module(root, record["model"]["family"])


def percentile_ms(record: Dict, q: float) -> Optional[float]:
    """The ``q``-th percentile of the window's request latencies, ms."""
    lat = record.get("latencies_s") or []
    return float(np.percentile(lat, q)) * 1e3 if lat else None


def _program(record: Dict, which: str):
    t = record.get("trace")
    if not t:
        return None
    name = record["programs"][which]
    calls = secs = 0
    for prog, v in t["programs"].items():
        if name in prog:
            calls += v["calls"]
            secs += v["seconds"]
    return (calls, secs) if calls else None


def program_ms(record: Dict, which: str) -> Optional[float]:
    """Mean device time of one call of the ``which`` program, ms."""
    p = _program(record, which)
    return p[1] / p[0] * 1e3 if p else None


def decode_roofline(record: Dict) -> Optional[float]:
    """Least time of the traced decode calls over their device time, %.

    The least time of a call is computed from the live slots its host
    dispatch saw (calls dispatched inside the traced window), averaged
    and applied to each decode call the device ran in that window."""
    p, t = _program(record, "decode"), record.get("trace")
    peaks = record.get("peaks")
    if not p or not peaks:
        return None
    lo, hi = t["host_t0"], t["host_t1"]
    lives = [live for ts, live in record["decode_calls"]
             if lo <= ts <= hi and live]
    if not lives:
        return None
    m, fam = record["model"], family(record)
    least = np.mean([costs.least_time(*fam.decode_call(m, live), peaks)
                     for live in lives])
    return 100.0 * least * p[0] / p[1]


def window_flops(record: Dict) -> float:
    """Model flops of every prefill and decode call of the window."""
    m, fam = record["model"], family(record)
    f = sum(fam.decode_call(m, live)[0]
            for _, live in record["decode_calls"])
    f += sum(fam.prefill_call(m, pos0, n)
             for _, pos0, n in record["prefill_calls"])
    return float(f)


def mfu(record: Dict) -> Optional[float]:
    """Model flops of the window over its seconds at the bf16 peak, %."""
    peaks = record.get("peaks")
    if not peaks or not record["decode_calls"]:
        return None
    return 100.0 * window_flops(record) / (record["window_s"]
                                           * peaks["bf16_flops"])


def idle_share(record: Dict) -> Optional[float]:
    """Share of the traced window with no operation on the device, %."""
    t = record.get("trace")
    if not t or t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def batch_occupancy(record: Dict) -> Optional[float]:
    """Live slots per decode call over the slots there are, %."""
    calls = record["decode_calls"]
    if not calls:
        return None
    live = sum(len(v) for _, v in calls)
    return 100.0 * live / (len(calls) * record["n_slots"])


def store_mib_per_request(record: Dict) -> Optional[float]:
    """MiB copied between device and host store per finished request:
    one entry per flush and per restore."""
    done = record["requests"]["completed"]
    if not done:
        return None
    c = record["counters"]
    moved = record["entry_bytes"] * (c["flushes"] + c["prefix_hits"])
    return moved / done / 2 ** 20


def prefix_hit_share(record: Dict) -> Optional[float]:
    """Admissions served by a restore over admissions in the window, %."""
    n = record["requests"]["admitted"]
    return 100.0 * record["counters"]["prefix_hits"] / n if n else None
