"""The comparison that decides ``correct``.

Once the window has closed and the program's device state is freed, a
sample drawn from the seed of the requests the window finished (the one
with the most served tokens always in it, and in a cell that restores,
one restored and one prefilled request where there are such) goes
through the plain float32 reference: each prompt with its served
tokens, once. At each served position the gap by which the served
token's logit lies below the reference's best is read; the widest gap
over the sample is compared with the configuration's limit
``check.max_logit_gap``. The tokens are greedy, so a sound program
serves the reference's best token or one within rounding of it.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

MIN_SERVED = 384          # served tokens the sample reaches, at least


def finished(window) -> List[Dict]:
    """The window's finished requests, as host data."""
    out = []
    for r, h in zip(window.requests, window.handles):
        if h is not None and h.done():
            out.append({"rid": r.rid, "prompt": r.prompt,
                        "tokens": h.result(), "max_new": r.max_new,
                        "restored": bool(h.request.restored)})
    return out


def sample(done: List[Dict], seed: int,
           min_served: int = MIN_SERVED) -> List[Dict]:
    """Requests to compare: the longest, a restored one and a prefilled
    one where there are such, then others in a seeded order until
    ``min_served`` served tokens."""
    if not done:
        return []
    rng = np.random.default_rng([seed, 11])
    order = [done[i] for i in rng.permutation(len(done))]
    picks = [max(done, key=lambda d: (len(d["tokens"]), -d["rid"]))]
    for want in (True, False):
        kind = [d for d in order if d["restored"] == want]
        if kind and not any(p["restored"] == want for p in picks):
            picks.append(kind[0])
    for d in order:
        if sum(len(p["tokens"]) for p in picks) >= min_served:
            break
        if all(d is not p for p in picks):
            picks.append(d)
    return picks


def compare(reference, seed: int, model: Dict, picks: List[Dict],
            done: List[Dict], limits: Dict) -> Dict[str, Dict]:
    """Every number compared, each with its limit and rule. A pick's
    ``chosen`` tokens, where it has them, are judged at each served
    position in place of the served ones (the control)."""
    short = sum(1 for d in done if len(d["tokens"]) != d["max_new"])
    checks = {"compared_requests": {"value": len(picks), "limit": 1,
                                    "rule": ">="},
              "short_outputs": {"value": short, "limit": 0, "rule": "<="}}
    if picks:
        res = reference.gaps(seed, model, [
            (p["prompt"], p["tokens"], p.get("chosen")) for p in picks])
        widest = max(float(np.max(r["gaps"])) for r in res)
    else:
        widest = float("nan")
    checks["max_logit_gap"] = {"value": widest,
                               "limit": float(limits["max_logit_gap"]),
                               "rule": "<="}
    return checks


def control(reference, seed: int, model: Dict,
            picks: List[Dict]) -> List[Dict]:
    """The control in the program's place: the same sample, with the
    token that the fp8 reference puts first at each served position
    (after the same served context) chosen there instead of the served
    one. :func:`compare` then reads its gaps in the float32 reference;
    a sound limit fails it."""
    low = reference.gaps(seed, model, [(p["prompt"], p["tokens"], None)
                                       for p in picks], precision="fp8")
    return [dict(p, chosen=[int(t) for t in r["argmax"]])
            for p, r in zip(picks, low)]


def passed(checks: Dict[str, Dict]) -> bool:
    """True when every compared number is within its limit."""
    ok = True
    for c in checks.values():
        v = c["value"]
        if v != v:                                  # NaN never passes
            return False
        ok &= v <= c["limit"] if c["rule"] == "<=" else v >= c["limit"]
    return bool(ok)
