"""One generator for every traffic mix: a ``bench/traffic/<name>.json``
file of parameters in, a seeded list of requests out.

A file holds:

* ``loop``: ``"open"`` (requests due on a fixed schedule at a mean of
  ``rate_rps``) or ``"closed"`` (``clients`` callers, each sending its
  next request when the last one is done);
* ``arrival`` (open loop): ``"poisson"`` (the default), one request per
  Poisson gap, or ``"burst"``, ``burst_size`` requests due at the same
  second, the bursts Poisson at ``rate_rps / burst_size``;
* ``prompts``: ``"catalog"`` (a catalog of ``catalog.n_docs`` documents
  asked for with zipf(``catalog.zipf_s``) popularity; set-up stores the
  ``catalog.prefill_store`` most popular) or ``"unique"``;
* ``prompt_len`` and ``output_len``: ``{length: share}``;
* ``stratify_block``: requests per block whose lengths hold the stated
  shares exactly (0: the whole list);
* ``schedule_seed`` (optional): the seed of the schedule, that is the
  order of the lengths, gaps and document ranks. Without it the run's
  seed draws the order.

Every seed gets the same set of lengths, gaps and document ranks, in
another order, or in the one order of ``schedule_seed``; the seed
always draws the token ids. So runs of different seeds do the same
work, and a seed changes only its order and content. The arrival gaps
are the quantiles of the exponential distribution at the rate, not
draws from it, for the same reason.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Tuple

import numpy as np

GOLDEN = (math.sqrt(5.0) - 1.0) / 2.0
SCHEDULE = ("order", "gaps", "outputs")     # draws fixed by schedule_seed


@dataclasses.dataclass(frozen=True)
class Req:
    """One request: its prompt, its output length and, in an open loop,
    the second of the window it is due at."""

    rid: int
    prompt: Tuple[int, ...]
    max_new: int
    due_s: Optional[float] = None
    doc: Optional[int] = None          # catalog rank (0 = most popular)


@dataclasses.dataclass(frozen=True)
class Plan:
    """What one run of a traffic mix sends."""

    loop: str
    requests: List[Req]
    clients: int = 0
    store_fill: List[Tuple[int, ...]] = dataclasses.field(
        default_factory=list)


def _levels(dist: Dict[str, float]):
    keys = sorted(dist, key=float)
    vals = np.array([float(k) for k in keys])
    p = np.array([float(dist[k]) for k in keys])
    if np.any(p < 0) or not np.isclose(p.sum(), 1.0):
        raise ValueError(f"shares must be >= 0 and sum to 1: {dist}")
    return vals, p


def apportion(p: np.ndarray, n: int) -> np.ndarray:
    """Whole counts summing to ``n`` in the proportions ``p`` (largest
    remainder; ties to the earlier level)."""
    raw = np.asarray(p, np.float64) * n
    counts = np.floor(raw).astype(int)
    order = sorted(range(len(p)), key=lambda i: (-(raw[i] - counts[i]), i))
    for i in order[: n - counts.sum()]:
        counts[i] += 1
    return counts


def stratified(dist: Dict[str, float], n: int, block: int,
               rng: np.random.Generator) -> np.ndarray:
    """``n`` values of ``dist``: each block of ``block`` (0: all ``n``)
    holds the shares as nearly as whole counts allow, shuffled."""
    vals, p = _levels(dist)
    block = block or n
    out, done = [], 0
    carry = np.zeros_like(p)
    while done < n:
        m = min(block, n - done)
        want = p * m + carry
        counts = apportion(want / want.sum(), m) if want.sum() else \
            apportion(p, m)
        carry = want - counts
        blk = np.repeat(vals, counts)
        rng.shuffle(blk)
        out.extend(blk.tolist())
        done += m
    return np.asarray(out, dtype=int)


def zipf_shares(n_docs: int, s: float) -> np.ndarray:
    """Popularity of catalog ranks 0..n_docs-1 under zipf(``s``)."""
    w = np.arange(1, n_docs + 1, dtype=np.float64) ** -s
    return w / w.sum()


def doc_lengths(dist: Dict[str, float], n_docs: int) -> np.ndarray:
    """Each catalog rank's prompt length: the inverse of the length
    distribution at a golden-ratio sequence, so every popularity band
    holds the stated mix, whatever the seed."""
    vals, p = _levels(dist)
    cdf = np.cumsum(p)
    u = ((np.arange(n_docs) + 0.5) * GOLDEN) % 1.0
    return vals[np.minimum(np.searchsorted(cdf, u, side="right"),
                           len(vals) - 1)].astype(int)


def poisson_gaps(rate: float, n: int, rng: np.random.Generator):
    """``n`` gaps (s): the exponential's quantiles at ``rate``, shuffled."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    rng.shuffle(gaps)
    return gaps


def _arrivals(traffic: Dict, seconds: float, rng: np.random.Generator):
    """An open loop's request count and due seconds, in order."""
    rate = float(traffic["rate_rps"])
    arrival = traffic.get("arrival", "poisson")
    if arrival == "poisson":
        n = max(1, int(round(rate * seconds)))
        return n, np.cumsum(poisson_gaps(rate, n, rng))
    if arrival == "burst":
        size = int(traffic["burst_size"])
        bursts = max(1, int(round(rate * seconds / size)))
        starts = np.cumsum(poisson_gaps(rate / size, bursts, rng))
        return bursts * size, np.repeat(starts, size)
    raise ValueError(f"unknown arrival {arrival!r}")


def _tokens(rng: np.random.Generator, n: int, vocab: int):
    return tuple(int(t) for t in rng.integers(1, vocab, size=n))


def build(traffic: Dict, seed: int, seconds: float, vocab: int) -> Plan:
    """The requests one run of ``traffic`` sends in ``seconds``."""
    schedule = int(traffic.get("schedule_seed", seed))
    rng = {k: np.random.default_rng([schedule if k in SCHEDULE else seed,
                                     i])
           for i, k in enumerate(("order", "gaps", "docs", "prompts",
                                  "outputs"))}
    loop = traffic["loop"]
    block = int(traffic.get("stratify_block", 0))
    if loop == "open":
        n, dues = _arrivals(traffic, seconds, rng["gaps"])
    elif loop == "closed":
        n = int(traffic["clients"]) * int(traffic.get("requests_per_client",
                                                      8))
        dues = [None] * n
    else:
        raise ValueError(f"unknown loop {loop!r}")
    outputs = stratified(traffic["output_len"], n, block, rng["outputs"])
    fill: List[Tuple[int, ...]] = []
    if traffic["prompts"] == "catalog":
        cat = traffic["catalog"]
        n_docs = int(cat["n_docs"])
        lens = doc_lengths(traffic["prompt_len"], n_docs)
        docs = [_tokens(rng["docs"], int(L), vocab) for L in lens]
        ranks = np.repeat(np.arange(n_docs),
                          apportion(zipf_shares(n_docs,
                                                float(cat["zipf_s"])), n))
        rng["order"].shuffle(ranks)
        prompts = [docs[r] for r in ranks]
        doc_ids = [int(r) for r in ranks]
        fill = docs[: int(cat.get("prefill_store", 0))]
    elif traffic["prompts"] == "unique":
        lens = stratified(traffic["prompt_len"], n, block, rng["order"])
        prompts = [_tokens(rng["prompts"], int(L), vocab) for L in lens]
        doc_ids = [None] * n
    else:
        raise ValueError(f"unknown prompts {traffic['prompts']!r}")
    reqs = [Req(rid=i, prompt=prompts[i], max_new=int(outputs[i]),
                due_s=None if dues[i] is None else float(dues[i]),
                doc=doc_ids[i]) for i in range(n)]
    return Plan(loop=loop, requests=reqs,
                clients=int(traffic.get("clients", 0)), store_fill=fill)
