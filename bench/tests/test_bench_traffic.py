"""Every traffic file under ``bench/traffic``: the same seed gives the
same requests, every seed the same lengths in another order, and the
lengths and arrivals follow the file's stated distributions."""
import collections
import hashlib
import json
import pathlib

import numpy as np
import pytest

from bench import traffic

ROOT = pathlib.Path(__file__).resolve().parents[2]
FILES = sorted((ROOT / "bench" / "traffic").glob("*.json"))
VOCAB = 151552
SECONDS = 40.0


def _load(path):
    return json.loads(path.read_text())


def _lens(plan):
    return collections.Counter((len(r.prompt), r.max_new)
                               for r in plan.requests)


@pytest.mark.parametrize("path", FILES, ids=lambda p: p.stem)
def test_same_seed_same_requests(path):
    t = _load(path)
    a = traffic.build(t, 2 ** 33 + 5, SECONDS, VOCAB)
    b = traffic.build(t, 2 ** 33 + 5, SECONDS, VOCAB)
    assert a == b
    c = traffic.build(t, 5, SECONDS, VOCAB)
    assert [r.prompt for r in c.requests] != [r.prompt for r in a.requests]


@pytest.mark.parametrize("path", FILES, ids=lambda p: p.stem)
def test_every_seed_does_the_same_work(path):
    t = _load(path)
    plans = [traffic.build(t, s, SECONDS, VOCAB) for s in (1, 2, 3 ** 20)]
    prompt_lens = [sorted(len(r.prompt) for r in p.requests) for p in plans]
    outputs = [sorted(r.max_new for r in p.requests) for p in plans]
    assert prompt_lens[0] == prompt_lens[1] == prompt_lens[2]
    assert outputs[0] == outputs[1] == outputs[2]
    if t["loop"] == "open":
        dues = [p.requests[-1].due_s for p in plans]
        assert max(dues) - min(dues) < 1e-9
    block = int(t.get("stratify_block", 0))
    if block:
        want = sorted(r.max_new for r in plans[0].requests[:block])
        for p in plans:
            assert sorted(r.max_new for r in p.requests[:block]) == want


@pytest.mark.parametrize("path", FILES, ids=lambda p: p.stem)
def test_lengths_follow_the_stated_shares(path):
    t = _load(path)
    plan = traffic.build(t, 17, SECONDS, VOCAB)
    n = len(plan.requests)
    for key, got in (("output_len", [r.max_new for r in plan.requests]),
                     ("prompt_len", [len(r.prompt)
                                     for r in plan.requests])):
        if key == "prompt_len" and t["prompts"] == "catalog":
            got = traffic.doc_lengths(t[key], t["catalog"]["n_docs"])
            n_key = len(got)
        else:
            n_key = n
        counts = collections.Counter(int(x) for x in got)
        for level, share in t[key].items():
            off = abs(counts[int(level)] - share * n_key)
            assert off <= 1.0 + 0.02 * n_key
        assert all(x % 128 == 0 for x in (len(r.prompt)
                                          for r in plan.requests))
    assert all(0 < tok < VOCAB for r in plan.requests for tok in r.prompt)


def test_open_loop_gaps_are_exponential_quantiles():
    rng = np.random.default_rng(0)
    gaps = traffic.poisson_gaps(2.0, 400, rng)
    assert abs(np.mean(gaps) - 0.5) < 0.02
    assert abs(np.median(gaps) - np.log(2) / 2.0) < 0.01


def test_catalog_popularity_is_zipf():
    t = _load(ROOT / "bench" / "traffic" / "doc-reuse.json")
    plan = traffic.build(t, 3, 400.0, VOCAB)
    ranks = collections.Counter(r.doc for r in plan.requests)
    p = traffic.zipf_shares(t["catalog"]["n_docs"], t["catalog"]["zipf_s"])
    n = len(plan.requests)
    for k in range(t["catalog"]["n_docs"]):
        assert abs(ranks[k] - p[k] * n) <= 1.0
    assert len(plan.store_fill) == t["catalog"]["prefill_store"]
    assert plan.store_fill[0] == plan.requests[
        [r.doc for r in plan.requests].index(0)].prompt


@pytest.mark.parametrize("path", FILES, ids=lambda p: p.stem)
def test_schedule_seed_fixes_the_order(path):
    """With ``schedule_seed`` every seed sends the same lengths at the
    same times in the same order; only the token ids differ."""
    t = _load(path)
    if "schedule_seed" not in t:
        t = dict(t, schedule_seed=3)
    a, b = (traffic.build(t, s, SECONDS, VOCAB) for s in (1, 2 ** 33 + 9))
    shape = [[(len(r.prompt), r.max_new, r.due_s, r.doc)
              for r in p.requests] for p in (a, b)]
    assert shape[0] == shape[1]
    assert [r.prompt for r in a.requests] != [r.prompt for r in b.requests]
    c = traffic.build(dict(t, schedule_seed=t["schedule_seed"] + 1), 1,
                      SECONDS, VOCAB)
    assert [(len(r.prompt), r.max_new, r.due_s) for r in c.requests] != \
        [(len(r.prompt), r.max_new, r.due_s) for r in a.requests]


# sha256 (first 16 hex digits) of every request and the store fill, as the
# generator made them before it read "arrival": the Poisson files must
# still give these plans bit for bit
POISSON_PLANS = {("doc-reuse", 7): "7c0c6291427e807a",
                 ("doc-reuse", 2 ** 33 + 5): "f16c2e50d40830f2",
                 ("chat-unique", 7): "232ad595cf15ea3e",
                 ("chat-unique", 2 ** 33 + 5): "480e1f999073cb3b",
                 ("reason-batch", 7): "e5a2309aae701986",
                 ("reason-batch", 2 ** 33 + 5): "4e8e7942fba9c1e3"}


def _digest(plan):
    h = hashlib.sha256()
    for r in plan.requests:
        h.update(repr((r.rid, r.prompt, r.max_new, r.due_s, r.doc)).encode())
    h.update(repr(plan.store_fill).encode())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("name,seed", sorted(POISSON_PLANS))
def test_poisson_files_give_the_same_plan_as_before(name, seed):
    t = _load(ROOT / "bench" / "traffic" / f"{name}.json")
    assert t.get("arrival", "poisson") == "poisson"
    assert _digest(traffic.build(t, seed, 51.0, VOCAB)) == \
        POISSON_PLANS[name, seed]
    if t["loop"] == "open":        # "poisson" is also the default
        t.pop("arrival")
        assert _digest(traffic.build(t, seed, 51.0, VOCAB)) == \
            POISSON_PLANS[name, seed]


@pytest.mark.parametrize("rate,seconds,size", [(0.64, 51.0, 8),
                                               (2.0, 30.0, 4),
                                               (0.1, 5.0, 8)])
def test_bursts_are_whole_and_due_together(rate, seconds, size):
    t = dict(_load(ROOT / "bench" / "traffic" / "doc-reuse-bursty.json"),
             rate_rps=rate, burst_size=size)
    plan = traffic.build(t, 2 ** 33 + 3, seconds, VOCAB)
    bursts = max(1, round(rate * seconds / size))
    dues = [r.due_s for r in plan.requests]
    assert len(dues) == bursts * size
    assert dues == sorted(dues)
    starts = sorted(set(dues))
    assert len(starts) == bursts
    assert all(dues.count(s) == size for s in starts)
    gaps = np.diff([0.0] + starts)
    want = -np.log1p(-(np.arange(bursts) + 0.5) / bursts) / (rate / size)
    np.testing.assert_allclose(sorted(gaps), want, rtol=1e-12)
    # lengths and documents are drawn as a Poisson file of as many
    # requests draws them
    poisson = traffic.build(dict(t, arrival="poisson",
                                 rate_rps=bursts * size / seconds),
                            2 ** 33 + 3, seconds, VOCAB)
    assert [(r.prompt, r.max_new, r.doc) for r in plan.requests] == \
        [(r.prompt, r.max_new, r.doc) for r in poisson.requests]


def test_bursty_file_sends_four_bursts_of_eight_in_one_order():
    t = _load(ROOT / "bench" / "traffic" / "doc-reuse-bursty.json")
    assert (t["arrival"], t["burst_size"], t["schedule_seed"]) == \
        ("burst", 8, 1)
    plans = [traffic.build(t, s, 51.0, VOCAB) for s in (3, 2 ** 31 + 11)]
    order = [[(r.due_s, r.doc, r.max_new) for r in p.requests]
             for p in plans]
    assert order[0] == order[1]
    assert len(order[0]) == 32 and len({d for d, _, _ in order[0]}) == 4
    other = traffic.build(dict(t, schedule_seed=2), 3, 51.0, VOCAB)
    assert [(r.due_s, r.doc, r.max_new) for r in other.requests] != order[0]
    with pytest.raises(ValueError, match="arrival"):
        traffic.build(dict(t, arrival="uniform"), 3, 51.0, VOCAB)
