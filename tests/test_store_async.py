"""The host page store's background device-to-host copy: ``put`` returns
with the entry pending (its leaves still device arrays), at most one entry
is pending, the next ``put`` or ``sync()`` finalizes it to host arrays,
and dropping, evicting or replacing it keeps the store's accounting and
its ``on_evict`` calls exactly as a store of host entries has them. A
restore from a pending entry serves the same tokens as one from a
finalized entry."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import MeshConfig, RunConfig, SHAPES
from repro.models import model as M
from repro.serving.engine import HostPageStore, Request, ServingEngine

PROMPT = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]


def _kv(fill: float, device: bool, rows: int = 4):
    k = np.full((rows, 64), fill, np.float32)      # 1 KiB per 4 rows
    return {"k": jnp.asarray(k) if device else k}


def _on_host(entry) -> bool:
    return all(isinstance(a, np.ndarray)
               for a in jax.tree_util.tree_leaves(entry["kv"]))


def test_put_returns_pending_and_sync_finalizes():
    store = HostPageStore()
    assert store.put(1, {"kv": _kv(1.0, True), "prompt": (1,)})
    entry = store.pages[1]
    assert isinstance(entry["kv"]["k"], jax.Array)
    assert store.is_pending(entry) and store.flush_async == 1
    store.sync()
    assert not store.is_pending(entry) and _on_host(entry)
    np.testing.assert_array_equal(entry["kv"]["k"], 1.0)
    assert store.flush_wait_ms >= 0.0
    store.sync()                                   # nothing left: no-op
    assert _on_host(entry) and store.flush_async == 1


def test_next_put_finalizes_so_at_most_one_is_pending():
    store = HostPageStore()
    for rid in range(4):
        assert store.put(rid, {"kv": _kv(float(rid), True)})
        pending = [r for r, e in store.pages.items() if store.is_pending(e)]
        assert pending == [rid]
        assert all(_on_host(store.pages[r]) for r in range(rid))
    assert store.flush_async == 4
    for rid in range(3):
        np.testing.assert_array_equal(store.pages[rid]["kv"]["k"], rid)


def test_host_leaves_are_stored_as_they_are():
    store = HostPageStore()
    assert store.put(1, {"kv": _kv(1.0, False)})
    assert not store.is_pending(store.pages[1]) and store.flush_async == 0


def _replay(device: bool):
    """One sequence of puts, a replacement, a drop and evictions; returns
    the store and its ``on_evict`` log."""
    log = []

    def on_evict(rid, entry, why):
        log.append((rid, why, float(np.asarray(entry["kv"]["k"])[0, 0])))

    store = HostPageStore(budget_bytes=3 * 1024, on_evict=on_evict)
    for rid in range(3):
        assert store.put(rid, {"kv": _kv(float(rid), device)})
    assert store.put(2, {"kv": _kv(20.0, device)})     # replace the pending
    assert store.put(4, {"kv": _kv(4.0, device)})      # evicts rid 0
    assert store.drop(4)                               # drop the pending
    assert not store.drop(4)
    assert store.put(5, {"kv": _kv(5.0, device, rows=12)})  # evicts 1 and 2
    pending = store.pages[5]
    store.budget_bytes = 1024
    assert not store.put(6, {"kv": _kv(6.0, device, rows=8)})  # 5, then 6
    assert not store.is_pending(pending)
    return store, log


def test_replace_drop_evict_of_a_pending_entry_match_host_entries():
    host, host_log = _replay(device=False)
    dev, dev_log = _replay(device=True)
    assert dev_log == host_log == [
        (2, "replace", 2.0), (0, "evict", 0.0), (4, "evict", 4.0),
        (1, "evict", 1.0), (2, "evict", 20.0), (5, "evict", 5.0),
        (6, "evict", 6.0)]
    assert (dev.bytes, dev.evictions) == (host.bytes, host.evictions) == \
        (0, 6)
    assert not dev.pages and dev.flush_async == 6 and host.flush_async == 0


def _make():
    cfg = registry.smoke("qwen3-1.7b")
    rc = RunConfig(model=cfg, shape=SHAPES["decode_32k"], mesh=MeshConfig())
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    return ServingEngine(params, cfg, rc, n_slots=2, max_seq=32,
                         prefill_chunk=4)


def _drain(eng):
    while eng.queue or any(s is not None for s in eng.slots):
        eng.step()


@pytest.mark.parametrize("finalize_first", [False, True])
def test_restore_from_pending_entry_serves_the_same_tokens(
        mesh_ctx, finalize_first):
    eng = _make()
    eng.submit(Request(rid=1, prompt=PROMPT, max_new_tokens=6))
    _drain(eng)
    first = eng.finished[-1].generated
    assert eng.stats["flushes"] == eng.stats["flush_async"] == 1
    assert eng.store.is_pending(eng.store.pages[1])
    if finalize_first:
        eng.store.sync()
    eng.submit(Request(rid=2, prompt=PROMPT, max_new_tokens=6))
    _drain(eng)
    again = eng.finished[-1]
    assert again.restored and again.generated == first
    assert eng.stats["prefix_hits"] == 1
    assert eng.stats["flush_pending_restores"] == (0 if finalize_first
                                                   else 1)
    assert eng.stats["flush_async"] == eng.stats["flushes"] == 2
    assert eng.store.is_pending(eng.store.pages[2])
    eng.run()                                      # the horizon syncs
    assert not any(eng.store.is_pending(e) for e in eng.store.pages.values())
    assert all(_on_host(e) for e in eng.store.pages.values())
    assert eng.stats["flush_wait_ms"] > 0.0
