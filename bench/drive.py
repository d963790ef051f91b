"""Drive the engine with a traffic plan on the wall clock.

Open loop: each request is submitted when it is due, whatever the
engine is doing, and its latency runs from when it was due to when
``done()`` first reads true, which is when its tokens are on the host.
A request still not done when the window closes counts at its age then.
Closed loop: ``clients`` callers each keep one request in the engine;
the window opens once every slot has finished its first prefill, and
output tokens are counted by the engine's counter, with the device
caught up at both ends.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax

from bench import system
from bench.traffic import Plan, Req


@dataclasses.dataclass
class Window:
    """What one measured window did."""

    seconds: float                   # its length, by the host clock
    requests: List[Req]              # every request it submitted
    handles: list                    # their engine handles, same order
    latencies_s: List[float]         # open loop: requests due in it
    tokens: int = 0                  # closed loop: output tokens made
    counters: Optional[Dict[str, float]] = None
    queue_depth: List[tuple] = dataclasses.field(default_factory=list)


def _submit(engine, r: Req, traced: bool):
    from repro.serving.engine import Request
    with system.span("bench.submit", traced):
        return engine.submit(Request(rid=r.rid, prompt=list(r.prompt),
                                     max_new_tokens=r.max_new))


def open_loop(engine, plan: Plan, seconds: float, traced: bool = False,
              on_time=None) -> Window:
    """Submit each request of ``plan`` when due; step while there is
    work, sleep until the next due time while there is none.
    ``on_time(t)`` is called between steps with the window's clock."""
    reqs = [r for r in plan.requests if r.due_s < seconds]
    handles: List = [None] * len(reqs)
    done_at: List[Optional[float]] = [None] * len(reqs)
    pending: List[int] = []
    depth: List[tuple] = []
    c0 = system.counters(engine)
    t0 = time.perf_counter()
    i = 0
    while True:
        now = time.perf_counter() - t0
        if on_time is not None:
            on_time(now)
        if now >= seconds:
            break
        while i < len(reqs) and reqs[i].due_s <= now:
            handles[i] = _submit(engine, reqs[i], traced)
            pending.append(i)
            i += 1
        if system.has_work(engine):
            with system.span("bench.step", traced):
                engine.step()
            t = time.perf_counter() - t0
            still = []
            for j in pending:
                if handles[j].done():
                    done_at[j] = t
                else:
                    still.append(j)
            pending = still
            depth.append((t, len(engine.queue)))
        else:
            nxt = reqs[i].due_s if i < len(reqs) else seconds
            with system.span("bench.wait", traced):
                time.sleep(max(0.0, min(nxt, seconds) - now))
    end = time.perf_counter() - t0
    lat = [(done_at[j] if done_at[j] is not None else end) - r.due_s
           for j, r in enumerate(reqs)]
    c1 = system.counters(engine)
    return Window(seconds=end, requests=reqs[:i], handles=handles[:i],
                  latencies_s=lat, queue_depth=depth,
                  counters={k: c1[k] - c0[k] for k in c1})


def closed_loop(engine, plan: Plan, seconds: float, traced: bool = False,
                on_time=None) -> Window:
    """``plan.clients`` callers; each sends its next request as soon as
    its last one is done."""
    reqs = list(plan.requests)
    handles = [_submit(engine, r, traced) for r in reqs[: plan.clients]]
    nxt = plan.clients
    with system.span("bench.step", traced):
        engine.step()                      # every slot prefills
    jax.block_until_ready((engine.cache, engine.last_tokens))
    c0 = system.counters(engine)
    t0 = time.perf_counter()
    live = list(range(len(handles)))
    while True:
        now = time.perf_counter() - t0
        if on_time is not None:
            on_time(now)
        if now >= seconds:
            break
        with system.span("bench.step", traced):
            engine.step()
        still = []
        for j in live:
            if handles[j].done():
                if nxt < len(reqs):
                    handles.append(_submit(engine, reqs[nxt], traced))
                    still.append(len(handles) - 1)
                    nxt += 1
            else:
                still.append(j)
        live = still
    with system.span("bench.drain", traced):
        jax.block_until_ready((engine.cache, engine.last_tokens))
    end = time.perf_counter() - t0
    c1 = system.counters(engine)
    counters = {k: c1[k] - c0[k] for k in c1}
    return Window(seconds=end, requests=reqs[: len(handles)],
                  handles=handles, latencies_s=[],
                  tokens=counters["decode_tokens"], counters=counters)
