"""The serving engine's span log: where a step's host time goes.

One :class:`SpanLog` per engine (``engine.spans``), off until
:meth:`SpanLog.start`. Off, :meth:`SpanLog.span` returns one shared null
context and the call site computes nothing else. On, each span enters a
``jax.profiler.TraceAnnotation`` of its name, so a profile shows it on
the host plane beside the device ops, and appends one record
``(name, t0_ns, t1_ns, parent, rid, attrs)`` on ``time.perf_counter_ns``,
``parent`` being the index of the span open around it. The records stay
in memory until read; :meth:`SpanLog.start` clears them.

The engine's spans, each at the boundary where its work happens:

================== ====================================================
``serve.queue``    ``submit()`` to the scheduler placing the request in
                   a slot (memory only: it is not nested in a step)
``serve.step``     the whole of ``ServingEngine.step``
``serve.prefill``  one request's chunked prefill; ``chunks`` lists
                   ``(pos0, n)`` per chunk dispatched
``serve.restore``  one request's pages back into its slot; ``bytes``
``serve.decode``   the fused decode dispatch; ``live`` lists the live
                   context per live slot
``serve.retire``   one slot's retirement, with its token transfer
``serve.flush``    one entry into the host store: the blocking part of
                   its copy, the insert and evictions; ``bytes``
``serve.flush.copy`` the blocking part of the device-to-host copy: the
                   wait for the previous entry's copy to land and the
                   start of this one's (it completes in the background);
                   ``bytes`` of this entry
================== ====================================================
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Optional, Tuple

import jax

Record = Tuple[str, int, Optional[int], Optional[int], Optional[int], Dict]

_OFF = contextlib.nullcontext()


class SpanLog:
    """Spans of one engine, kept in memory while on."""

    def __init__(self):
        self.on = False
        self._records: List[list] = []
        self._open: List[int] = []       # indices of the spans open now

    def start(self) -> None:
        """Clear the records and record from now on."""
        self._records, self._open = [], []
        self.on = True

    def stop(self) -> None:
        """Record nothing more; the records stay readable."""
        self.on = False

    def records(self) -> List[Record]:
        """Every span kept, in the order they opened (``parent`` indexes
        this list); one still open has ``t1_ns`` None."""
        return [tuple(r) for r in self._records]

    def span(self, name: str, rid: Optional[int] = None, **attrs):
        """A context timing ``name``; it yields the span's ``attrs``
        dict, for attributes known only inside it, or None when off."""
        if not self.on:
            return _OFF
        return _Span(self, name, rid, attrs)

    def add(self, name: str, t0_ns: int, rid: Optional[int] = None,
            **attrs) -> None:
        """Record a span that began at ``t0_ns`` and ends now, outside
        any other (it was open across steps)."""
        if self.on:
            self._records.append([name, t0_ns, time.perf_counter_ns(),
                                  None, rid, attrs])


class _Span:
    __slots__ = ("log", "rec", "note")

    def __init__(self, log: SpanLog, name: str, rid, attrs):
        self.log = log
        self.rec = [name, None, None, None, rid, attrs]
        self.note = jax.profiler.TraceAnnotation(name)

    def __enter__(self) -> Dict:
        log, rec = self.log, self.rec
        rec[3] = log._open[-1] if log._open else None
        log._open.append(len(log._records))
        log._records.append(rec)
        self.note.__enter__()
        rec[1] = time.perf_counter_ns()
        return rec[5]

    def __exit__(self, *exc) -> None:
        self.rec[2] = time.perf_counter_ns()
        self.note.__exit__(*exc)
        if self.log._open:               # start() may have cleared it
            self.log._open.pop()
