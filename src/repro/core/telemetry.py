"""Host spans and counters of the program, for operators and the profiler.

``span(name)`` times a block on the host's clock.  While a
``jax.profiler`` trace is active it also marks the block on the
profiler's host plane as ``mosaic:<name>``, on the same clock as the
device's events, so a stretch in which the device idles can be put down
to the host work that filled it.  ``count(name, n)`` adds to a named
counter.  Both add to one process-wide registry: ``snapshot()`` copies
it and ``diff(after, before)`` subtracts two copies, which gives what a
stretch of work did.

Always on.  Spans sit around whole calls (a memo sync, a sweep's
sampling), a few per call of seconds, so they cost microseconds per call.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, List

__all__ = ["PREFIX", "span", "count", "snapshot", "diff"]

PREFIX = "mosaic:"

_lock = threading.Lock()
_spans: Dict[str, List[float]] = {}      # name -> [seconds, count]
_counters: Dict[str, float] = {}


@contextlib.contextmanager
def span(name: str):
    """Time the block, or as a decorator each call, as span ``name``;
    recorded on the exception path too."""
    from jax.profiler import TraceAnnotation
    t0 = time.perf_counter()
    try:
        with TraceAnnotation(PREFIX + name):
            yield
    finally:
        dt = time.perf_counter() - t0
        with _lock:
            s = _spans.setdefault(name, [0.0, 0])
            s[0] += dt
            s[1] += 1


def count(name: str, n=1) -> None:
    """Add ``n`` to counter ``name``."""
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def snapshot() -> dict:
    """``{"spans": {name: [seconds, count]}, "counters": {name: value}}``."""
    with _lock:
        return {"spans": {k: list(v) for k, v in _spans.items()},
                "counters": dict(_counters)}


def diff(after: dict, before: dict) -> dict:
    """What happened between two snapshots; names that did not move are
    left out."""
    bs, bc = before["spans"], before["counters"]
    spans = {k: [s - bs.get(k, (0.0, 0))[0], c - bs.get(k, (0.0, 0))[1]]
             for k, (s, c) in after["spans"].items()}
    counters = {k: v - bc.get(k, 0) for k, v in after["counters"].items()}
    return {"spans": {k: v for k, v in spans.items() if v[1]},
            "counters": {k: v for k, v in counters.items() if v}}
