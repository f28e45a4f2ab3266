"""From a profiler trace to device busy time, per-module device time and
the breakdown of the traced window.

``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes
into a plain ``Trace``: per device, the op events and the module events;
and the host spans of the benchmark (``TraceAnnotation`` names that
start with ``bench:``) and of the program (``repro.core.telemetry``'s
``mosaic:``).  ``reduce`` works on a ``Trace`` alone, so it is tested
on a small recorded one (``bench/tests/data``).

- The window is the ``bench:window`` span.  The TPU records every op,
  and the search scans run millions of tiny ops a second: its trace
  buffers fill within seconds of busy device time and it drops what
  follows ("Trace Buffers Dropped").  A trace that dropped ends, for
  the reduction, where its last recorded device event ends: busy, idle
  and module time are read over that part of the window alone.
- Busy is the union of the device's op intervals inside the window;
  the idle share is 1 - busy / window.  With several devices, busy is
  their mean.
- A module's device time is the summed duration of its events on the
  device's module line, by the name the trace shows with its
  ``(<id>)`` suffix dropped; its count is the number of executions.
  An execution that the end of a dropped trace cuts is left out.
- The breakdown lists the ops that took the most device time, and the
  idle time of the first device named by the innermost host span, the
  benchmark's or the program's, that covers the middle of each idle gap,
  without its prefix (``host`` where none does).
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from typing import Dict, List, Optional, Tuple

__all__ = ["Trace", "load_xplane", "load_json", "reduce", "module_name",
           "head"]

Event = Tuple[str, float, float]        # name, start ns, duration ns
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DROPPED = "Trace Buffers Dropped"
SPAN_PREFIXES = ("bench:", "mosaic:")
WINDOW_SPAN = "bench:window"


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]          # device plane -> op events
    modules: Dict[str, List[Event]]      # device plane -> module events
    spans: List[Event]                   # bench: and mosaic: host spans
    dropped: bool = False                # the device dropped trace buffers

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def module_name(name: str) -> str:
    """``jit_refine(1234)`` -> ``jit_refine``."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def _is_device(plane: str) -> bool:
    return plane.startswith("/device:") and "CPU" not in plane


def load_xplane(log_dir: str) -> Optional[Trace]:
    """The newest ``.xplane.pb`` under ``log_dir``; None if there is none."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        return None
    data = ProfileData.from_file(files[-1])
    ops: Dict[str, List[Event]] = {}
    mods: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    dropped = False
    for plane in data.planes:
        if _is_device(plane.name):
            for line in plane.lines:
                dest = ops if line.name == OPS_LINE else \
                    mods if line.name == MODULES_LINE else None
                if dest is None:
                    dropped |= any(e.name == DROPPED for e in line.events)
                    continue
                dest.setdefault(plane.name, []).extend(
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, float(e.start_ns), float(e.duration_ns))
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIXES))
    return Trace(ops, mods, spans, dropped)


def head(trace: Trace, n: int) -> Trace:
    """The trace with at most ``n`` op events per device: a small sample
    to keep."""
    return Trace({k: sorted(v, key=lambda e: e[1])[:n]
                  for k, v in trace.ops.items()},
                 trace.modules, trace.spans, trace.dropped)


def load_json(path: str) -> Trace:
    with open(path) as f:
        d = json.load(f)
    return Trace({k: [tuple(e) for e in v] for k, v in d["ops"].items()},
                 {k: [tuple(e) for e in v] for k, v in d["modules"].items()},
                 [tuple(e) for e in d["spans"]], bool(d.get("dropped")))


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(events: List[Event], lo: float, hi: float):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def _span_at(spans: List[Event], t: float) -> str:
    """The innermost (shortest) host span covering ``t``, without its
    prefix."""
    best = None
    for name, s, d in spans:
        if name != WINDOW_SPAN and s <= t <= s + d \
                and (best is None or d < best[1]):
            best = (name, d)
    return best[0].split(":", 1)[1] if best else "host"


def reduce(trace: Trace, top: int = 10) -> Optional[dict]:
    """busy_s, window_s, modules {name: [seconds, count]} and the
    breakdown; None when the trace holds no window or no device op."""
    win = [(s, d) for n, s, d in trace.spans if n == WINDOW_SPAN]
    devices = sorted(p for p, ev in trace.ops.items() if ev)
    if not win or not devices:
        return None
    lo, hi = win[0][0], win[0][0] + win[0][1]
    if trace.dropped:
        hi = min(hi, max(s + d for p in devices
                         for ev in (trace.ops[p], trace.modules.get(p, []))
                         for _, s, d in ev))
    busy, busy_first = [], None
    for p in devices:
        u = _union([(a, b) for _, a, b in _clip(trace.ops[p], lo, hi)])
        busy.append(sum(b - a for a, b in u))
        if busy_first is None:
            busy_first = u
    modules: Dict[str, List[float]] = {}
    for p in devices[:1]:
        for name, a, b in _clip(trace.modules.get(p, []), lo, hi):
            if trace.dropped and b >= hi:
                continue            # cut by the end of the trace
            m = modules.setdefault(module_name(name), [0.0, 0])
            m[0] += (b - a) * 1e-9
            m[1] += 1
    op_time: Dict[str, float] = {}
    for name, a, b in _clip(trace.ops[devices[0]], lo, hi):
        name = name.split(" = ")[0]
        op_time[name] = op_time.get(name, 0.0) + (b - a) * 1e-9
    gaps: Dict[str, float] = {}
    edge = lo
    for a, b in busy_first + [(hi, hi)]:
        if a > edge:
            name = _span_at(trace.spans, (edge + a) / 2)
            gaps[name] = gaps.get(name, 0.0) + (a - edge) * 1e-9
        edge = max(edge, b)
    by_time = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": sum(busy) / len(busy) * 1e-9,
            "window_s": (hi - lo) * 1e-9,
            "dropped": trace.dropped,
            "devices": len(devices),
            "modules": modules,
            "breakdown": {"device_ops": [[k, v] for k, v in by_time(op_time)],
                          "idle_gaps": [[k, v] for k, v in by_time(gaps)]}}
