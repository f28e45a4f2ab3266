"""What every driver shares, and how a traffic mix's ``kind`` finds its
driver: ``bench/drivers/<kind>.py``, whose class ``Driver`` drives the
program for that kind of mix.  A new kind is a new file there.  Every
size comes from the configuration and the mix files; every input from
``--seed``.

A driver has four steps, which ``run.py`` calls in order:

``setup()``            build the engine, make the inputs, warm every
                       program shape the window uses (set-up time)
``window(seconds)``    the measured work; returns a ``Window``
``sample()``           what the timed path produced, for the reference
``close()``            stop what the driver started
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os
import sys
from typing import Dict

__all__ = ["Window", "span", "engine", "make_driver"]

DRIVERS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "drivers")


@dataclasses.dataclass
class Window:
    """What one window measured.  ``end_to_end`` holds the cell's
    end-to-end metrics except ``setup_s``; ``counters`` what the
    per-layer readers use; ``checked`` the numbers of the correctness
    comparison that the timed path's own counts give (the rest come
    from the reference)."""

    end_to_end: Dict[str, float]
    attempted: int
    failed: int
    counters: Dict[str, float]
    checked: Dict[str, float] = dataclasses.field(default_factory=dict)


def span(name: str):
    """A host span in the profiler's trace (no cost when not tracing)."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation("bench:" + name)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def engine(config: dict):
    """The configuration's ``EvalEngine`` over its workloads."""
    from repro.core.dse.api import EngineConfig
    from repro.core.dse.engine import EvalEngine
    from repro.core.workloads.suite import workload_names
    wls = config["workloads"]
    if wls == "suite":
        wls = workload_names()
    return EvalEngine(list(wls), config=EngineConfig(**config["engine"]))


def driver_class(kind: str):
    """``Driver`` of ``bench/drivers/<kind>.py``."""
    path = os.path.join(DRIVERS, kind + ".py")
    spec = importlib.util.spec_from_file_location("bench_driver_" + kind,
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.Driver


def make_driver(config: dict, mix: dict, seed: int):
    return driver_class(mix["kind"])(config, mix, seed)
