"""Shared benchmark helpers."""
from __future__ import annotations

import json
import os
import statistics
import time
from typing import Callable, Dict, List, Sequence

from repro.compile_cache import enable_compile_cache

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results", "bench")
REPO_ROOT = os.path.join(os.path.dirname(__file__), "..")


enable_compile_cache()


def save_repo_json(filename: str, payload) -> str:
    """Write a machine-readable benchmark payload at the repo root (the
    cross-PR perf trajectory files, e.g. BENCH_PR3.json)."""
    path = os.path.join(REPO_ROOT, filename)
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=float, sort_keys=True)
        f.write("\n")
    return path


def median_s(samples: Sequence[float]) -> float:
    """Median seconds over benchmark repeats (the BENCH_PR*.json metric:
    robust to one-off scheduler noise, unlike min)."""
    return float(statistics.median(samples))


def save_json(name: str, payload) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=float)
    return path


def load_json(name: str):
    path = os.path.join(RESULTS_DIR, f"{name}.json")
    if os.path.exists(path):
        return json.load(open(path))
    return None


def timed(fn: Callable, *args, repeats: int = 3, **kw):
    """(result, us_per_call) with one warmup."""
    fn(*args, **kw)
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args, **kw)
    us = (time.perf_counter() - t0) / repeats * 1e6
    return out, us


def csv_row(name: str, us_per_call: float, derived: str) -> str:
    return f"{name},{us_per_call:.1f},{derived}"
