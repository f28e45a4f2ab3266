"""Shared fixtures.  NOTE: no XLA_FLAGS here — smoke tests and benches see
1 CPU device; only launch/dryrun.py forces the 512-device host platform.
"""
import json
import os
import pathlib

import numpy as np
import pytest

from repro.compile_cache import enable_compile_cache

GOLDEN_DIR = pathlib.Path(__file__).parent / "golden"

# Persistent XLA compilation cache: $JAX_COMPILATION_CACHE_DIR or the
# checkout's .jax_cache/ (repro.compile_cache), so the jitted
# simulator/mapper compiles are paid once per cache, not once per run.
enable_compile_cache()

# Hypothesis example budgets: the default profile keeps tier-1 fast; the
# CI "thorough" profile (non-blocking -m slow job) widens the search.
try:
    from hypothesis import settings

    # database=None: no example database under .hypothesis/, so a run
    # reads nothing beyond the committed tree
    settings.register_profile("default", deadline=None, database=None)
    settings.register_profile("thorough", max_examples=300, deadline=None,
                              database=None)
    settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "default"))
except ImportError:  # pragma: no cover - hypothesis is optional locally
    pass


def pytest_addoption(parser):
    parser.addoption(
        "--regen-golden", action="store_true", default=False,
        help="rewrite tests/golden/*.json from the current cost model "
             "instead of comparing against them")


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def _diff_nested(ref, new, rtol, atol, path, out):
    """Collect human-readable numeric diffs between two golden payloads."""
    if isinstance(ref, dict) or isinstance(new, dict):
        rk = set(ref) if isinstance(ref, dict) else set()
        nk = set(new) if isinstance(new, dict) else set()
        for k in sorted(rk | nk):
            if k not in rk:
                out.append(f"{path}.{k}: added")
            elif k not in nk:
                out.append(f"{path}.{k}: removed")
            else:
                _diff_nested(ref[k], new[k], rtol, atol, f"{path}.{k}", out)
    elif isinstance(ref, list) or isinstance(new, list):
        if not isinstance(ref, list) or not isinstance(new, list):
            out.append(f"{path}: {type(ref).__name__} -> {type(new).__name__}"
                       f" ({ref!r} -> {new!r})")
            return
        if len(ref) != len(new):
            out.append(f"{path}: length {len(ref)} -> {len(new)}")
            return
        for i, (r, n) in enumerate(zip(ref, new)):
            _diff_nested(r, n, rtol, atol, f"{path}[{i}]", out)
    elif isinstance(ref, bool) or isinstance(new, bool) \
            or isinstance(ref, str) or isinstance(new, str):
        if ref != new:
            out.append(f"{path}: {ref!r} -> {new!r}")
    elif isinstance(ref, (int, float)) and isinstance(new, (int, float)):
        if not np.isclose(ref, new, rtol=rtol, atol=atol, equal_nan=True):
            rel = abs(new - ref) / max(abs(ref), 1e-300)
            out.append(f"{path}: {ref!r} -> {new!r} (rel {rel:.3e})")
    elif ref != new:
        out.append(f"{path}: {ref!r} -> {new!r}")


@pytest.fixture
def golden(request):
    """Tolerance-aware golden-trace comparator.

    ``golden(name, payload)`` compares ``payload`` against
    ``tests/golden/<name>.json``; with ``--regen-golden`` it rewrites the
    file instead.  Failures list every diverging leaf with its relative
    error, so an intentional cost-model edit shows its numeric footprint.
    """
    def compare(name, payload, rtol=1e-6, atol=1e-12):
        path = GOLDEN_DIR / f"{name}.json"
        if request.config.getoption("--regen-golden"):
            GOLDEN_DIR.mkdir(exist_ok=True)
            path.write_text(json.dumps(payload, indent=1, sort_keys=True)
                            + "\n")
            return
        assert path.exists(), (
            f"missing golden trace {path}; run `pytest --regen-golden` "
            f"to freeze the current cost model")
        ref = json.loads(path.read_text())
        diffs = []
        _diff_nested(ref, payload, rtol, atol, name, diffs)
        assert not diffs, (
            "golden trace mismatch (regen with --regen-golden if the "
            "cost-model change is intentional):\n  " + "\n  ".join(diffs))

    return compare
