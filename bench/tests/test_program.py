"""The program's own spans and counters reach the per-layer readers as
``ctx["program"]``: the delta of ``repro.core.telemetry`` across the
window, nothing of set-up; and each reader of them, on a made ctx and on
empty inputs."""
import json
import os
import subprocess
import sys

import pytest

from harness import spec
from harness.driver import Window

HERE = os.path.dirname(os.path.abspath(__file__))


def _program(spans=None, counters=None):
    return {"program": {"spans": spans or {}, "counters": counters or {}},
            "counters": {"seconds": 30.0}}


READINGS = [
    ("memo_hit_share.refine",
     _program(counters={"refine.rows": 2400, "refine.memo_hits": 600}),
     25.0),
    ("search_discard_share.refine",
     _program(counters={"refine.searched_rows": 2000,
                        "refine.discarded_rows": 500}), 25.0),
    ("memo_sync_ms_per_call.refine",
     _program(spans={"memo.preload": [0.032, 2],
                     "memo.drain": [0.144, 2]}), 88.0),
    ("sample_share.sweep",
     _program(spans={"sweep.sample": [2.4, 4]}), 8.0),
    ("area_evals_per_genome.sweep",
     _program(counters={"sweep.area_evals": 56160, "sweep.sampled": 960}),
     58.5),
]


@pytest.mark.parametrize("name,ctx,want", READINGS,
                         ids=[r[0] for r in READINGS])
def test_program_reader(name, ctx, want):
    assert spec.metric_reader(name)(ctx) == pytest.approx(want, rel=1e-12)


EMPTY = [
    {},
    {"program": None, "counters": None},
    _program(),
    {"program": {"spans": {}, "counters": {}}, "counters": {"seconds": 0}},
    # a span without the window's seconds
    {"program": {"spans": {"sweep.sample": [2.4, 4]}, "counters": {}}},
]


@pytest.mark.parametrize("name", [r[0] for r in READINGS])
@pytest.mark.parametrize("ctx", EMPTY, ids=range(len(EMPTY)))
def test_program_reader_on_empty_inputs(name, ctx):
    assert spec.metric_reader(name)(ctx) is None


class _Driver:
    """Counts and spans in set-up, and others in the window."""

    def setup(self):
        from repro.core import telemetry
        telemetry.count("refine.rows", 1000)
        with telemetry.span("memo.preload"):
            pass

    def window(self, seconds):
        from repro.core import telemetry
        telemetry.count("refine.rows", 400)
        telemetry.count("refine.memo_hits", 100)
        with telemetry.span("sweep.sample"):
            pass
        return Window({}, 1, 0, {"seconds": seconds})


def test_timed_window_holds_the_window_alone():
    import run
    d = _Driver()
    d.setup()
    win, program = run.timed_window(d, 2.0)
    assert win.counters == {"seconds": 2.0}
    assert program["counters"] == {"refine.rows": 400,
                                   "refine.memo_hits": 100}
    assert list(program["spans"]) == ["sweep.sample"]
    assert program["spans"]["sweep.sample"][1] == 1


# a whole traced run on the CPU with the driver above: the readers see
# the window's 100 hits of 400 rows, not set-up's 1000 rows, and no
# preload (set-up's alone)
RUN = """
import json, os, sys
sys.path[:0] = [sys.argv[1], os.path.join(os.path.dirname(sys.argv[1]),
                                         "src")]
sys.path.insert(0, os.path.join(sys.argv[1], "tests"))
import jax
import numpy as np
import run
from harness import compare, driver
from harness.spec import Cell
from reference import oracle
from reference.mosaic.dse.encoding import random_genomes
from test_program import _Driver

g = random_genomes(np.random.default_rng(3), 1)


class D(_Driver):
    def sample(self):
        rows = oracle.score_rows(g[0].tolist(), ["kan"], "latency",
                                 "aggregate")
        return compare.Sample(g, ["kan"], "latency", "aggregate",
                              rows[None])

    def close(self):
        pass


driver.make_driver = lambda *a: D()
cell = Cell("study.refine", 1, {}, {"kind": "made", "trace_seconds": 1},
            {"off_share": 0.0},
            [], [{"name": "memo_hit_share.refine", "unit": "%"},
                 {"name": "memo_sync_ms_per_call.refine", "unit": "ms/call"}])
args = run.parse(["--workload", "study.refine", "--seed", "1",
                  "--seconds", "1", "--trace", "1"])
line = run.run(args, cell, jax.devices()[:1], sys.argv[2])
print(json.dumps(line))
"""


def test_run_passes_the_window_delta_to_readers(tmp_path):
    p = subprocess.run([sys.executable, "-c", RUN, spec.BENCH,
                        str(tmp_path)],
                       capture_output=True, text=True, timeout=600,
                       env=dict(os.environ, JAX_PLATFORMS="cpu",
                                JAX_COMPILATION_CACHE_DIR=str(
                                    tmp_path / "cache")))
    assert p.returncode == 0, p.stderr[-4000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["metrics"] == {"memo_hit_share.refine":
                               {"value": 25.0, "unit": "%"}}


@pytest.mark.parametrize("cell", ("study.refine", "study.sweep"))
def test_traced_tiny_run_reads_every_program_metric(cell):
    """A traced run of each cell through its real driver, at a tiny size
    on the CPU: every per-layer metric that the program's spans and
    counters give is reported (the device trace's need a chip)."""
    p = subprocess.run([sys.executable, os.path.join(HERE, "tiny.py"), cell,
                        "--trace", "1"], capture_output=True, text=True,
                       timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-4000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["checks"]
    want = {m["name"] for m in spec.load_cell(cell).per_layer
            if m["source"] != "device_trace"}
    assert want <= set(line["metrics"])
    assert all(v["value"] > 0 for k, v in line["metrics"].items()
               if k.startswith(("memo_", "search_discard", "sample_",
                                "area_evals")))
