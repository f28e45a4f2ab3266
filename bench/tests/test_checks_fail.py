"""The comparison that decides ``correct`` fails what it must fail.

Each case drives a whole run of a cell on the CPU at a tiny size
(``tiny.py``, in a process of its own): a sound run comes out correct;
the precision control (64-bit floats switched off after import) and each
planted fault in the timed path come out not correct."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = ("study.refine", "study.sweep")


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(HERE, "tiny.py"),
                        *args], capture_output=True, text=True, env=env,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    line = _run(cell)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_float32_control_is_not_correct(cell):
    line = _run(cell, "--control", "float32")
    assert line["correct"] is False
    assert line["checks"]["off_share"]["value"] \
        > line["checks"]["off_share"]["limit"]


@pytest.mark.parametrize("fault", ("alter", "half", "frozen"))
@pytest.mark.parametrize("cell", CELLS)
def test_planted_fault_is_not_correct(cell, fault):
    line = _run(cell, "--fault", fault)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
