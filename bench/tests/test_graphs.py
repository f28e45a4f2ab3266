"""A configuration brings its own workload graph to the reference:
``bench/reference/graphs/<name>.py`` is found by name, in the spawned
workers of ``compare.reference`` too, and a name in neither place, or a
file that reuses a suite name, is an error."""
import os
import shutil
import subprocess
import sys

import pytest

from harness import spec
from reference import oracle

KAN_AGAIN = """from ..mosaic.workloads.misc import kan


def build():
    return kan()
"""

# scores 6 genomes on "kan" and on a graphs file that builds the frozen
# kan graph, in 2 spawned workers, through the harness's own path
SCORE = """
import sys
import numpy as np
sys.path.insert(0, sys.argv[1])
from harness import compare
from reference.mosaic.dse.encoding import random_genomes
g = random_genomes(np.random.default_rng(7), 6)
s = compare.Sample(g, ["kan", "kan_again"], "latency", "aggregate", None)
rows, _ = compare.reference(s, workers=2)
assert rows.shape == (6, 3, 2), rows.shape
assert np.isfinite(rows[:, 0]).any()
assert np.array_equal(rows[:, :, 0], rows[:, :, 1])
print("same")
"""


def test_graphs_file_scores_like_the_suite_graph(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(spec.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (bench / "reference" / "graphs" / "kan_again.py").write_text(KAN_AGAIN)
    p = subprocess.run([sys.executable, "-c", SCORE, str(bench)],
                       capture_output=True, text=True, timeout=600,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-4000:]
    assert p.stdout.split() == ["same"]


@pytest.fixture
def graphs(tmp_path, monkeypatch):
    """A graphs directory of the test's own, with the cache cleared on
    both sides."""
    monkeypatch.setattr(oracle, "GRAPHS", str(tmp_path))
    oracle._graph.cache_clear()
    yield tmp_path
    oracle._graph.cache_clear()


def test_unknown_name_names_both_places(graphs):
    with pytest.raises(KeyError) as e:
        oracle._graph("no_such_workload")
    assert "suite name" in str(e.value)
    assert os.path.join(str(graphs), "no_such_workload.py") in str(e.value)


def test_graphs_file_may_not_reuse_a_suite_name(graphs):
    (graphs / "kan.py").write_text(KAN_AGAIN)
    with pytest.raises(ValueError, match="reuses the suite name"):
        oracle._graph("kan")


def test_graphs_file_in_process(graphs):
    (graphs / "kan_again.py").write_text(KAN_AGAIN)
    g, ref = oracle._graph("kan_again"), oracle._graph("kan")
    assert g is not ref
    assert g.nodes == ref.nodes
