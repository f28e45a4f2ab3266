"""The reduction from a trace to busy time, module time and the
breakdown: on a hand-made trace with known answers, on a small trace
recorded on a TPU v5e (``data/``), and on a CPU trace for the host spans
the reading keeps."""
import glob
import os

import pytest

from harness import trace

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6   # ns


def _made():
    ops = {"/device:TPU:0": [("fusion.1", 10 * MS, 20 * MS),
                             ("fusion.2", 15 * MS, 10 * MS),   # overlaps
                             ("copy.3", 50 * MS, 10 * MS),
                             ("fusion.1", 95 * MS, 20 * MS)]}  # runs out
    mods = {"/device:TPU:0": [("jit_refine(12)", 10 * MS, 20 * MS),
                              ("jit_batched(7)", 50 * MS, 10 * MS),
                              ("jit_batched(7)", 95 * MS, 20 * MS)]}
    spans = [("bench:window", 0.0, 100 * MS),
             ("bench:refine_call", 6 * MS, 30 * MS),
             ("bench:run_sweep", 30 * MS, 70 * MS),
             ("bench:engine.evaluate", 45 * MS, 20 * MS),
             ("bench:later", 200 * MS, 5 * MS)]
    return trace.Trace(ops, mods, spans)


def test_reduce_made_trace():
    r = trace.reduce(_made())
    assert r["window_s"] == pytest.approx(0.1)
    # union inside the window: [10, 30] + [50, 60] + [95, 100]
    assert r["busy_s"] == pytest.approx(0.035)
    assert r["modules"]["jit_refine"] == pytest.approx([0.02, 1])
    assert r["modules"]["jit_batched"] == pytest.approx([0.015, 2])
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.025)
    gaps = dict(r["breakdown"]["idle_gaps"])
    # [0, 10] host, [30, 50] middle 40 -> run_sweep, [60, 95] middle
    # 77.5 -> run_sweep
    assert gaps == pytest.approx({"host": 0.01, "run_sweep": 0.055})
    assert r["busy_s"] + sum(gaps.values()) == pytest.approx(r["window_s"])


def test_reduce_needs_a_window_and_a_device():
    t = _made()
    assert trace.reduce(trace.Trace({}, {}, t.spans)) is None
    assert trace.reduce(trace.Trace(t.ops, t.modules, t.spans[1:])) is None


def test_module_name():
    assert trace.module_name("jit_refine(1234)") == "jit_refine"
    assert trace.module_name("jit_batched") == "jit_batched"


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(HERE, "data", "*.trace.json"))))
def test_recorded_trace(path):
    t = trace.load_json(path)
    r = trace.reduce(t)
    assert r is not None and 0 < r["busy_s"] <= r["window_s"]
    gaps = sum(v for _, v in r["breakdown"]["idle_gaps"])
    assert r["busy_s"] + gaps == pytest.approx(r["window_s"], rel=1e-9)
    assert r["modules"]


def test_recorded_sweep_slice():
    """A sweep window on the chip: the device waits while the host
    samples (inside ``run_sweep``, before ``engine.evaluate``), then the
    search kernel (``jit_run_all``) runs."""
    r = trace.reduce(trace.load_json(os.path.join(HERE, "data",
                                                  "sweep_v5e.trace.json")))
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert max(gaps, key=gaps.get) == "run_sweep"
    assert gaps["run_sweep"] > 2.0
    assert list(r["modules"]) == ["jit_run_all"]


def test_program_span_names_the_gap():
    """A program span (``mosaic:``) inside a benchmark span is the
    innermost, and names the gap without its prefix."""
    t = _made()
    t.spans.append(("mosaic:sweep.sample", 30 * MS, 15 * MS))
    r = trace.reduce(t)
    gaps = dict(r["breakdown"]["idle_gaps"])
    # [30, 50] middle 40 -> sweep.sample; [60, 95] middle 77.5 -> run_sweep
    assert gaps == pytest.approx({"host": 0.01, "sweep.sample": 0.02,
                                  "run_sweep": 0.035})
    assert r["busy_s"] == pytest.approx(0.035)
    assert r["window_s"] == pytest.approx(0.1)


def test_recorded_sweep_reads_as_before():
    """Busy, window and modules of the recorded slice, pinned to what
    the reduction read before it kept the program's spans."""
    r = trace.reduce(trace.load_json(os.path.join(HERE, "data",
                                                  "sweep_v5e.trace.json")))
    assert r["busy_s"] == 6.418595186
    assert r["window_s"] == 8.987399721000001
    assert r["modules"] == {"jit_run_all": [6.418615035, 1]}
    assert r["dropped"] is False and r["devices"] == 1


def test_load_keeps_benchmark_and_program_spans(tmp_path):
    """On the CPU's profiler: ``bench:`` and ``mosaic:`` host spans are
    kept, other host events are not."""
    import jax
    from repro.core import telemetry
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench:window"):
            with telemetry.span("memo.preload"):
                with jax.profiler.TraceAnnotation("other"):
                    jax.numpy.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    t = trace.load_xplane(str(tmp_path))
    names = {n for n, _, _ in t.spans}
    assert {"bench:window", "mosaic:memo.preload"} <= names
    assert all(n.startswith(("bench:", "mosaic:")) for n in names)
