"""End-to-end arithmetic: a rate over the whole window, the numbers the
window's own counts give, and the comparison numbers."""
import numpy as np

from harness import compare
from harness.driver import driver_class


def test_refine_rate_spans_the_whole_window(monkeypatch):
    import time

    from repro.core.dse.ga import GAConfig
    clock = [0.0]
    monkeypatch.setattr(time, "perf_counter", lambda: clock[0])
    d = driver_class("ga_refine")({"ga": {}}, {}, 0)
    d.gacfg = GAConfig(population=200, generations=5)

    class R:
        generations_run = 5

    def call(seed):
        clock[0] += 7.0
        d.in_call_s += 6.5
        return R(), 550

    d._call = call
    w = d.window(20.0)
    # three calls (7, 14, 21 s): the last one ends past 20 s and counts
    assert w.attempted == 3 and w.counters["generations"] == 15
    assert w.end_to_end["refine_s_per_gen"] == 21.0 / 15
    assert w.counters["in_call_s"] == 19.5
    # 200 seed genomes and 5 x 180 non-elite children could be new per
    # call; 550 of those 1100 were
    assert w.checked["stale_share"] == 0.5


def test_host_gap_share_reads_the_time_outside_the_calls():
    from harness import spec
    read = spec.metric_reader("host_gap_share.refine")
    assert read({"counters": {"seconds": 16.0, "in_call_s": 15.0}}) == 6.25
    assert read({"counters": {"seconds": 0.0, "in_call_s": 0.0}}) is None


def test_sweep_rate_spans_the_whole_window(monkeypatch):
    import time
    clock = [0.0]
    monkeypatch.setattr(time, "perf_counter", lambda: clock[0])
    d = driver_class("sweep")({}, {}, 0)

    class S:
        latency = np.zeros((960, 20))

    def call(seed):
        clock[0] += 4.0
        d.hits, d.misses = d.hits + 6, d.misses + 954
        return S()

    d._call = call
    w = d.window(10.0)
    assert w.attempted == 3
    assert w.end_to_end["sweep_evals_per_s"] == 3 * 960 * 20 / 12.0
    assert w.checked["repeat_share"] == 18 / 2880


def test_pair_errors_and_numbers():
    ref = np.ones((4, 3, 2))
    rows = ref.copy()
    rows[0, 0, 0] = 1 + 1e-12          # agrees
    rows[1, 2, 1] = 1 + 1e-3           # a flip
    rows[2, :, 0] = [np.inf, np.inf, 0.0]   # unmappable on one side only
    s = compare.Sample(np.zeros((4, 43)), ["a", "b"], "latency",
                       "aggregate", rows, np.array([1.0, 2, 3, 4]))
    n = compare.numbers(s, ref, np.array([1.0, 2, 3, 4 * (1 + 1e-9)]))
    assert n["off_share"] == 2 / 8
    assert compare.pair_errors(rows, ref).max() == np.inf
    assert abs(n["area_err_max"] - 1e-9) < 1e-15


def test_pairs_unmappable_on_both_sides_are_left_out():
    ref = np.ones((2, 3, 1))
    ref[1, :, 0] = [np.inf, np.inf, 0.0]
    rows = ref.copy()
    rows[0, 1, 0] = 2.0
    s = compare.Sample(np.zeros((2, 43)), ["a"], "latency", "aggregate",
                       rows)
    n = compare.numbers(s, ref, None)
    assert n == {"off_share": 1.0}


def test_judge_needs_a_limit_for_every_number():
    import pytest
    out = compare.judge({"off_share": 0.5}, {"off_share": 0.1, "x": 1})
    assert out["off_share"]["ok"] is False
    with pytest.raises(KeyError):
        compare.judge({"y": 0.0}, {})
