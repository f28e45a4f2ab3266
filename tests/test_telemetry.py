"""The program's spans and counters (``repro.core.telemetry``): what the
registry adds up, what ``diff`` of two snapshots leaves, and that a span
lands on the profiler's host plane under ``mosaic:<name>``."""
import glob
import os
import threading

import pytest

from repro.core import telemetry


def _delta(before):
    return telemetry.diff(telemetry.snapshot(), before)


def test_nested_spans_add_seconds_and_counts():
    @telemetry.span("t.inner")
    def inner():
        pass

    before = telemetry.snapshot()
    with telemetry.span("t.outer"):
        for _ in range(2):
            with telemetry.span("t.inner"):
                pass
        inner()
    d = _delta(before)["spans"]
    assert d["t.outer"][1] == 1 and d["t.inner"][1] == 3
    assert 0.0 <= d["t.inner"][0] <= d["t.outer"][0]


def test_diff_of_two_snapshots():
    telemetry.count("t.diff", 5)
    with telemetry.span("t.diff_span"):
        pass
    before = telemetry.snapshot()
    telemetry.count("t.diff", 2)
    telemetry.count("t.diff_new")
    with telemetry.span("t.diff_span"):
        pass
    d = _delta(before)
    assert d["counters"]["t.diff"] == 2
    assert d["counters"]["t.diff_new"] == 1
    assert d["spans"]["t.diff_span"][1] == 1
    # names that did not move between the snapshots are left out
    assert "t.outer" not in d["spans"]
    assert telemetry.diff(before, before) == {"spans": {}, "counters": {}}


def test_span_that_raises_still_records():
    before = telemetry.snapshot()
    with pytest.raises(ValueError):
        with telemetry.span("t.raises"):
            raise ValueError("boom")
    assert _delta(before)["spans"]["t.raises"][1] == 1


def test_counts_from_threads_add_up():
    before = telemetry.snapshot()

    def work():
        for _ in range(1000):
            telemetry.count("t.threads")
            with telemetry.span("t.thread_span"):
                pass

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    d = _delta(before)
    assert d["counters"]["t.threads"] == 4000
    assert d["spans"]["t.thread_span"][1] == 4000


def test_span_on_profiler_host_plane(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.span("t.traced"):
            jnp.arange(8.0).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    assert files
    names = {e.name
             for plane in ProfileData.from_file(files[0]).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert telemetry.PREFIX + "t.traced" in names
