"""Checkpoint/resume for the §4 pipeline (PR 8): a resumed run must be
*bitwise* equal to an uninterrupted one — merged Pareto front, per-seed
per-bracket results, and ``best()`` — with completed stages replayed
from their durable records (never recomputed), pinned both for an
in-process interrupt and (``-m slow``) a real SIGKILL mid-refinement.
"""
import os
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from repro.core.dse.api import EngineConfig
from repro.core.dse.checkpoint import (CheckpointMismatch,
                                       PipelineCheckpoint, run_digest)
from repro.core.dse.engine import EvalEngine
from repro.core.dse.ga import GAConfig
from repro.core.dse.pipeline import run_pipeline

WLS = ["kan"]
CFG = GAConfig(population=16, generations=3, seed_top_k=8,
               early_stop=10_000)
KW = dict(seeds=(0, 1), brackets=(100.0, 200.0), samples_per_stratum=4,
          cfg=CFG)


def _engine():
    return EvalEngine(WLS, config=EngineConfig(backend="exact",
                                               nonfinite="skip"))


def _assert_same_study(ref, res):
    assert ref.front_points.tobytes() == res.front_points.tobytes()
    assert ref.front_genomes.tobytes() == res.front_genomes.tobytes()
    assert ref.evaluated == res.evaluated
    for s in KW["seeds"]:
        assert set(ref.results[s]) == set(res.results[s])
        for b, r in ref.results[s].items():
            q = res.results[s][b]
            assert r.best_fitness == q.best_fitness, (s, b)
            assert r.best_genome.tobytes() == q.best_genome.tobytes()
            assert r.history == q.history, (s, b)
            for k in ("latency", "energy", "tops_w"):
                assert np.asarray(r.best_metrics[k]).tobytes() == \
                    np.asarray(q.best_metrics[k]).tobytes(), (s, b, k)
    for b in KW["brackets"]:
        rb, qb = ref.best(b), res.best(b)
        assert (rb is None) == (qb is None)
        if rb is not None:
            assert rb.best_fitness == qb.best_fitness
            assert rb.best_genome.tobytes() == qb.best_genome.tobytes()


class _Interrupt(Exception):
    pass


def test_interrupted_resume_bitwise_equal(tmp_path):
    ref = run_pipeline(WLS, engine=_engine(), **KW)
    ck = str(tmp_path / "ck")

    seen = []

    def tripwire(ev):
        seen.append(ev["stage"])
        if len(seen) == 3:          # mid-study: after seed 0's 2nd stage
            raise _Interrupt

    with pytest.raises(_Interrupt):
        run_pipeline(WLS, checkpoint=ck, on_stage=tripwire, **KW)

    # no torn records: interrupted writes leave only final-name .npz
    assert not [f for f in os.listdir(ck) if f.endswith(".tmp")]

    events = []
    res = run_pipeline(WLS, checkpoint=ck,
                       on_stage=lambda ev: events.append(dict(ev)), **KW)
    # record-before-emit: every stage that reported before the interrupt
    # replays from its record, flagged resumed, and nothing re-runs
    resumed = [(e["stage"], e.get("seed"), e.get("bracket"))
               for e in events if e.get("resumed")]
    assert len(resumed) >= len(seen)
    assert [r[0] for r in resumed[:len(seen)]] == seen
    _assert_same_study(ref, res)

    # a fully-complete directory resumes everything: zero engine work
    eng = _engine()
    replay = run_pipeline(WLS, engine=eng, checkpoint=ck, **KW)
    assert eng.stats.dispatches == 0
    _assert_same_study(ref, replay)


def test_checkpoint_digest_guards_study_identity(tmp_path):
    ck = str(tmp_path / "ck")
    run_pipeline(WLS, checkpoint=ck, **KW)
    # same parameters: fine (resumes); different ones: refused
    run_pipeline(WLS, checkpoint=ck, **KW)
    with pytest.raises(CheckpointMismatch):
        run_pipeline(WLS, checkpoint=ck, **{**KW, "seeds": (0, 2)})
    with pytest.raises(CheckpointMismatch):
        run_pipeline(WLS, checkpoint=ck,
                     **{**KW, "cfg": GAConfig(population=32, generations=3,
                                              seed_top_k=8,
                                              early_stop=10_000)})


def test_checkpoint_record_load_roundtrip(tmp_path):
    ck = PipelineCheckpoint(str(tmp_path / "ck"))
    with pytest.raises(RuntimeError):
        ck.record("sweep:0", x=np.arange(3))    # open() must run first
    ck.open("digest-a")
    arr = np.array([5e-324, 1e308, -0.0, np.inf])
    ck.record("refine:0:100", vals=arr, n=np.int64(4))
    assert ck.has("refine:0:100") and not ck.has("sweep:0")
    # a second handle on the directory sees the same records, bitwise
    ck2 = PipelineCheckpoint(ck.path).open("digest-a")
    assert ck2.completed() == ["refine:0:100"]
    got = ck2.load("refine:0:100")
    assert got["vals"].tobytes() == arr.tobytes()
    assert int(got["n"]) == 4
    with pytest.raises(CheckpointMismatch):
        PipelineCheckpoint(ck.path).open("digest-b")


def test_run_digest_sensitivity():
    eng = _engine()
    base = run_digest(eng, (0, 1), (100.0,), 4, CFG, None, 5, 2)
    assert base == run_digest(eng, (0, 1), (100.0,), 4, CFG, None, 5, 2)
    assert base != run_digest(eng, (0, 2), (100.0,), 4, CFG, None, 5, 2)
    assert base != run_digest(eng, (0, 1), (200.0,), 4, CFG, None, 5, 2)
    assert base != run_digest(eng, (0, 1), (100.0,), 8, CFG, None, 5, 2)
    assert base != run_digest(eng, (0, 1), (100.0,), 4, CFG, 2, 5, 2)
    other = EvalEngine(["resnet50_int8"],
                       config=EngineConfig(backend="exact"))
    assert base != run_digest(other, (0, 1), (100.0,), 4, CFG, None, 5, 2)


_KILL_CHILD = textwrap.dedent("""
    import sys
    from repro.core.dse.engine import EvalEngine
    from repro.core.dse.ga import GAConfig
    from repro.core.dse.pipeline import run_pipeline

    def on_stage(ev):
        print(f"STAGE {ev['stage']}", flush=True)

    run_pipeline(["kan"], seeds=(0, 1), brackets=(100.0, 200.0),
                 samples_per_stratum=4,
                 cfg=GAConfig(population=16, generations=3, seed_top_k=8,
                              early_stop=10_000),
                 checkpoint=sys.argv[1], on_stage=on_stage)
    print("PIPELINE DONE", flush=True)
""")


@pytest.mark.slow
def test_sigkill_resume_bitwise_equal(tmp_path):
    """Kill -9 a checkpointed pipeline right after its first refinement
    reports, then resume: the study must equal an uninterrupted run
    bitwise, with the completed stages replayed (resumed events + zero
    dispatches for them) instead of recomputed."""
    ref = run_pipeline(WLS, engine=_engine(), **KW)
    ck = str(tmp_path / "ck")
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.Popen(
        [sys.executable, "-c", _KILL_CHILD, ck],
        stdout=subprocess.PIPE, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    stages = []
    try:
        for line in proc.stdout:       # SIGKILL mid-study, no warning
            if line.startswith("STAGE"):
                stages.append(line.split()[1])
            if len(stages) == 2:       # sweep + first refine reported
                proc.kill()            # SIGKILL: no atexit, no flush
                break
        assert proc.wait(timeout=60) == -signal.SIGKILL
    finally:
        if proc.poll() is None:        # pragma: no cover - cleanup
            proc.kill()
            proc.wait(timeout=60)
    assert stages == ["sweep", "refine"]

    # the records the child reported before dying are durable
    done = PipelineCheckpoint(ck).open(
        run_digest(_engine(), KW["seeds"], KW["brackets"],
                   KW["samples_per_stratum"], CFG, None, 5, 2)).completed()
    assert "sweep:0" in done

    events = []
    eng = _engine()
    res = run_pipeline(WLS, engine=eng, checkpoint=ck,
                       on_stage=lambda ev: events.append(dict(ev)), **KW)
    resumed = [(e["stage"], e.get("seed")) for e in events
               if e.get("resumed")]
    assert ("sweep", 0) in resumed     # skipped, not recomputed
    # resumed stages cost zero simulation: every dispatch the resumed
    # run made belongs to the stages the child never finished
    full = _engine()
    run_pipeline(WLS, engine=full, **KW)
    assert eng.stats.dispatches < full.stats.dispatches
    _assert_same_study(ref, res)
