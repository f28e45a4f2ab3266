"""Fused multi-seed pipeline (PR 7).

Pins the PR's load-bearing contracts:

* THE bitwise invariant — a seeded single-island ``run_ga_fused`` run
  (device-resident memo, whole refinement as one dispatch) equals the
  host-memo device loop ``run_ga(loop="device")`` genome-for-genome
  (best_genome + history + fitness), with warm memo state bitwise inert;
* the device config mirror — ``_chip_area_device``/``_configs_device``
  areas equal the host ``genome_areas`` bit-for-bit (the Eq. 8 band
  input; host-precomputed gather tables, no device mul->add chains);
* ``bracket_bounds`` NaN path — unknown brackets score every design
  -inf, known brackets reproduce ``area_bracket`` membership exactly;
* island-model determinism — same-seed island runs replay bitwise, on
  one device and (``-m slow``) under ``shard=True`` with the island
  axis sharded over forced host devices;
* ``run_pipeline`` — stage events, cumulative Pareto-front validity,
  cross-seed best() accounting.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import telemetry
from repro.core.dse.api import EngineConfig
from repro.core.dse.engine import EvalEngine, genome_areas
from repro.core.dse.encoding import GENOME_LEN, random_genomes
from repro.core.dse.ga import GAConfig, run_ga
from repro.core.dse.ga_device import (bracket_bounds, fitness_device,
                                      run_ga_fused)
from repro.core.dse.objective import AREA_BRACKETS, area_bracket
from repro.core.dse.pareto import pareto_mask
from repro.core.dse.pipeline import run_pipeline
from repro.core.dse.sweep import run_sweep

WLS = ["kan"]
CFG = GAConfig(population=16, generations=3, seed_top_k=8, early_stop=100)


def _sweep():
    return run_sweep(WLS, samples_per_stratum=4, seed=0,
                     brackets=(100.0, 200.0))


def _exact():
    return EvalEngine(WLS, config=EngineConfig(backend="exact"))


def _same(a, b) -> bool:
    return (a is not None and b is not None
            and np.array_equal(a.best_genome, b.best_genome)
            and a.history == b.history
            and a.best_fitness == b.best_fitness)


# ---------------------------------------------------------------- invariant
def test_fused_bitwise_equals_host_memo_device_loop():
    sw = _sweep()
    dev = run_ga(sw, 200.0, CFG, seed=1, engine=_exact(), loop="device")
    fused = run_ga(sw, 200.0, CFG, seed=1, engine=_exact(), loop="fused")
    assert _same(dev, fused)
    assert dev.evaluated == fused.evaluated


def test_fused_warm_memo_is_bitwise_inert():
    """Replaying on an engine whose store already holds every row (and
    preloading it into the device memo) changes nothing: memo hits are
    served bitwise, all-hit generations skip the scan."""
    sw = _sweep()
    eng = _exact()
    cold = run_ga_fused(sw, 200.0, CFG, seed=2, engine=eng, islands=1)
    warm = run_ga_fused(sw, 200.0, CFG, seed=2, engine=eng, islands=1)
    assert _same(cold.result, warm.result)
    assert cold.generations_run == warm.generations_run
    assert np.array_equal(cold.population, warm.population)
    for k in cold.pop_metrics:
        assert np.array_equal(cold.pop_metrics[k], warm.pop_metrics[k])


def _refine_counters(before):
    c = telemetry.diff(telemetry.snapshot(), before)["counters"]
    return {k: c.get(k, 0) for k in ("refine.rows", "refine.memo_hits",
                                     "refine.searched_rows",
                                     "refine.discarded_rows")}


def test_fused_warm_replay_counts_every_row_a_memo_hit():
    """The replay of a synced cold run: the device memo answers every
    row of every generation and the search scan never runs."""
    sw = _sweep()
    eng = _exact()
    run_ga_fused(sw, 200.0, CFG, seed=2, engine=eng, islands=1)
    before = telemetry.snapshot()
    warm = run_ga_fused(sw, 200.0, CFG, seed=2, engine=eng, islands=1)
    P = CFG.population
    assert warm.memo_hits.shape == (warm.generations_run + 1,)
    assert np.all(warm.memo_hits == P)
    assert np.all(warm.searched == 0)
    c = _refine_counters(before)
    assert c["refine.memo_hits"] == c["refine.rows"]
    assert c["refine.searched_rows"] == c["refine.discarded_rows"] == 0


def test_fused_cold_run_counters_match_registry():
    """With an empty memo the seed population is searched; the registry's
    ``refine.*`` deltas are the sums of the per-generation arrays."""
    sw = _sweep()
    before = telemetry.snapshot()
    cold = run_ga_fused(sw, 200.0, CFG, seed=2, engine=_exact(), islands=1,
                        store_sync=False)
    P, n = CFG.population, cold.generations_run
    assert cold.searched[0] == 1 and cold.memo_hits[0] == 0
    assert cold.memo_hits.sum() < P * (n + 1)
    assert np.all((cold.memo_hits >= 0) & (cold.memo_hits <= P))
    # the scan runs exactly when some row misses
    assert np.array_equal(cold.searched, (cold.memo_hits < P).astype(int))
    c = _refine_counters(before)
    assert c["refine.rows"] == P * (n + 1) == cold.result.evaluated
    assert c["refine.memo_hits"] == cold.memo_hits.sum()
    assert c["refine.searched_rows"] == P * cold.searched.sum()
    assert c["refine.discarded_rows"] == (cold.memo_hits
                                          * cold.searched).sum()


def test_fused_counters_seeded_determinism():
    sw = _sweep()
    runs = [run_ga_fused(sw, 200.0, CFG, seed=5, engine=_exact(), islands=1,
                         store_sync=False) for _ in range(2)]
    assert np.array_equal(runs[0].memo_hits, runs[1].memo_hits)
    assert np.array_equal(runs[0].searched, runs[1].searched)


def test_fused_frontend_validation():
    sw = _sweep()
    with pytest.raises(ValueError, match="fused"):
        run_ga(sw, 200.0, CFG, seed=0, loop="fused",
               on_generation=lambda **kw: None)
    with pytest.raises(ValueError, match="exact"):
        run_ga_fused(sw, 200.0, CFG, seed=0,
                     engine=EvalEngine(WLS, config=EngineConfig(backend="scan")))
    # a bracket with no homogeneous baseline returns None (run_ga
    # contract) — the baseline is cumulative over brackets, so only a
    # bracket BELOW every sampled homo design lacks one
    assert 50.0 not in sw.homo_baseline()
    assert run_ga_fused(sw, 50.0, CFG, seed=0, engine=_exact()) is None


def test_oversized_seed_set_truncates_to_population():
    """seed_top_k > population with enough in-bracket sweep survivors
    used to leave generation 0 over-populated: the host loop silently
    ran it at the wrong size and the fused while_loop crashed on the
    shape mismatch.  All loops must seed exactly ``population`` genomes
    — and still agree bitwise."""
    sw = run_sweep(WLS, samples_per_stratum=16, seed=0, brackets=(200.0,))
    cfg = GAConfig(population=8, generations=2, seed_top_k=50,
                   early_stop=100)
    fit = sw.fitness(cfg.alpha)
    assert ((sw.bracket == 200.0) & np.isfinite(fit)).sum() > cfg.population
    dev = run_ga(sw, 200.0, cfg, seed=1, engine=_exact(), loop="device")
    fused = run_ga(sw, 200.0, cfg, seed=1, engine=_exact(), loop="fused")
    assert _same(dev, fused)
    assert dev.evaluated == fused.evaluated


# ------------------------------------------------------------ device configs
def test_device_areas_bitwise_equal_host():
    from repro.core.dse.ga_device import _chip_area_device, _configs_device
    import jax
    from repro.core.calibrate.asap7 import DEFAULT_CALIB

    rng = np.random.default_rng(17)
    g = np.concatenate([random_genomes(rng, 32, family=f)
                        for f in (None, "homo", "hetero_bl", "hetero_bls")])
    host = genome_areas(g)
    area_only = np.asarray(jax.jit(
        lambda x: _chip_area_device(x, DEFAULT_CALIB))(g.astype(np.int32)))
    assert host.tobytes() == area_only.tobytes()
    _, _, full = jax.jit(
        lambda x: _configs_device(x, DEFAULT_CALIB))(g.astype(np.int32))
    assert host.tobytes() == np.asarray(full).tobytes()


# ------------------------------------------------------------- bracket band
def test_bracket_bounds_unknown_bracket_nan():
    lo, hi = bracket_bounds(123.0)
    assert np.isnan(lo) and np.isnan(hi)
    # host parity: area_bracket never assigns an unknown bracket, so the
    # device band must reject every area -> all fitness -inf
    metrics = {"latency": np.ones((4, 1)), "energy": np.ones((4, 1)),
               "tops_w": np.ones((4, 1)),
               "area": np.array([10.0, 100.0, 400.0, 1e6])}
    fit = fitness_device(metrics, np.ones(1), 123.0)
    assert np.all(fit == -np.inf)


def test_bracket_bounds_band_matches_area_bracket():
    areas = np.concatenate([np.asarray(AREA_BRACKETS),
                            np.asarray(AREA_BRACKETS) + 1e-9,
                            np.asarray(AREA_BRACKETS) - 1e-9,
                            [1e-3, 25.0, 1e5]])
    for b in AREA_BRACKETS:
        lo, hi = bracket_bounds(b)
        for a in areas:
            assert ((lo < a <= hi) == (area_bracket(float(a)) == b)), (b, a)


# ----------------------------------------------------------------- islands
def test_island_ga_seeded_determinism():
    sw = _sweep()
    r1 = run_ga_fused(sw, 200.0, CFG, seed=3, engine=_exact(), islands=2,
                      migrate_every=1, migrate_k=2)
    r2 = run_ga_fused(sw, 200.0, CFG, seed=3, engine=_exact(), islands=2,
                      migrate_every=1, migrate_k=2)
    assert _same(r1.result, r2.result)
    assert np.array_equal(r1.population, r2.population)
    # islands partition the population: a different trajectory from the
    # panmictic run is expected (not asserted), but validity must hold
    assert np.isfinite(r1.result.best_fitness)


def test_island_validation():
    sw = _sweep()
    with pytest.raises(ValueError, match="divisible"):
        run_ga_fused(sw, 200.0, CFG, seed=0, engine=_exact(), islands=3)
    tiny = GAConfig(population=4, generations=1, seed_top_k=2)
    with pytest.raises(ValueError, match="elites"):
        run_ga_fused(sw, 200.0, tiny, seed=0, engine=_exact(), islands=4)


@pytest.mark.slow
def test_island_ga_determinism_under_shard():
    """Under forced host devices with ``shard=True`` (island axis
    sharded over the device ring, migration lowered to a collective
    permute) the seeded island GA replays bitwise — and matches the
    single-device run of the identical configuration computed in the
    parent process."""
    ref = run_ga_fused(_sweep(), 200.0, CFG, seed=4, engine=_exact(),
                       islands=4, migrate_every=1, migrate_k=1)
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
from repro.core.dse.api import EngineConfig
from repro.core.dse.engine import EvalEngine
from repro.core.dse.ga import GAConfig
from repro.core.dse.ga_device import run_ga_fused
from repro.launch.mesh import island_sharding, population_sharding
from repro.core.dse.sweep import run_sweep
assert island_sharding(4) is not None
# indivisible layouts fall back to one device (with a log line)
assert island_sharding(3) is None and population_sharding(6) is None
assert population_sharding(8) is not None
sw = run_sweep(["kan"], samples_per_stratum=4, seed=0,
               brackets=(100.0, 200.0))
cfg = GAConfig(population=16, generations=3, seed_top_k=8, early_stop=100)
runs = [run_ga_fused(sw, 200.0, cfg, seed=4,
                     engine=EvalEngine(["kan"], config=EngineConfig(
                         backend="exact", shard=True)),
                     islands=4, migrate_every=1, migrate_k=1)
        for _ in range(2)]
a, b = (r.result for r in runs)
assert np.array_equal(a.best_genome, b.best_genome)
assert a.history == b.history and a.best_fitness == b.best_fitness
print("GENOME", a.best_genome.tobytes().hex())
print("HIST", ",".join(repr(float(h)) for h in a.history))
"""
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
           "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env)
    assert "GENOME" in out.stdout, out.stderr[-2000:]
    lines = dict(l.split(" ", 1) for l in out.stdout.strip().splitlines()
                 if " " in l)
    assert lines["GENOME"] == ref.result.best_genome.tobytes().hex()
    assert lines["HIST"] == ",".join(repr(float(h))
                                     for h in ref.result.history)


# ---------------------------------------------------------------- pipeline
def test_run_pipeline_stages_and_front():
    events = []
    res = run_pipeline(WLS, seeds=(0, 1), brackets=(100.0, 200.0),
                       samples_per_stratum=4, cfg=CFG, engine=_exact(),
                       islands=1, on_stage=events.append)
    stages = [e["stage"] for e in events]
    assert stages.count("sweep") == 2 and stages.count("seed_done") == 2
    assert stages.count("refine") == 4
    # the cumulative front: sorted by mean energy, all points mutually
    # non-dominating, genomes aligned
    assert res.front_points.shape[1] == 3
    assert res.front_genomes.shape == (len(res.front_points), GENOME_LEN)
    assert np.all(np.diff(res.front_points[:, 0]) >= 0)
    assert pareto_mask(res.front_points).all()
    # refine events carry the cumulative front of their moment
    last_refine = [e for e in events if e["stage"] == "refine"][-1]
    assert np.array_equal(last_refine["front"]["points"], res.front_points)
    # cross-seed accounting
    for b in (100.0, 200.0):
        best = res.best(b)
        assert best is not None
        assert best.best_fitness == max(
            r[b].best_fitness for r in res.results.values() if b in r)
    assert res.evaluated == sum(r.evaluated for by_b in res.results.values()
                                for r in by_b.values())
    # seed boundaries drained device-computed rows back to the store
    assert events[-1]["stage"] == "seed_done"
    assert any(e["drained"] > 0 for e in events if e["stage"] == "seed_done")


def test_run_pipeline_validation():
    with pytest.raises(ValueError, match="exact"):
        run_pipeline(WLS, seeds=(0,), brackets=(200.0,),
                     samples_per_stratum=2,
                     engine=EvalEngine(WLS, config=EngineConfig(backend="scan")))
