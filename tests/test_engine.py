"""Cache-aware DSE evaluation engine: bitwise parity of the vectorized
genome->SoA stacking against the reference decode() path, memoization
identity, canonicalization soundness, prefilter semantics, and GA
fixed-seed equivalence with the pre-refactor evaluation path."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.dse.api import EngineConfig
from repro.core.dse.batch_eval import (batch_evaluate, prepare_configs,
                                       prepare_workload)
from repro.core.dse.encoding import (FIELDS_PER_TILE, GENOME_LEN,
                                     _TILE_FIELDS, decode, random_genomes)
from repro.core.dse.engine import (EvalEngine, canonical_genomes,
                                   genome_areas, genomes_to_configs)
from repro.core.dse.sweep import evaluate_genomes_reference
from repro.core.workloads import build

WLS = ["kan", "resnet50_int8"]


def _mixed_genomes(n_per=32, seed=7):
    rng = np.random.default_rng(seed)
    return np.concatenate([random_genomes(rng, n_per, family=f)
                           for f in (None, "homo", "hetero_bl",
                                     "hetero_bls")])


def test_vectorized_stacking_bitwise_parity():
    g = _mixed_genomes()
    ref = prepare_configs([decode(x, f"g{i}") for i, x in enumerate(g)])
    vec = genomes_to_configs(g)
    for grp in ("tile", "chip"):
        assert set(ref[grp]) == set(vec[grp])
        for k in ref[grp]:
            assert np.array_equal(ref[grp][k], vec[grp][k]), (grp, k)


def test_genome_areas_match_reference():
    from repro.core.simulator.area import chip_area
    g = _mixed_genomes(8)
    areas = genome_areas(g)
    for i, x in enumerate(g):
        assert areas[i] == chip_area(decode(x))


def test_memoized_results_identical_to_fresh():
    rng = np.random.default_rng(1)
    g = random_genomes(rng, 20)
    eng = EvalEngine(WLS)
    fresh = evaluate_genomes_reference(g, WLS)
    first = eng.evaluate(g)
    for k in fresh:
        assert np.array_equal(fresh[k], first[k]), k
    assert first["meta"]["backend"] == "scan"
    assert first["meta"]["misses"] == len(g)
    # second pass: all hits, bitwise identical
    misses_before = eng.stats.misses
    again = eng.evaluate(g)
    assert eng.stats.misses == misses_before
    assert again["meta"]["hits"] == len(g)
    assert again["meta"]["hit_rate"] == 1.0
    metric_keys = [k for k in first if k != "meta"]
    for k in metric_keys:
        assert np.array_equal(first[k], again[k]), k
    # shuffled subset rides the memo and still matches
    idx = rng.permutation(len(g))[:9]
    sub = eng.evaluate(g[idx])
    for k in metric_keys:
        assert np.array_equal(first[k][idx], sub[k]), k
    assert eng.stats.misses == misses_before
    assert eng.stats.hit_rate() > 0


def test_duplicates_within_one_call_simulated_once():
    rng = np.random.default_rng(2)
    g = random_genomes(rng, 6)
    batch = np.concatenate([g, g[::-1]])
    eng = EvalEngine(["kan"])
    out = eng.evaluate(batch)
    assert eng.stats.misses == 6
    assert eng.stats.hits == 6
    for k in ("latency", "energy", "tops_w", "area"):
        assert np.array_equal(out[k][:6], out[k][6:][::-1]), k


def test_canonical_genomes_zero_inactive_blocks():
    rng = np.random.default_rng(3)
    g = random_genomes(rng, 64)
    c = canonical_genomes(g)
    for i, genome in enumerate(g):
        n_types = int(genome[0]) + 1
        for t in range(n_types, 3):
            sl = slice(1 + t * FIELDS_PER_TILE, 1 + (t + 1) * FIELDS_PER_TILE)
            assert (c[i, sl] == 0).all()
    # canonicalization never changes area or metrics
    assert np.array_equal(genome_areas(g), genome_areas(c))
    ws = prepare_workload(build("kan"))
    r1 = batch_evaluate(ws, prepare_configs([decode(x) for x in g]))
    r2 = batch_evaluate(ws, prepare_configs([decode(x) for x in c]))
    for k in ("latency_s", "energy_pj", "achieved_tops"):
        assert np.array_equal(r1[k], r2[k]), k


def test_special_tile_inert_genes():
    """Genes decode() ignores on Special-Function tiles (rows/cols and the
    MAC-path knobs) produce bitwise-identical metrics and area."""
    rng = np.random.default_rng(5)
    g = random_genomes(rng, 12, family="hetero_bls")
    g2 = g.copy()
    base = 1 + 2 * FIELDS_PER_TILE
    for f in ("rows", "cols", "engine", "prec", "sparsity", "dataflow",
              "asym", "pipe"):
        g2[:, base + _TILE_FIELDS.index(f)] = rng.integers(0, 3, len(g))
    assert np.array_equal(canonical_genomes(g), canonical_genomes(g2))
    ws = prepare_workload(build("kan"))
    r1 = batch_evaluate(ws, prepare_configs([decode(x) for x in g]))
    r2 = batch_evaluate(ws, prepare_configs([decode(x) for x in g2]))
    for k in ("latency_s", "energy_pj", "achieved_tops"):
        assert np.array_equal(r1[k], r2[k]), k


def test_asym_equivalence_classes():
    """asym_mac only acts through supports_precision; the canonical map
    collapses variants that cannot change any op's support."""
    rng = np.random.default_rng(6)
    g = random_genomes(rng, 24)
    g2 = g.copy()
    col = _TILE_FIELDS.index("asym")
    for t in range(3):
        g2[:, 1 + t * FIELDS_PER_TILE + col] = rng.integers(0, 4, len(g))
    same = np.all(canonical_genomes(g) == canonical_genomes(g2), axis=1)
    assert same.any()
    idx = np.nonzero(same)[0]
    chips1 = [decode(g[i]) for i in idx]
    chips2 = [decode(g2[i]) for i in idx]
    ws = prepare_workload(build("resnet50_int8"), aggressive_int4=True)
    r1 = batch_evaluate(ws, prepare_configs(chips1))
    r2 = batch_evaluate(ws, prepare_configs(chips2))
    for k in ("latency_s", "energy_pj", "achieved_tops"):
        assert np.array_equal(r1[k], r2[k]), k


def test_keep_prefilter_skips_without_poisoning_the_memo():
    rng = np.random.default_rng(4)
    g = random_genomes(rng, 12)
    eng = EvalEngine(["kan"])
    areas = eng.areas(g)
    cut = float(np.median(areas))
    out = eng.evaluate(g, keep=lambda a: a <= cut)
    skipped = areas > cut
    assert np.isinf(out["latency"][skipped]).all()
    assert np.isinf(out["energy"][skipped]).all()
    assert eng.stats.skips == int(skipped.sum())
    # areas are exact even for skipped genomes
    assert np.array_equal(out["area"], areas)
    assert out["meta"]["skips"] == int(skipped.sum())
    # an unfiltered follow-up simulates the skipped genomes for real
    full = eng.evaluate(g)
    fresh = EvalEngine(["kan"]).evaluate(g)
    for k in full:
        if k == "meta":
            continue
        assert np.array_equal(full[k], fresh[k]), k


def test_pad_size_rounds_to_mesh_multiple_after_bucket():
    """Sharded batch padding: the jit bucket is rounded up to a mesh-size
    multiple AFTER bucket rounding (an indivisible batch axis would fall
    back to whole-batch per-device replication), and shape reuse can
    never hand back a non-multiple."""
    class _Mesh:
        size = 8

    class _Sharding:
        mesh = _Mesh()

    eng = EvalEngine(["kan"])
    eng._sharding = _Sharding()
    for n in (1, 17, 18, 33, 63, 64, 65):
        p = eng._pad_size(n)
        assert p >= n and p % 8 == 0, (n, p)
    # a stale non-multiple shape in the reuse window is filtered out
    eng._shapes.add(42)
    p = eng._pad_size(28)   # bucket 28 -> mesh-rounded 32; window [32, 48]
    assert p % 8 == 0 and p != 42
    # unsharded engines keep plain bucket padding
    plain = EvalEngine(["kan"])
    assert plain._pad_size(17) == 20


def test_reserve_shapes_pads_every_miss_count_minimally():
    """``reserve_shapes`` registers the whole bucket range of a GA's miss
    counts up front, so no count reuses a larger shape within the 1.5x
    window: every batch pads to its own bucket."""
    eng = EvalEngine(["kan"])
    eng.reserve_shapes(200)
    assert eng._shapes == set(range(16, 204, 4))
    assert [eng._pad_size(n) for n in (1, 16, 17, 150, 200)] \
        == [16, 16, 20, 152, 200]
    fresh = EvalEngine(["kan"])
    fresh._pad_size(200)
    assert fresh._pad_size(150) == 200     # unreserved: reuses within 1.5x


def test_memo_lru_eviction_bounded_and_correct():
    """The canonical-genome memo is a bounded LRU: size never exceeds
    ``memo_max``, the oldest (least recently touched) entries are evicted
    first, and evicted genomes re-simulate to identical rows."""
    rng = np.random.default_rng(8)
    g = random_genomes(rng, 12)
    eng = EvalEngine(["kan"], config=EngineConfig(memo_max=8, batch=4))
    assert eng.memo_max == 8
    first = eng.evaluate(g)
    assert len(eng._memo) <= 8
    # the first rows were evicted -> re-scoring them is a miss, not a hit
    misses_before = eng.stats.misses
    again = eng.evaluate(g[:4])
    assert eng.stats.misses > misses_before
    for k in ("latency", "energy", "tops_w", "area"):
        assert np.array_equal(first[k][:4], again[k]), k
    # hits refresh recency: a touched entry survives newer insertions
    eng2 = EvalEngine(["kan"], config=EngineConfig(memo_max=8, batch=4))
    eng2.evaluate(g[:8])
    keep_key = b"latency:" + eng2._key(canonical_genomes(g[:1])[0])
    eng2.evaluate(g[:1])              # touch -> most recent
    eng2.evaluate(g[8:12])            # insert 4 more, evicting the LRU end
    assert keep_key in eng2._memo
    assert len(eng2._memo) <= 8
    # memo_limit stays accepted as the pre-PR-5 alias (it now warns)
    with pytest.warns(DeprecationWarning):
        assert EvalEngine(["kan"], memo_limit=9, batch=4).memo_max == 9


def test_exact_backend_evaluate_matches_rescore():
    """backend='exact' (the fused class-specialized search kernel) scores
    evaluate() bitwise identically to the exact rescore path, reports
    itself in meta, and memoizes like any other backend."""
    g = random_genomes(np.random.default_rng(9), 10)
    eng = EvalEngine(WLS, config=EngineConfig(backend="exact"))
    out = eng.evaluate(g)
    assert out["meta"]["backend"] == "exact"
    ref = EvalEngine(WLS).rescore(g)
    for k in ("latency", "energy", "tops_w", "area"):
        assert np.array_equal(out[k], ref[k]), k
    again = eng.evaluate(g)
    assert again["meta"]["hit_rate"] == 1.0
    for k in ("latency", "energy", "tops_w", "area"):
        assert np.array_equal(out[k], again[k]), k
    # throughput mode rides the same scan
    tp = eng.evaluate(g, mode="throughput")
    tp_ref = EvalEngine(WLS).rescore(g, mode="throughput")
    for k in ("latency", "energy", "tops_w", "area"):
        assert np.array_equal(tp[k], tp_ref[k]), k
    # the fused search kernel rejects the python per-candidate mapper
    with pytest.raises(ValueError):
        EvalEngine(WLS, config=EngineConfig(backend="exact",
                                            exact_mapper="python"))


def test_evaluate_accepts_precomputed_canonical():
    g = random_genomes(np.random.default_rng(10), 6)
    eng = EvalEngine(["kan"])
    a = eng.evaluate(g, canonical=canonical_genomes(g))
    b = EvalEngine(["kan"]).evaluate(g)
    for k in ("latency", "energy", "tops_w", "area"):
        assert np.array_equal(a[k], b[k]), k
    # memo keys line up: the same genomes are now all hits
    assert eng.evaluate(g)["meta"]["hit_rate"] == 1.0


def test_rescore_batched_mapper_matches_python_mapper():
    """The compile-free exact path (default) scores bitwise identically
    to the per-candidate map_graph + lower_plan pipeline."""
    g = random_genomes(np.random.default_rng(5), 6)
    rb = EvalEngine(["kan"]).rescore(g)
    rp = EvalEngine(["kan"],
                    config=EngineConfig(exact_mapper="python")).rescore(g)
    for k in ("latency", "energy", "tops_w", "area"):
        assert np.array_equal(rb[k], rp[k]), k
    assert rb["meta"]["mapper"] == "batched"
    assert rp["meta"]["mapper"] == "python"
    assert rb["meta"]["backend"] == rp["meta"]["backend"] == "batched"


def test_run_ga_fixed_seed_same_best_fitness():
    """The cache-aware engine (memo + vectorized stacking + bracket
    prefilter) reproduces the pre-refactor GA result bit-for-bit."""
    from repro.core.dse.ga import GAConfig, run_ga
    from repro.core.dse.sweep import run_sweep

    sw = run_sweep(WLS, samples_per_stratum=4, seed=0,
                   brackets=(100.0, 200.0))
    cfg = GAConfig(population=10, generations=3, seed_top_k=6, early_stop=3)
    legacy = run_ga(sw, 200.0, cfg, seed=1,
                    engine=EvalEngine(WLS, config=EngineConfig(
                        memoize=False, vectorized=False)),
                    prefilter=False)
    cached = run_ga(sw, 200.0, cfg, seed=1, engine=EvalEngine(WLS),
                    prefilter=True)
    assert legacy is not None and cached is not None
    assert legacy.best_fitness == cached.best_fitness
    assert np.array_equal(legacy.best_genome, cached.best_genome)
    assert legacy.history == cached.history


@pytest.mark.slow
def test_sharded_evaluation_matches_single_device():
    """Candidate-axis sharding over forced host devices is a pure layout
    change: results match the unsharded engine bitwise."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
from repro.core.dse.encoding import random_genomes
from repro.core.dse.api import EngineConfig
from repro.core.dse.engine import EvalEngine
g = random_genomes(np.random.default_rng(0), 16)
plain = EvalEngine(["kan"]).evaluate(g)
shard = EvalEngine(["kan"], config=EngineConfig(shard=True))
assert shard._sharding is not None
out = shard.evaluate(g)
for k in plain:
    assert np.array_equal(plain[k], out[k]), k
# the compile-free exact path shards too; 13 is deliberately uneven so
# _pad_size's mesh rounding is what keeps the batch divisible
g13 = g[:13]
pr = EvalEngine(["kan"]).rescore(g13)
sr = shard.rescore(g13)
for k in ("latency", "energy", "tops_w", "area"):
    assert np.array_equal(pr[k], sr[k]), k
print("OK")
"""
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
           "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=240, env=env)
    assert "OK" in out.stdout, out.stderr[-2000:]


def test_memo_max_applies_to_caller_supplied_store():
    """An explicit ``memo_max`` used to be silently ignored whenever the
    caller passed ``store=`` — the cap must re-cap the store's in-memory
    LRU tier, or raise when there is no LRU tier to cap."""
    from repro.core.dse.store import (MemoryLRUStore, SqliteStore,
                                      TieredStore)

    st = MemoryLRUStore(max_entries=1000)
    eng = EvalEngine(["kan"],
                     config=EngineConfig(memo_max=8, batch=4, store=st))
    assert st.max_entries == 8 and eng.memo_max == 8
    g = random_genomes(np.random.default_rng(3), 12)
    eng.evaluate(g)
    assert len(st) <= 8

    # the resize evicts eagerly when the store already holds more
    big = MemoryLRUStore(max_entries=1000)
    warm = EvalEngine(["kan"],                       # no cap: untouched
                      config=EngineConfig(batch=4, store=big))
    warm.evaluate(g)
    assert big.max_entries == 1000 and len(big) > 8
    EvalEngine(["kan"], config=EngineConfig(memo_max=8, batch=4,
                                            store=big))
    assert big.max_entries == 8 and len(big) <= 8

    # tiered: the cap lands on the LRU front
    tiered = TieredStore(MemoryLRUStore(max_entries=500),
                         SqliteStore(":memory:"))
    EvalEngine(["kan"], config=EngineConfig(memo_max=16, batch=4,
                                            store=tiered))
    assert tiered.front.max_entries == 16

    # no LRU tier to cap -> error, not a silent no-op
    with pytest.raises(ValueError, match="memo_max"):
        EvalEngine(["kan"], config=EngineConfig(
            memo_max=8, batch=4, store=SqliteStore(":memory:")))
    # the default cap is NOT "explicit": plain stores pass through
    assert EvalEngine(["kan"], config=EngineConfig(
        store=MemoryLRUStore(max_entries=777))).store.max_entries == 777


def test_export_import_memo_roundtrip():
    """The seed-boundary sync surface: ``export_memo`` returns exactly
    the store's rows for one mode (canonical genomes + float64 rows),
    and ``import_memo`` makes a cold engine serve them as pure hits,
    bitwise."""
    g = random_genomes(np.random.default_rng(4), 6)
    eng = EvalEngine(["kan"], config=EngineConfig(backend="exact"))
    m = eng.evaluate(g)
    canon, rows = eng.export_memo()
    assert canon.shape[1:] == (GENOME_LEN,) and rows.shape[1:] == (3, 1)
    assert len(canon) == len(np.unique(canonical_genomes(g), axis=0))
    # rows match the evaluation bitwise (set comparison via sorting)
    key = np.lexsort(canon.T)
    want = {canonical_genomes(g)[i].tobytes():
            np.stack([m["latency"][i], m["energy"][i],
                      m["tops_w"][i]]).tobytes() for i in range(len(g))}
    got = {canon[i].tobytes(): rows[i].tobytes() for i in range(len(canon))}
    assert got == want
    # back-to-back exports over an unchanged store return the cached view
    c2, r2 = eng.export_memo()
    assert c2 is canon and r2 is rows

    cold = EvalEngine(["kan"], config=EngineConfig(backend="exact"))
    assert cold.import_memo(canon, rows) == len(canon)
    served = cold.evaluate(g)
    assert served["meta"]["hits"] == len(g)
    for k in ("latency", "energy", "tops_w"):
        assert np.array_equal(served[k], m[k]), k
    # shape and mode guards
    with pytest.raises(ValueError, match="mode"):
        eng.export_memo(mode="bogus")
    with pytest.raises(ValueError, match="shape"):
        eng.import_memo(canon, rows[:, :2])


# =============================================================================
# non-finite guard (PR 8)
# =============================================================================

def _poison_simulate(eng, cell=(0, 0)):
    """Make the engine's next simulation return one NaN latency cell."""
    inner = eng._simulate
    state = {"armed": True}

    def wrapped(cfgs, n, genomes=None, mode=None):
        lat, en, tw = inner(cfgs, n, genomes=genomes, mode=mode)
        if state["armed"]:
            state["armed"] = False
            lat = np.array(lat, np.float64, copy=True)
            lat[cell] = np.nan
        return lat, en, tw

    eng._simulate = wrapped
    return state


def test_nonfinite_default_raises_naming_the_genome():
    from repro.core.dse.engine import NonFiniteMetricsError
    g = random_genomes(np.random.default_rng(11), 5)
    eng = EvalEngine(["kan"], config=EngineConfig(backend="exact"))
    _poison_simulate(eng)
    with pytest.raises(NonFiniteMetricsError) as ei:
        eng.evaluate(g)
    err = ei.value
    assert err.retryable                     # transient by contract
    assert err.canon.shape == (GENOME_LEN,)  # the culprit, canonical
    assert str(err.canon.tolist()) in str(err)
    # the poisoned batch never reached the memo: a retry is bitwise clean
    clean = EvalEngine(["kan"], config=EngineConfig(backend="exact")).evaluate(g)
    retried = eng.evaluate(g)
    for k in ("latency", "energy", "tops_w"):
        assert clean[k].tobytes() == retried[k].tobytes(), k


def test_nonfinite_skip_scores_minus_inf_and_never_memoizes():
    g = random_genomes(np.random.default_rng(11), 5)
    eng = EvalEngine(["kan"], config=EngineConfig(backend="exact",
                                                  nonfinite="skip"))
    _poison_simulate(eng)
    res = eng.evaluate(g)
    assert res["meta"]["nonfinite"] == 1
    bad = np.isinf(res["latency"]).all(axis=1) & \
        np.isinf(res["energy"]).all(axis=1) & (res["tops_w"] == 0).all(axis=1)
    assert bad.sum() == 1                    # exactly the poisoned row
    # the skipped row was not memoized: re-evaluating recomputes it —
    # now un-poisoned — and the whole batch matches a clean engine
    again = eng.evaluate(g)
    assert again["meta"]["nonfinite"] == 0
    clean = EvalEngine(["kan"], config=EngineConfig(backend="exact")).evaluate(g)
    for k in ("latency", "energy", "tops_w"):
        assert clean[k].tobytes() == again[k].tobytes(), k


def test_nonfinite_ctor_validation():
    with pytest.raises(ValueError, match="nonfinite"):
        EvalEngine(["kan"], config=EngineConfig(nonfinite="bogus"))
    # legitimate unmappable rows (inf, inf, 0) are NOT corruption: the
    # skip path leaves genuinely-infinite sentinel rows alone
    eng = EvalEngine(["kan"], config=EngineConfig(backend="exact",
                                                  nonfinite="raise"))
    assert eng.nonfinite == "raise"
