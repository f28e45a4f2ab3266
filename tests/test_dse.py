"""DSE engine: encoding, stratified sampling, sweep, GA, Bayes, Pareto."""
import numpy as np
import pytest

from repro.core.dse.encoding import (FAMILIES, GENOME_LEN, decode,
                                     genome_bounds, random_genomes,
                                     sample_in_bracket)
from repro.core.dse.ga import GAConfig, run_ga
from repro.core.dse.objective import AREA_BRACKETS, area_bracket, fitness
from repro.core.dse.pareto import pareto_front, pareto_mask
from repro.core.dse.sweep import run_sweep
from repro.core.ir import Precision
from repro.core.simulator.area import chip_area

WLS = ["resnet50_int8", "kan", "spec_decode"]


def test_genome_decode_valid_chips(rng):
    for g in random_genomes(rng, 64):
        chip = decode(g)
        assert 1 <= len(chip.tiles) <= 3
        assert chip.num_tiles >= 1


def test_family_constraints(rng):
    homo = decode(random_genomes(rng, 1, family="homo")[0])
    assert len(homo.tiles) == 1
    t = homo.tiles[0][0]
    assert t.precisions == frozenset({Precision.INT8, Precision.FP16})
    assert t.sfu_mask == 0
    bls = decode(random_genomes(rng, 1, family="hetero_bls")[0])
    assert len(bls.tiles) == 3
    assert bls.tiles[2][0].sfu_mask > 0
    assert bls.tiles[2][0].is_special


def test_bracket_sampling(rng):
    def area_fn(g):
        return chip_area(decode(g))

    for b in (100.0, 200.0):
        gs = sample_in_bracket(rng, 8, "hetero_bl", b, area_fn)
        areas = [area_fn(g) for g in gs]
        assert all(a <= b for a in areas)
        assert np.mean([b / 2 < a <= b for a in areas]) >= 0.5


def test_area_bracket_assignment():
    assert area_bracket(30) == 50.0
    assert area_bracket(199) == 200.0
    assert area_bracket(1000) == 800.0


def test_pareto_properties(rng):
    pts = rng.random((64, 3))
    mask = pareto_mask(pts)
    assert mask.any()
    front = pts[mask]
    # no front point dominates another
    for i in range(len(front)):
        for j in range(len(front)):
            if i != j:
                assert not (np.all(front[i] <= front[j])
                            and np.any(front[i] < front[j]))
    # every dominated point is dominated by some front point
    dominated = pts[~mask]
    for d in dominated:
        assert np.any(np.all(front <= d, axis=1) & np.any(front < d, axis=1))


@pytest.mark.slow
def test_sweep_and_ga_smoke():
    sw = run_sweep(WLS, samples_per_stratum=8, seed=0,
                   brackets=(100.0, 200.0))
    assert sw.genomes.shape[0] == 8 * 2 * 3
    fit = sw.fitness()
    assert np.isfinite(fit).sum() > len(fit) * 0.5
    base = sw.homo_baseline()
    assert 200.0 in base
    ga = run_ga(sw, 200.0, GAConfig(population=12, generations=2,
                                    seed_top_k=8, early_stop=2))
    assert ga is not None
    assert np.isfinite(ga.best_fitness)
    assert ga.evaluated >= 24


def test_pareto_duplicate_rows_keep_first():
    """Bitwise-identical rows are mutually non-dominating, so without a
    dedupe every copy survived — cumulative fronts (streamed service
    updates, the pipeline's cross-seed merge) grew with each repeated
    candidate.  Only the FIRST copy may survive."""
    pts = np.array([[1.0, 2.0], [1.0, 2.0], [3.0, 1.0],
                    [1.0, 2.0], [3.0, 1.0]])
    mask = pareto_mask(pts)
    assert mask.tolist() == [True, False, True, False, False]
    # idempotence: feeding a front back in keeps exactly that front
    assert pareto_mask(pts[mask]).all()
    # dominated duplicates stay dominated
    pts2 = np.array([[0.5, 0.5], [9.0, 9.0], [9.0, 9.0]])
    assert pareto_mask(pts2).tolist() == [True, False, False]
    # front ordering survives the dedupe
    assert pareto_front(pts).tolist() == [0, 2]


def test_pareto_mask_device_matches_host(rng):
    from repro.core.dse.pareto import pareto_mask_device

    pts = rng.random((48, 3))
    dup = np.concatenate([pts, pts[::3], pts[:5]])   # inject duplicates
    host = pareto_mask(dup)
    dev = np.asarray(pareto_mask_device(dup))
    assert np.array_equal(host, dev)
    assert np.array_equal(pareto_mask(np.zeros((0, 3))),
                          np.asarray(pareto_mask_device(np.zeros((0, 3)))))


# =============================================================================
# topology genes (mesh/torus, grid aspect, NoC width, DRAM channels; PR 9)
# =============================================================================

def test_topology_gene_roundtrip():
    """Every value of each interconnect gene decodes to the matching
    ChipConfig field, and the host decode agrees with the vectorized
    ``genomes_to_configs`` chip arrays gene-for-gene."""
    from repro.core.arch import KNOB_GRID
    from repro.core.dse.encoding import (IDX_ASPECT, IDX_DRAM_CH,
                                         IDX_NOC_BPC, IDX_TOPO)
    from repro.core.dse.engine import genomes_to_configs
    from repro.core.simulator.costs import grid_dims

    rng = np.random.default_rng(3)
    g = random_genomes(rng, 48)
    g[:, IDX_TOPO] = np.arange(48) % 2
    g[:, IDX_ASPECT] = np.arange(48) % 3
    g[:, IDX_NOC_BPC] = np.arange(48) % 4
    g[:, IDX_DRAM_CH] = np.arange(48) % 4
    cfgs = genomes_to_configs(g)
    chip_f = cfgs["chip"]
    for i in range(48):
        chip = decode(g[i])
        assert chip.torus == bool(KNOB_GRID["noc_topology"][i % 2])
        assert chip.grid_aspect == KNOB_GRID["grid_aspect"][i % 3]
        assert chip.noc_bytes_per_cycle == KNOB_GRID["noc_bpc"][i % 4]
        assert chip.dram_channels == KNOB_GRID["dram_channels"][i % 4]
        assert float(chip_f["torus"][i]) == float(chip.torus)
        assert float(chip_f["noc_bpc"][i]) == chip.noc_bytes_per_cycle
        assert float(chip_f["dram_channels"][i]) == chip.dram_channels
        gw, gh = grid_dims(np, float(chip.num_tiles), chip.grid_aspect)
        assert float(chip_f["grid_w"][i]) == float(gw)
        assert float(chip_f["grid_h"][i]) == float(gh)
        # area includes the NoC-width/torus scale + DRAM PHY term
        assert float(chip_f["chip_area"][i]) == chip_area(chip)


def test_sample_in_bracket_counts_area_evals():
    """``sweep.area_evals`` is the number of ``area_fn`` calls the
    sampler made, ``sweep.sampled`` the genomes it returned."""
    from repro.core import telemetry
    calls = []

    def area_fn(g):
        calls.append(1)
        return chip_area(decode(g))

    before = telemetry.snapshot()
    g = sample_in_bracket(np.random.default_rng(6), 12, "hetero_bls",
                          200.0, area_fn)
    c = telemetry.diff(telemetry.snapshot(), before)["counters"]
    assert c["sweep.area_evals"] == len(calls) >= len(g)
    assert c["sweep.sampled"] == len(g) == 12


@pytest.mark.parametrize("seed,evals,digest", [
    (1, 6697, "7087ecc55e332079"),
    (2, 6996, "f83791380f35d0f9"),
    (3, 6980, "75255cf12daa868d")])
def test_sample_in_bracket_stream_pinned_by_area_gather(seed, evals, digest):
    """The table gather drives the sampler through the very genomes the
    reference ``decode`` + ``chip_area`` does: every stratum returns the
    same int32 rows after the same number of area evaluations.  The
    count and the rows' digest pin the stream itself, as the sampler
    drew it when it repaired int32 rows with ``decode`` + ``chip_area``
    on every step."""
    import hashlib
    from repro.core import telemetry
    from repro.core.dse.engine import genome_area_fn

    def sample(area_fn):
        rng = np.random.default_rng(seed)
        before = telemetry.snapshot()
        out = [sample_in_bracket(rng, 8, fam, b, area_fn)
               for fam in FAMILIES for b in AREA_BRACKETS]
        c = telemetry.diff(telemetry.snapshot(), before)["counters"]
        return out, c["sweep.area_evals"]

    fast, fast_evals = sample(genome_area_fn())
    ref, ref_evals = sample(lambda g: chip_area(decode(g)))
    assert fast_evals == ref_evals == evals
    for a, b in zip(fast, ref):
        assert a.dtype == b.dtype == np.int32
        assert np.array_equal(a, b)
    rows = np.concatenate(fast)
    assert rows.shape == (8 * len(FAMILIES) * len(AREA_BRACKETS), GENOME_LEN)
    assert hashlib.sha256(rows.tobytes()).hexdigest()[:16] == digest


def test_run_sweep_genomes_unchanged_by_area_gather(monkeypatch):
    """``run_sweep`` samples the genomes the reference area path would."""
    from repro.core.dse import sweep
    from repro.core.dse.engine import EvalEngine
    eng = EvalEngine(["kan"])
    kw = dict(samples_per_stratum=2, seed=7, brackets=(100.0, 200.0),
              engine=eng)
    fast = run_sweep(["kan"], **kw)
    monkeypatch.setattr(sweep, "genome_area_fn",
                        lambda calib: lambda g: chip_area(decode(g), calib))
    ref = run_sweep(["kan"], **kw)
    assert fast.genomes.dtype == ref.genomes.dtype
    assert np.array_equal(fast.genomes, ref.genomes)
    assert np.array_equal(fast.area, ref.area)


def test_homo_family_pins_interconnect_genes():
    """The §4.3 homogeneous baseline stays on the stock interconnect: its
    stratum pins the topology genes to the mesh/64B/1-channel defaults,
    so the iso-area comparison never credits the baseline with a torus."""
    from repro.core.dse.encoding import INTERCONNECT_GENE_DEFAULTS
    area_fn = lambda g: chip_area(decode(g))
    rng = np.random.default_rng(4)
    g = sample_in_bracket(rng, 64, "homo", 200.0, area_fn)
    for idx, v in INTERCONNECT_GENE_DEFAULTS.items():
        assert np.all(g[:, idx] == v), idx
    # hetero strata do explore the genes
    gh = sample_in_bracket(rng, 256, "hetero_bls", 200.0, area_fn)
    from repro.core.dse.encoding import IDX_TOPO
    assert len(np.unique(gh[:, IDX_TOPO])) > 1


def test_canonicalization_preserves_interconnect_genes():
    """Interconnect genes are never don't-care on multi-type chips:
    canonicalization must not collapse two designs that differ only in
    topology (their metrics differ on the link tier)."""
    from repro.core.dse.encoding import IDX_TOPO
    from repro.core.dse.engine import canonical_genomes
    rng = np.random.default_rng(5)
    g = random_genomes(rng, 16)
    g2 = g.copy()
    g2[:, IDX_TOPO] = 1 - (g2[:, IDX_TOPO] % 2)
    c, c2 = canonical_genomes(g), canonical_genomes(g2)
    assert np.all(c[:, IDX_TOPO] != c2[:, IDX_TOPO])
    # and the genes survive canonicalization verbatim
    assert np.array_equal(c[:, IDX_TOPO], g[:, IDX_TOPO] % 2) or \
        np.array_equal(c[:, IDX_TOPO], g[:, IDX_TOPO])
