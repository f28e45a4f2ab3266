"""Device-side GA generation loop (paper §4.5) over the exact search path.

The Stage-2 refinement loop, with the genetics moved off the host: one
jitted ``jax.random``-keyed dispatch per generation runs tournament
selection (size ``cfg.tournament``), uniform crossover, Poisson-k gene
mutation and elitism over the whole ``(P, GENOME_LEN)`` population —
replacing the ~P Python tournament draws, per-child numpy crossover
/mutation, and per-generation host round trips of the historical loop
(``ga.run_ga(loop="host")``).  The same dispatch canonicalizes the
children (``canonical_genomes``, ported to jnp bit-for-bit), so the
engine's mode-keyed memo lookup costs no extra host pass: elites and
duplicate children are cache hits that skip the simulation scan
entirely.

Scoring goes through an ``EvalEngine`` — by default one constructed
with ``backend="exact"``, the class-specialized fused mapping+execution
scan (``compiler.batched_mapper.search_and_simulate``), so the Eq. 8
fitness the tournament selects on is computed from *exact*
(fused-mapper) metrics: search-time fitness equals a post-hoc
``rescore()`` bitwise, retiring the approximate-search-then-rescore
fidelity gap for GA refinement.  The Eq. 8 fitness itself (iso-area
savings vs the bracket's homogeneous baseline + the alpha TOPS/W
tie-break, with the area-bracket validity mask) is a jitted device
kernel over the (P, W) metric matrices.

Seeded runs are bitwise-deterministic: the genome stream is a
``jax.random`` fold of (seed, bracket), engine metrics are
batch-composition-independent (pinned by tests/test_engine.py), and two
same-seed runs produce identical ``best_genome``/``history``
(tests/test_ga_device.py).  With a sharded engine and a population
divisible by the mesh, the population axis of the genetics dispatch is
placed with the same ``NamedSharding`` as the evaluation batches
(``launch.mesh.population_sharding``).

The one *documented* departure from the host loop's numpy genetics: the
Poisson-k mutation draw is truncated at ``MUT_GENES_MAX`` (= 8) genes
per child (P[k > 8 | k ~ Poisson(2)] < 3e-4); the host loop keeps the
unbounded draw.  Both are the paper's operator — the two loops walk
different (equally valid) random streams either way.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Dict, Optional

import numpy as np

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp

from ..arch import MAX_TILE_TYPES, MAX_TILES
from ..calibrate.asap7 import CalibrationTable, DEFAULT_CALIB
from ..simulator.batched import CHIP_KEYS, TILE_KEYS
from ..simulator.costs import grid_dims
from ..simulator.orchestrator import CACHE_FRAC
from .api import EngineConfig
from .device_memo import (DeviceMemo, drain_to_store, memo_from_store,
                          memo_init, memo_insert, memo_lookup)
from .encoding import (FIELDS_PER_TILE, GENOME_LEN, IDX_ASPECT, IDX_DRAM,
                       IDX_DRAM_CH, IDX_ICONN, IDX_NOC_BPC, IDX_TOPO,
                       genome_bounds, random_genomes)
from .engine import (_ARRAY_DIM, _ASPECT, _ASYM, _ASYM_CANON, _ASYM_COL,
                     _COUNT, _DATAFLOW, _DB, _DRAM, _DRAM_CH, _ENGINE,
                     _FIELD_COL, _HOPS_TABLE, _MODE_KEYS, _NOC_BPC, _PIPE,
                     _PREC_COL, _PREC_MASK, _PREC_MAX, _SFU, _SFU_COL,
                     _SPARSITY, _SPECIAL_INERT_COLS, _SRAM_KB, _TOPO,
                     EvalEngine)
from .objective import ALPHA, AREA_BRACKETS, area_bracket

__all__ = ["run_ga_device", "run_ga_fused", "FusedRefinement",
           "MUT_GENES_MAX", "canonical_genomes_device", "fitness_device",
           "bracket_bounds"]

# Poisson-k mutation truncation of the device loop (see module docstring)
MUT_GENES_MAX = 8

_SFU_DEV = jnp.asarray(_SFU)
_ASYM_CANON_DEV = jnp.asarray(_ASYM_CANON, jnp.int32)


# =============================================================================
# device canonicalization (bitwise port of engine.canonical_genomes)
# =============================================================================

def _canonical_device(g):
    """jnp mirror of ``engine.canonical_genomes`` on a (P, GENOME_LEN)
    int array — same zeroing order, same tables, bit-for-bit (pinned by
    tests/test_ga_device.py)."""
    n_types = g[:, 0] + 1
    for t in range(MAX_TILE_TYPES):
        base = 1 + t * FIELDS_PER_TILE
        inactive = t >= n_types
        block = g[:, base:base + FIELDS_PER_TILE]
        g = g.at[:, base:base + FIELDS_PER_TILE].set(
            jnp.where(inactive[:, None], 0, block))
        special = (_SFU_DEV[g[:, base + _SFU_COL] % len(_SFU)] > 0) \
            & ~inactive
        for col in _SPECIAL_INERT_COLS:
            g = g.at[:, base + col].set(
                jnp.where(special, 0, g[:, base + col]))
        g = g.at[:, base + _ASYM_COL].set(
            _ASYM_CANON_DEV[g[:, base + _PREC_COL] % 4,
                            g[:, base + _ASYM_COL] % 4].astype(g.dtype))
    return g


@jax.jit
def _canonical_device_jit(g):
    return _canonical_device(g)


def canonical_genomes_device(genomes: np.ndarray) -> np.ndarray:
    """Host-callable wrapper over the jitted device canonicalizer."""
    g = np.asarray(genomes, np.int64).reshape(-1, GENOME_LEN)
    return np.asarray(_canonical_device_jit(jnp.asarray(g)))


# =============================================================================
# Eq. 8 fitness kernel
# =============================================================================

def bracket_bounds(bracket: float):
    """(lo, hi] area band equivalent to ``area_bracket(a) == bracket``
    (the last bracket is open above: oversized chips land in it)."""
    if bracket not in AREA_BRACKETS:
        return math.nan, math.nan      # no design can match (host parity)
    bi = AREA_BRACKETS.index(bracket)
    lo = AREA_BRACKETS[bi - 1] if bi > 0 else -math.inf
    hi = bracket if bi < len(AREA_BRACKETS) - 1 else math.inf
    return lo, hi


@jax.jit
def _fitness_kernel(en, tw, lat, area, e_homo, lo, hi, alpha):
    """Eq. 8 on the (P, W) metric matrices, on device: mean iso-area
    savings vs the bracket's homogeneous baseline + alpha * TOPS/W
    normalized over comparable (in-bracket, valid) designs only;
    invalid/out-of-bracket rows score -inf (``ga._fitness`` semantics)."""
    sav = (e_homo[None, :] - en) / jnp.maximum(e_homo[None, :], 1e-30)
    fit = sav.mean(axis=1)
    peak_tw = tw.max(axis=1)
    bad = ~jnp.isfinite(lat).all(axis=1) | ~(lat > 0).all(axis=1)
    bad = bad | ~((area > lo) & (area <= hi))
    ok = ~bad
    max_tw = jnp.max(jnp.where(ok, peak_tw, -jnp.inf))
    max_tw = jnp.where(jnp.any(ok), max_tw, 1.0)
    fit = fit + alpha * peak_tw / jnp.maximum(max_tw, 1e-30)
    return jnp.where(bad, -jnp.inf, fit)


def fitness_device(metrics: Dict[str, np.ndarray], e_homo: np.ndarray,
                   bracket: float, alpha: float = ALPHA) -> np.ndarray:
    """Eq. 8 fitness of an engine ``evaluate()``/``rescore()`` result
    through the device kernel — the scoring the device GA loop selects
    on (and what the exact-search/rescore parity property compares)."""
    lo, hi = bracket_bounds(bracket)
    return np.asarray(_fitness_kernel(
        jnp.asarray(metrics["energy"]), jnp.asarray(metrics["tops_w"]),
        jnp.asarray(metrics["latency"]), jnp.asarray(metrics["area"]),
        jnp.asarray(e_homo, jnp.float64), jnp.asarray(lo, jnp.float64),
        jnp.asarray(hi, jnp.float64), jnp.asarray(alpha, jnp.float64)))


# =============================================================================
# the jitted generation kernel
# =============================================================================

@functools.lru_cache(maxsize=32)
def _genetics_kernel(population: int, tournament: int, n_elite: int,
                     crossover_rate: float, mutation_rate: float):
    """One GA generation as a single jitted dispatch:
    ``(pop, fit, key) -> (children, canonical(children))``.

    Mirrors the host loop's operator semantics — elites pass through
    unchanged, each non-elite slot pair comes from two size-K
    tournaments, uniform crossover swaps genes with p=0.5 at
    ``crossover_rate``, and mutated children redraw Poisson-k genes
    uniformly under ``genome_bounds`` (k truncated at MUT_GENES_MAX on
    device) — over a different (jax.random) stream.
    """
    bounds = jnp.asarray(genome_bounds(), jnp.int32)
    L = GENOME_LEN
    n_pairs = max(-(-(population - n_elite) // 2), 0)
    n_children = n_pairs * 2

    def gen(pop, fit, key):
        pop = pop.astype(jnp.int32)
        k_t, k_cx, k_cxm, k_mut, k_mk, k_mg, k_mv = jax.random.split(key, 7)
        # ---- elitism -----------------------------------------------------
        elite_idx = jnp.argsort(-fit)[:n_elite]
        elites = pop[elite_idx]
        # ---- tournament selection (all draws in one dispatch) ------------
        idx = jax.random.randint(k_t, (n_children, tournament), 0, population)
        winners = idx[jnp.arange(n_children), jnp.argmax(fit[idx], axis=1)]
        pa = pop[winners[0::2]]
        pb = pop[winners[1::2]]
        # ---- uniform crossover ------------------------------------------
        do_cx = jax.random.uniform(k_cx, (n_pairs,)) < crossover_rate
        swap = do_cx[:, None] & (jax.random.uniform(k_cxm, (n_pairs, L)) < 0.5)
        ca = jnp.where(swap, pb, pa)
        cb = jnp.where(swap, pa, pb)
        children = jnp.stack([ca, cb], axis=1).reshape(n_children, L)
        # ---- Poisson-k gene mutation ------------------------------------
        do_mut = jax.random.uniform(k_mut, (n_children,)) < mutation_rate
        k_genes = jnp.clip(jax.random.poisson(k_mk, 2.0, (n_children,)),
                           1, MUT_GENES_MAX)
        genes = jax.random.randint(k_mg, (n_children, MUT_GENES_MAX), 0, L)
        vals = jnp.floor(jax.random.uniform(k_mv, (n_children, MUT_GENES_MAX))
                         * bounds[genes]).astype(jnp.int32)

        def mutate(child, do, kk, gg, vv):
            # sequential application: later draws overwrite earlier ones
            # on duplicate gene indices, like the host fancy assignment
            def body(j, ch):
                return jnp.where(do & (j < kk), ch.at[gg[j]].set(vv[j]), ch)
            return jax.lax.fori_loop(0, MUT_GENES_MAX, body, child)

        children = jax.vmap(mutate)(children, do_mut, k_genes, genes, vals)
        new_pop = jnp.concatenate([elites, children])[:population]
        return new_pop, _canonical_device(new_pop)

    return jax.jit(gen)


# =============================================================================
# the generation loop
# =============================================================================

def run_ga_device(sweep, bracket: float, cfg=None, seed: int = 0,
                  calib: CalibrationTable = DEFAULT_CALIB,
                  verbose: bool = False, engine: Optional[EvalEngine] = None,
                  prefilter: bool = True,
                  on_generation: Optional[Callable] = None):
    """GA refinement at one area budget on the device generation loop.

    Same contract as ``ga.run_ga`` (which delegates here by default):
    seeded from the sweep's top-k at the bracket, returns a ``GAResult``
    or None when the bracket has no homogeneous baseline.  Without an
    explicit ``engine``, scoring runs the exact search backend — one
    class-specialized fused map+execute dispatch per workload per
    generation, memo hits (elites, duplicate children) and
    bracket-prefiltered genomes skipping the scan.  ``engine`` may be
    any object with the engine scoring surface — e.g. the evaluation
    service's ``DSEClient``, which coalesces this loop's populations
    with other tenants' candidates.  ``on_generation(gen, pop, fit,
    metrics)`` is invoked after every scored population (gen 0 = the
    seed population) — the hook the service streams Pareto-front
    updates from.
    """
    from .ga import GAConfig, GAResult
    cfg = cfg or GAConfig()
    engine = (engine.check_workloads(sweep.workloads, calib)
              if engine is not None
              else EvalEngine(sweep.workloads, calib,
                              config=EngineConfig(backend="exact",
                                                  nonfinite="skip")))
    rng = np.random.default_rng(seed + int(bracket))
    base = sweep.homo_baseline()
    if bracket not in base:
        return None
    e_homo = np.asarray(base[bracket], np.float64)
    lo, hi = bracket_bounds(bracket)

    # ---- seed population: identical to the host loop -----------------------
    fit_sweep = sweep.fitness(cfg.alpha)
    in_b = np.nonzero((sweep.bracket == bracket) & np.isfinite(fit_sweep))[0]
    order = in_b[np.argsort(-fit_sweep[in_b])][:cfg.seed_top_k]
    pop = sweep.genomes[order].copy()[:cfg.population]
    while len(pop) < cfg.population:
        fill = random_genomes(rng, cfg.population - len(pop),
                              family="hetero_bls" if rng.random() < 0.5
                              else None)
        pop = np.concatenate([pop, fill])[:cfg.population]
    pop = np.ascontiguousarray(pop, np.int32)

    def keep(areas: np.ndarray) -> np.ndarray:
        # vectorized `area_bracket(a) == bracket` (bracket_bounds parity
        # is pinned by tests/test_ga_device.py)
        return (areas > lo) & (areas <= hi)

    def evaluate(genomes: np.ndarray, canonical=None):
        m = engine.evaluate(genomes, keep=keep if prefilter else None,
                            canonical=canonical)
        m.pop("meta", None)  # best_metrics holds per-genome arrays only
        fit = fitness_device(m, e_homo, bracket, cfg.alpha)
        return fit, m

    # per-generation miss counts sweep the whole bucket range: register
    # the shapes up front so every dispatch is minimally padded
    engine.reserve_shapes(cfg.population)
    fit, metrics = evaluate(pop)
    if on_generation is not None:
        on_generation(0, pop, fit, metrics)
    best_i = int(np.argmax(fit))
    best = (fit[best_i], pop[best_i].copy(),
            {k: v[best_i] for k, v in metrics.items()})
    history = [float(best[0])]
    evaluated = len(pop)
    stall = 0

    n_elite = max(int(cfg.elitism * cfg.population), 1)
    gen_fn = _genetics_kernel(cfg.population, cfg.tournament, n_elite,
                              cfg.crossover_rate, cfg.mutation_rate)
    key = jax.random.PRNGKey(seed + int(bracket))
    sharding = None
    if engine._sharding is not None:
        from ...launch.mesh import population_sharding
        sharding = population_sharding(cfg.population)
    pop_dev = jnp.asarray(pop, jnp.int32)
    if sharding is not None:
        pop_dev = jax.device_put(pop_dev, sharding)

    for gen in range(cfg.generations):
        key, sub = jax.random.split(key)
        pop_dev, canon_dev = gen_fn(pop_dev, jnp.asarray(fit), sub)
        # ONE host transfer per generation: the (P, GENOME_LEN) children
        # + their canonical forms (the engine's memo keys)
        pop = np.asarray(pop_dev)
        canon = np.asarray(canon_dev)
        fit, metrics = evaluate(pop, canonical=canon)
        if on_generation is not None:
            on_generation(gen + 1, pop, fit, metrics)
        evaluated += len(pop)
        gi = int(np.argmax(fit))
        if fit[gi] > best[0]:
            best = (fit[gi], pop[gi].copy(),
                    {k: v[gi] for k, v in metrics.items()})
            stall = 0
        else:
            stall += 1
        history.append(float(best[0]))
        if verbose:
            print(f"[ga-dev {bracket:.0f}mm2] gen {gen}: best={best[0]:+.4f} "
                  f"(stall {stall})")
        if stall >= cfg.early_stop:
            break

    sav = (e_homo - best[2]["energy"]) / np.maximum(e_homo, 1e-30)
    return GAResult(bracket=bracket, best_genome=best[1],
                    best_fitness=float(best[0]), best_savings_per_wl=sav,
                    best_metrics=best[2], history=history, evaluated=evaluated)


# =============================================================================
# device genome -> config stacking (bitwise port of genomes_to_configs)
# =============================================================================

_ARRAY_DIM_DEV = jnp.asarray(_ARRAY_DIM)
_SRAM_KB_DEV = jnp.asarray(_SRAM_KB)
_COUNT_DEV = jnp.asarray(_COUNT, jnp.int32)
_ENGINE_DEV = jnp.asarray(_ENGINE)
_SPARSITY_DEV = jnp.asarray(_SPARSITY)
_DATAFLOW_DEV = jnp.asarray(_DATAFLOW)
_PIPE_DEV = jnp.asarray(_PIPE)
_DB_DEV = jnp.asarray(_DB)
_ASYM_DEV = jnp.asarray(_ASYM)
_PREC_MASK_DEV = jnp.asarray(_PREC_MASK)
_PREC_MAX_DEV = jnp.asarray(_PREC_MAX, jnp.int32)
_DRAM_DEV = jnp.asarray(_DRAM)
_HOPS_TABLE_DEV = jnp.asarray(_HOPS_TABLE)
_TOPO_DEV = jnp.asarray(_TOPO)
_NOC_BPC_DEV = jnp.asarray(_NOC_BPC)
_DRAM_CH_DEV = jnp.asarray(_DRAM_CH)


def _grid_table():
    """(grid_w, grid_h) for every tile count 0..MAX_TILES and aspect
    knob, computed by the host's ``grid_dims`` (as ``genomes_to_configs``
    does) for the device builder to gather."""
    n = np.arange(MAX_TILES + 1, dtype=np.float64)[:, None]
    return tuple(jnp.asarray(t) for t in grid_dims(np, n, _ASPECT[None, :]))


def _area_tables(calib: CalibrationTable):
    """Device views of the cached host tables.  Converted per call so the
    constants belong to whichever trace consumes them — caching the
    ``jnp`` arrays themselves would capture trace-local tracers whenever
    the first call happens inside a jit trace, poisoning every later
    retrace (a second kernel shape in the same process) with an
    UnexpectedTracerError."""
    return tuple(jnp.asarray(t) for t in _area_tables_host(calib))


@functools.lru_cache(maxsize=4)
def _area_tables_host(calib: CalibrationTable):
    """Host-precomputed Eq. 7 area tables over the full (discrete) knob
    grid: per-type tile area, tile area x count, and NoC area by tile
    count.  XLA:CPU contracts mul+add chains into FMAs under jit — no
    flag or ``optimization_barrier`` prevents it — which skips the host
    stack's per-product rounding and breaks this port's bitwise-parity
    contract.  So the device does NO area arithmetic: every area value
    is a gather from these tables, each entry computed by the exact
    numpy expressions ``engine._per_type_values`` runs (identical
    rounding by construction).  Grid: prec(4) x engine(4) x sparsity(3)
    x rows(5) x cols(5) x sfu(len _SFU) x sram(7) = 42 K entries."""
    S = len(_SFU)
    p_, e_, s_, r_, c_, f_, k_ = np.meshgrid(
        np.arange(4), np.arange(4), np.arange(3), np.arange(5),
        np.arange(5), np.arange(S), np.arange(7), indexing="ij")
    sfu = _SFU[f_]
    special = sfu > 0
    rows = np.where(special, 0.0, _ARRAY_DIM[r_])
    cols = np.where(special, 0.0, _ARRAY_DIM[c_])
    num_macs = rows * cols
    big = num_macs >= 1024.0
    dsp_count = np.where(special, 1.0, np.where(big, 2.0, 1.0))
    dsp_simd = np.full_like(dsp_count, 64.0)
    max_prec = _PREC_MAX[p_]
    eng_idx = np.asarray(_ENGINE[e_], np.int64)
    sp_idx = np.asarray(_SPARSITY[s_], np.int64)
    sram_kb = _SRAM_KB[k_]

    a_mac_mm2 = np.asarray(calib.a_mac_mm2, np.float64)
    eng_a = np.asarray(calib.engine_a_mult, np.float64)
    sp_a = np.asarray(calib.sparsity_a_mult, np.float64)
    a_mac_unit = a_mac_mm2[max_prec] * eng_a[eng_idx]
    a_mac = num_macs * a_mac_unit * sp_a[sp_idx]
    a_sram = sram_kb * calib.a_sram_mm2_per_kb
    a_dsp = dsp_count * dsp_simd * calib.a_dsp_mm2_per_lane
    sfu_i = np.asarray(sfu, np.int64)
    a_spec = np.where(sfu_i & 1, calib.a_fft_mm2, 0.0)
    a_spec = a_spec + np.where(sfu_i & 2, calib.a_lif_mm2, 0.0)
    a_spec = a_spec + np.where(sfu_i & 4, calib.a_poly_mm2, 0.0)
    a_ports = calib.a_ports_base_mm2 \
        + (rows + cols) * calib.a_ports_per_lane_mm2
    area = a_mac + a_sram + a_dsp + a_spec + a_ports

    count_terms = area[..., None] * _COUNT        # x count, pre-rounded
    max_tiles = MAX_TILE_TYPES * int(np.max(_COUNT))
    # NoC term by (tile count, noc_bpc knob, torus knob): the host stack
    # computes ``(num_tiles * a_noc) * noc_scale`` left-associatively —
    # precompute every product here so the device gathers a finished
    # float64 (the same FMA-contraction hazard as the tile terms)
    n_tiles = np.arange(max_tiles + 1, dtype=np.float64)
    noc_scale = (0.5 + 0.5 * _NOC_BPC / 64.0)[:, None] \
        * np.where(_TOPO[None, :] > 0, 1.25, 1.0)
    noc = (n_tiles * calib.a_noc_mm2_per_tile)[:, None, None] \
        * noc_scale[None, :, :]
    # per-channel DRAM PHY term by the dram_channels knob
    dram_phy = (_DRAM_CH - 1.0) * calib.a_dram_phy_mm2
    return (np.ascontiguousarray(area.reshape(-1)),
            np.ascontiguousarray(count_terms.reshape(-1, len(_COUNT))),
            np.ascontiguousarray(noc),
            np.ascontiguousarray(dram_phy))


def _chip_area_device(g, calib: CalibrationTable):
    """(P,) chip areas only — what the Eq. 8 fitness band consumes —
    through the same ``_area_tables`` gathers ``_configs_device`` runs
    (bitwise identical by construction).  Split out so the fused loop's
    all-hit generations (every child memoized) pay a handful of gathers
    instead of full config building.  Traceable inside jit."""
    g = g.astype(jnp.int32)      # int64 is emulated on the TPU
    B = g.shape[0]
    T = MAX_TILE_TYPES

    def tcol(t, f):
        return g[:, 1 + t * FIELDS_PER_TILE + _FIELD_COL[f]]

    area_tab, count_tab, noc_tab, dram_tab = _area_tables(calib)
    sfu_idx = jnp.stack([tcol(t, "sfu") % len(_SFU) for t in range(T)],
                        axis=1)
    prec_idx = jnp.stack([tcol(t, "prec") % 4 for t in range(T)], axis=1)
    eng_k = jnp.stack([tcol(t, "engine") % 4 for t in range(T)], axis=1)
    sp_k = jnp.stack([tcol(t, "sparsity") % 3 for t in range(T)], axis=1)
    rows_k = jnp.stack([tcol(t, "rows") % 5 for t in range(T)], axis=1)
    cols_k = jnp.stack([tcol(t, "cols") % 5 for t in range(T)], axis=1)
    sram_k = jnp.stack([tcol(t, "sram") % 7 for t in range(T)], axis=1)
    flat = (((prec_idx * 4 + eng_k) * 3 + sp_k) * 5 + rows_k) * 5 + cols_k
    flat = (flat * len(_SFU) + sfu_idx) * 7 + sram_k

    counts = jnp.stack([_COUNT_DEV[tcol(t, "count") % 8] for t in range(T)],
                       axis=1)
    n_types = (g[:, 0] + 1)[:, None]
    active = jnp.arange(T, dtype=jnp.int32)[None, :] < n_types
    counts = jnp.where(active, counts, 0)
    num_tiles = counts.sum(axis=1)

    cnt_k = jnp.stack([tcol(t, "count") % len(_COUNT) for t in range(T)],
                      axis=1)
    terms = jnp.where(active, count_tab[flat, cnt_k], 0.0)
    area = jnp.zeros(B)
    for t in range(T):
        area = area + terms[:, t]
    area = area + noc_tab[num_tiles,
                          g[:, IDX_NOC_BPC] % 4, g[:, IDX_TOPO] % 2]
    return area + dram_tab[g[:, IDX_DRAM_CH] % 4]


def _configs_device(g, calib: CalibrationTable):
    """jnp mirror of ``engine.genomes_to_configs`` on a (P, GENOME_LEN)
    int array: same knob tables, same modulo wrapping, same Eq. 7 term
    order, same *sequential* peak-TOPS/chip-area accumulation — so the
    (tile, chip) stacks and areas are bit-for-bit the host stack that
    ``place_configs`` would ship (pinned by tests/test_pipeline.py).
    Returns ``(tile, chip, chip_area)``: the search kernel's two config
    dicts (f64, exactly TILE_KEYS/CHIP_KEYS) plus the (P,) areas the
    fitness band needs.  Traceable inside jit."""
    g = g.astype(jnp.int32)      # int64 is emulated on the TPU
    B = g.shape[0]
    T = MAX_TILE_TYPES

    def tcol(t, f):
        return g[:, 1 + t * FIELDS_PER_TILE + _FIELD_COL[f]]

    v: Dict[str, jnp.ndarray] = {}
    sfu_idx = jnp.stack([tcol(t, "sfu") % len(_SFU) for t in range(T)],
                        axis=1)
    sfu = _SFU_DEV[sfu_idx]
    special = sfu > 0
    rows = jnp.stack([_ARRAY_DIM_DEV[tcol(t, "rows") % 5] for t in range(T)],
                     axis=1)
    cols = jnp.stack([_ARRAY_DIM_DEV[tcol(t, "cols") % 5] for t in range(T)],
                     axis=1)
    rows = jnp.where(special, 0.0, rows)
    cols = jnp.where(special, 0.0, cols)
    big = rows * cols >= 1024.0
    v["rows"], v["cols"] = rows, cols
    v["num_macs"] = rows * cols
    clock_mhz = jnp.where(special, 800.0, jnp.where(big, 1200.0, 500.0))
    v["dsp_count"] = jnp.where(special, 1.0, jnp.where(big, 2.0, 1.0))
    dsp_simd = jnp.full((B, T), 64.0)
    v["sfu_mask"] = sfu
    v["sfu_parallel"] = jnp.full((B, T), 16.0)
    v["sram_bpc"] = jnp.full((B, T), 8 * 16.0)   # default sram_banks=8

    v["engine"] = jnp.stack([_ENGINE_DEV[tcol(t, "engine") % 4]
                             for t in range(T)], axis=1)
    prec_idx = jnp.stack([tcol(t, "prec") % 4 for t in range(T)], axis=1)
    v["prec_mask"] = _PREC_MASK_DEV[prec_idx]
    max_prec = _PREC_MAX_DEV[prec_idx]
    v["max_prec"] = max_prec.astype(jnp.float64)
    v["sparsity"] = jnp.stack([_SPARSITY_DEV[tcol(t, "sparsity") % 3]
                               for t in range(T)], axis=1)
    v["dataflow"] = jnp.stack([_DATAFLOW_DEV[tcol(t, "dataflow") % 3]
                               for t in range(T)], axis=1)
    v["sram_kb"] = jnp.stack([_SRAM_KB_DEV[tcol(t, "sram") % 7]
                              for t in range(T)], axis=1)
    v["double_buffer"] = jnp.stack([_DB_DEV[tcol(t, "db") % 2]
                                    for t in range(T)], axis=1)
    v["pipeline_depth"] = jnp.stack([_PIPE_DEV[tcol(t, "pipe") % 4]
                                     for t in range(T)], axis=1)
    v["asym_mac"] = jnp.stack([_ASYM_DEV[tcol(t, "asym") % 4]
                               for t in range(T)], axis=1)
    v["cache_cap"] = v["sram_kb"] * 1024.0 * CACHE_FRAC
    v["dsp_lanes"] = v["dsp_count"] * dsp_simd
    v["clock_hz"] = clock_mhz * 1e6

    # tile_area (Eq. 7) as a pure gather from the host-precomputed knob
    # grid (see _area_tables for why no area arithmetic may run on device)
    area_tab, count_tab, noc_tab, dram_tab = _area_tables(calib)
    eng_k = jnp.stack([tcol(t, "engine") % 4 for t in range(T)], axis=1)
    sp_k = jnp.stack([tcol(t, "sparsity") % 3 for t in range(T)], axis=1)
    rows_k = jnp.stack([tcol(t, "rows") % 5 for t in range(T)], axis=1)
    cols_k = jnp.stack([tcol(t, "cols") % 5 for t in range(T)], axis=1)
    sram_k = jnp.stack([tcol(t, "sram") % 7 for t in range(T)], axis=1)
    flat = (((prec_idx * 4 + eng_k) * 3 + sp_k) * 5 + rows_k) * 5 + cols_k
    flat = (flat * len(_SFU) + sfu_idx) * 7 + sram_k
    v["area_mm2"] = area_tab[flat]

    counts = jnp.stack([_COUNT_DEV[tcol(t, "count") % 8] for t in range(T)],
                       axis=1)
    n_types = (g[:, 0] + 1)[:, None]
    counts = jnp.where(jnp.arange(T, dtype=jnp.int32)[None, :] < n_types,
                       counts, 0)

    starts = jnp.concatenate(
        [jnp.zeros((B, 1), counts.dtype),
         jnp.cumsum(counts, axis=1)[:, :-1]], axis=1)
    ends = starts + counts
    slots = jnp.arange(MAX_TILES, dtype=jnp.int32)
    member = (slots[None, None, :] >= starts[:, :, None]) \
        & (slots[None, None, :] < ends[:, :, None])

    tile = {}
    for f in ("num_macs", "rows", "cols", "engine", "prec_mask", "asym_mac",
              "sparsity", "dataflow", "sram_kb", "dsp_lanes", "dsp_count",
              "sfu_mask", "sfu_parallel", "double_buffer", "pipeline_depth",
              "clock_hz", "cache_cap", "sram_bpc", "area_mm2", "max_prec"):
        tile[f] = jnp.sum(jnp.where(member, v[f][:, :, None], 0.0), axis=1)
    tile["exists"] = member.any(axis=1).astype(jnp.float64)

    num_tiles = counts.sum(axis=1)
    # grid dims as a gather too: round(sqrt(n) * aspect) meets exact .5
    # ties (n = 9, aspect 0.5) that the TPU's float64 sqrt misses
    grid = _grid_table()
    gw = grid[0][num_tiles, g[:, IDX_ASPECT] % 3]
    gh = grid[1][num_tiles, g[:, IDX_ASPECT] % 3]
    chip = {
        "dram_gbps": _DRAM_DEV[g[:, IDX_DRAM] % 6],
        "hops": _HOPS_TABLE_DEV[g[:, IDX_ICONN] % 4, num_tiles],
        "noc_bpc": _NOC_BPC_DEV[g[:, IDX_NOC_BPC] % 4],
        "noc_base_cycles": jnp.full(B, 8.0),
        "ref_clock_hz": jnp.full(B, 1000 * 1e6),
        "torus": _TOPO_DEV[g[:, IDX_TOPO] % 2],
        "dram_channels": _DRAM_CH_DEV[g[:, IDX_DRAM_CH] % 4],
        "grid_w": gw,
        "grid_h": gh,
    }
    assert set(tile) == set(TILE_KEYS) and set(chip) == set(CHIP_KEYS)

    # chip_area: per-type sequential sum in type order + NoC (host order),
    # every term a gather from the pre-rounded area x count / NoC / DRAM
    # PHY tables
    cnt_k = jnp.stack([tcol(t, "count") % len(_COUNT) for t in range(T)],
                      axis=1)
    active = jnp.arange(T, dtype=jnp.int32)[None, :] < n_types
    terms = jnp.where(active, count_tab[flat, cnt_k], 0.0)
    area = jnp.zeros(B)
    for t in range(T):
        area = area + terms[:, t]
    area = area + noc_tab[num_tiles,
                          g[:, IDX_NOC_BPC] % 4, g[:, IDX_TOPO] % 2]
    area = area + dram_tab[g[:, IDX_DRAM_CH] % 4]
    return tile, chip, area


# =============================================================================
# the fused refinement: whole GA run (island model) as ONE dispatch
# =============================================================================

@dataclasses.dataclass
class FusedRefinement:
    """``run_ga_fused`` output: the ``GAResult`` plus what the pipeline's
    cross-seed Pareto merge and seed-boundary store sync consume — the
    device memo state and the final scored population (which always
    contains the best-ever genome: elitism carries it forward)."""

    result: "GAResult"               # noqa: F821 — ga.GAResult
    memo: DeviceMemo
    population: np.ndarray           # (P, GENOME_LEN) final genomes
    pop_metrics: Dict[str, np.ndarray]   # latency/energy/tops_w (P, W), area (P,)
    generations_run: int


@functools.lru_cache(maxsize=16)
def _refine_kernel(calib: CalibrationTable, n_state: int, mode: str,
                   population: int, islands: int, generations: int,
                   tournament: int, n_elite: int, crossover_rate: float,
                   mutation_rate: float, early_stop: int,
                   migrate_every: int, migrate_k: int,
                   fidelity: str = "aggregate"):
    """The whole Stage-2 refinement as ONE jitted dispatch: a
    ``lax.while_loop`` over generations whose body runs ring migration
    (islands > 1), the genetics kernel, canonicalization, the
    device-memo probe, the fused exact search scan (skipped entirely via
    ``lax.cond`` when every row hits), the memo insert, and the Eq. 8
    fitness + best/stall tracking — no host round trip anywhere inside.

    With ``islands == 1`` the generation body is exactly the host-memo
    device loop's: same ``_genetics_kernel`` instance, same key-split
    sequence, memo hits bitwise inert — which is what makes a seeded
    single-island run genome-for-genome equal to ``run_ga_device``
    (pinned by tests/test_pipeline.py).  With ``islands > 1`` the
    population is logically (islands, P/islands) — per-island
    tournaments/elites over per-island key streams, and every
    ``migrate_every`` generations each island's top ``migrate_k`` rows
    replace the next island's worst via ``jnp.roll`` over the island
    axis (a collective permute when that axis is sharded — see
    ``launch.mesh.island_sharding``).  Migrant fitness rows travel with
    the genomes, so migration costs no rescoring.
    """
    from ..compiler.batched_mapper import _jitted_search_population

    P, I = population, islands
    Pi = P // I
    L = GENOME_LEN
    lkey, ekey, akey = _MODE_KEYS[mode]
    gen_fn = _genetics_kernel(Pi, tournament, n_elite, crossover_rate,
                              mutation_rate)
    search_fn = _jitted_search_population(calib, n_state, True, fidelity)

    def score(pop, canon, memo, e_homo, lo, hi, alpha, ops, tms):
        # areas only (cheap gathers, bitwise _configs_device's) — full
        # config building happens inside the miss branch, so an all-hit
        # generation skips it along with the scan
        area = _chip_area_device(pop, calib)
        hit, mv = memo_lookup(memo, canon)

        def cached(_):
            return mv[:, 0], mv[:, 1], mv[:, 2]

        def fresh(_):
            tile, chip, _ = _configs_device(pop, calib)
            outs = search_fn(tile, chip, *ops, tms)
            l, e, a, ok = (outs[k] for k in (lkey, ekey, akey, "ok"))  # (P, W)
            power = e * 1e-12 / jnp.maximum(l, 1e-30)
            t = a / jnp.maximum(power, 1e-30)
            # unmappable rows: inf latency/energy, zero TOPS/W (the
            # engine's exact-path masking, elementwise identical).  A
            # NaN cell (cost-model corruption) is masked the same way —
            # the device memo must never cache a non-finite row, and the
            # host engine would have scored it skip/-inf too.  No NaN
            # ever arises from a healthy cost model, so the extra mask
            # is bitwise inert on clean runs.
            okk = ok & ~(jnp.isnan(l) | jnp.isnan(e)
                         | jnp.isnan(t) | jnp.isinf(t))
            lat = jnp.where(okk, l, jnp.inf)
            en = jnp.where(okk, e, jnp.inf)
            tw = jnp.where(okk, t, 0.0)
            # hit rows take their memo values — numerically a no-op
            # (metrics are bitwise reproducible) but keeps the two cond
            # branches the same function of the memo state
            return (jnp.where(hit[:, None], mv[:, 0], lat),
                    jnp.where(hit[:, None], mv[:, 1], en),
                    jnp.where(hit[:, None], mv[:, 2], tw))

        # warm replay: a generation whose every child is memoized skips
        # the search scan wholesale
        lat, en, tw = jax.lax.cond(jnp.all(hit), cached, fresh, None)
        memo = memo_insert(memo, canon, jnp.stack([lat, en, tw], axis=1),
                           update=~hit)
        fit = _fitness_kernel(en, tw, lat, area, e_homo, lo, hi, alpha)
        return fit, lat, en, tw, area, memo

    def migrate(popI, fitI):
        order = jnp.argsort(-fitI, axis=1)             # best first
        top = order[:, :migrate_k]
        worst = order[:, Pi - migrate_k:]
        mig_g = jnp.take_along_axis(popI, top[:, :, None], axis=1)
        mig_f = jnp.take_along_axis(fitI, top, axis=1)
        mig_g = jnp.roll(mig_g, 1, axis=0)             # ring: i <- i-1
        mig_f = jnp.roll(mig_f, 1, axis=0)
        ii = jnp.arange(I)[:, None]
        return (popI.at[ii, worst].set(mig_g),
                fitI.at[ii, worst].set(mig_f))

    def breed(a):
        gen, key, pop, fit, _ = a          # gen: generations completed
        if I > 1:
            popI = pop.reshape(I, Pi, L)
            fitI = fit.reshape(I, Pi)
            popI, fitI = jax.lax.cond(
                (gen > 0) & (gen % migrate_every == 0),
                lambda a: migrate(*a), lambda a: a, (popI, fitI))
            pop = popI.reshape(P, L)
            fit = fitI.reshape(P)
        key, sub = jax.random.split(key)
        if I == 1:
            pop, canon = gen_fn(pop, fit, sub)
        else:
            subs = jax.random.split(sub, I)
            popI, canonI = jax.vmap(gen_fn)(
                pop.reshape(I, Pi, L), fit.reshape(I, Pi), subs)
            pop = popI.reshape(P, L)
            canon = canonI.reshape(P, L)
        return key, pop, canon

    def refine(pop0, key, memo, e_homo, lo, hi, alpha, ops, tms):
        # Iteration 0 scores the seed population, every later one breeds
        # and scores a generation: ``score`` (the search kernel) appears
        # once in the program — each copy costs minutes of TPU compile.
        pop0 = pop0.astype(jnp.int32)
        W = e_homo.shape[0]
        zw = jnp.zeros((P, W), jnp.float64)
        best = (jnp.asarray(-jnp.inf, jnp.float64),
                jnp.zeros(L, jnp.int32), jnp.zeros(W, jnp.float64),
                jnp.zeros(W, jnp.float64), jnp.zeros(W, jnp.float64),
                jnp.asarray(0.0, jnp.float64))
        hist = jnp.full(generations + 1, -jnp.inf)
        carry = (jnp.asarray(0), jnp.asarray(0), key, pop0,
                 _canonical_device(pop0), jnp.zeros(P, jnp.float64),
                 zw, zw, zw, jnp.zeros(P, jnp.float64), memo, best, hist)

        def cond(c):
            it, stall = c[0], c[1]
            return (it == 0) | ((it <= generations) & (stall < early_stop))

        def body(c):
            (it, stall, key, pop, canon, fit, lat, en, tw, area, memo,
             best, hist) = c
            key, pop, canon = jax.lax.cond(
                it > 0, breed, lambda a: (a[1], a[2], a[4]),
                (it - 1, key, pop, fit, canon))
            fit, lat, en, tw, area, memo = score(
                pop, canon, memo, e_homo, lo, hi, alpha, ops, tms)
            gi = jnp.argmax(fit)
            imp = (it == 0) | (fit[gi] > best[0])

            def pick(new, old):
                return jnp.where(imp, new, old)

            best = (pick(fit[gi], best[0]), pick(pop[gi], best[1]),
                    pick(lat[gi], best[2]), pick(en[gi], best[3]),
                    pick(tw[gi], best[4]), pick(area[gi], best[5]))
            stall = jnp.where(imp, 0, stall + 1)
            hist = hist.at[it].set(best[0])
            return (it + 1, stall, key, pop, canon, fit, lat, en, tw, area,
                    memo, best, hist)

        (it, _, _, pop, _, fit, lat, en, tw, area, memo, best,
         hist) = jax.lax.while_loop(cond, body, carry)
        gen = it - 1
        return {"gen": gen, "pop": pop, "fit": fit, "lat": lat, "en": en,
                "tw": tw, "area": area, "memo": memo, "hist": hist,
                "best_fit": best[0], "best_genome": best[1],
                "best_lat": best[2], "best_en": best[3],
                "best_tw": best[4], "best_area": best[5]}

    return jax.jit(refine)


def run_ga_fused(sweep, bracket: float, cfg=None, seed: int = 0,
                 calib: CalibrationTable = DEFAULT_CALIB,
                 verbose: bool = False,
                 engine: Optional[EvalEngine] = None,
                 islands: Optional[int] = None, migrate_every: int = 5,
                 migrate_k: int = 2, memo: Optional[DeviceMemo] = None,
                 memo_capacity: int = 1 << 15,
                 store_sync: bool = True) -> Optional[FusedRefinement]:
    """GA refinement at one area budget with the WHOLE run fused into one
    jitted dispatch, scored against the device-resident memo
    (``dse.device_memo``) instead of per-generation host memo round
    trips.

    Same seeding and contract as ``run_ga_device`` (None when the
    bracket has no homogeneous baseline); requires a *local*
    ``EvalEngine(backend="exact")`` — the loop builds configs and runs
    the search scan itself on device, so a remote ``DSEClient`` can't
    serve it.  ``islands=None`` picks one island per local device when
    the population splits evenly (``launch.mesh.default_islands``), else
    a single panmictic island, which walks the exact genome stream of
    ``run_ga_device`` (the PR's bitwise invariant).  ``store_sync=True``
    treats this call as one seed boundary: the memo preloads from the
    engine store's LRU tier and drains back after the run (the §4
    pipeline passes ``memo=`` and manages boundaries itself).

    The engine's ``stats``/store see nothing per generation — that is
    the point; hits/misses live in the device table until drained.
    """
    from .ga import GAConfig, GAResult
    from ..compiler.batched_mapper import _search_xs_cached
    cfg = cfg or GAConfig()
    if engine is None:
        engine = EvalEngine(sweep.workloads, calib,
                            config=EngineConfig(backend="exact",
                                                nonfinite="skip"))
    elif not isinstance(engine, EvalEngine):
        raise ValueError("run_ga_fused needs a local EvalEngine — the "
                         "fused loop stages configs and the search scan "
                         "itself, which a remote client cannot serve")
    else:
        engine.check_workloads(sweep.workloads, calib)
    if engine.backend != "exact":
        raise ValueError("run_ga_fused requires backend='exact' (the fused "
                         f"search kernel); got {engine.backend!r}")
    rng = np.random.default_rng(seed + int(bracket))
    base = sweep.homo_baseline()
    if bracket not in base:
        return None
    e_homo = np.asarray(base[bracket], np.float64)
    lo, hi = bracket_bounds(bracket)
    W = len(engine.workloads)

    # ---- seed population: identical to run_ga_device ----------------------
    fit_sweep = sweep.fitness(cfg.alpha)
    in_b = np.nonzero((sweep.bracket == bracket) & np.isfinite(fit_sweep))[0]
    order = in_b[np.argsort(-fit_sweep[in_b])][:cfg.seed_top_k]
    pop = sweep.genomes[order].copy()[:cfg.population]
    while len(pop) < cfg.population:
        fill = random_genomes(rng, cfg.population - len(pop),
                              family="hetero_bls" if rng.random() < 0.5
                              else None)
        pop = np.concatenate([pop, fill])[:cfg.population]
    pop = np.ascontiguousarray(pop, np.int32)

    P = cfg.population
    if islands is None:
        from ...launch.mesh import default_islands
        islands = default_islands(P)
    islands = max(int(islands), 1)
    if P % islands:
        raise ValueError(f"population {P} not divisible into "
                         f"{islands} islands")
    Pi = P // islands
    n_elite = max(int(cfg.elitism * Pi), 1)
    if n_elite >= Pi:
        raise ValueError(f"per-island population {Pi} leaves no room for "
                         f"{n_elite} elites — fewer islands or more genomes")
    mk = max(min(int(migrate_k), Pi // 2), 1) if islands > 1 else 0

    if memo is None:
        memo = memo_from_store(engine, memo_capacity) if store_sync \
            else memo_init(memo_capacity, W)
    elif memo.vals.shape[-1] != W:
        raise ValueError(f"memo carries {memo.vals.shape[-1]}-workload "
                         f"rows; engine scores {W}")

    per_op, steps, n_state, tms = _search_xs_cached(
        [engine._prepared(w) for w in engine.workloads])

    kernel = _refine_kernel(calib, n_state, engine.mode, P, islands,
                            cfg.generations, cfg.tournament, n_elite,
                            cfg.crossover_rate, cfg.mutation_rate,
                            cfg.early_stop, int(migrate_every), mk,
                            engine.fidelity)

    pop_dev = jnp.asarray(pop, jnp.int32)
    sharding = None
    if islands > 1:
        from ...launch.mesh import island_sharding
        sharding = island_sharding(islands)
    elif engine._sharding is not None:
        from ...launch.mesh import population_sharding
        sharding = population_sharding(P)
    if sharding is not None:
        pop_dev = jax.device_put(pop_dev, sharding)

    key = jax.random.PRNGKey(seed + int(bracket))
    out = kernel(pop_dev, key, memo,
                 jnp.asarray(e_homo), jnp.asarray(lo, jnp.float64),
                 jnp.asarray(hi, jnp.float64),
                 jnp.asarray(cfg.alpha, jnp.float64), (per_op, steps), tms)

    n_gens = int(out["gen"])
    history = [float(x) for x in np.asarray(out["hist"][:n_gens + 1])]
    best_metrics = {"latency": np.asarray(out["best_lat"]),
                    "energy": np.asarray(out["best_en"]),
                    "tops_w": np.asarray(out["best_tw"]),
                    "area": np.float64(out["best_area"])}
    sav = (e_homo - best_metrics["energy"]) / np.maximum(e_homo, 1e-30)
    result = GAResult(
        bracket=bracket, best_genome=np.asarray(out["best_genome"]),
        best_fitness=float(out["best_fit"]), best_savings_per_wl=sav,
        best_metrics=best_metrics, history=history,
        evaluated=P * (n_gens + 1))
    memo = out["memo"]
    if store_sync:
        drain_to_store(memo, engine)
    if verbose:
        print(f"[ga-fused {bracket:.0f}mm2] {n_gens} gens x {P} genomes "
              f"({islands} island(s)): best={result.best_fitness:+.4f}")
    return FusedRefinement(
        result=result, memo=memo,
        population=np.asarray(out["pop"]),
        pop_metrics={"latency": np.asarray(out["lat"]),
                     "energy": np.asarray(out["en"]),
                     "tops_w": np.asarray(out["tw"]),
                     "area": np.asarray(out["area"])},
        generations_run=n_gens)
