"""Cache-aware DSE evaluation engine — the single hot path of the search.

Every search frontend (stratified sweep, GA refinement, Bayesian
optimization, genome hillclimb) funnels its candidate scoring through one
``EvalEngine``.  The engine owns three caches and one scaling axis:

1. **Workload preparation cache** — ``prepare_workload(build(name))``
   (compiler passes 1-2 + SoA tensorization) runs once per
   ``(workload, precision/fusion setting)`` per process, not once per
   batch per generation.  Shared module-wide via an LRU.
2. **Genome memoization** — results are keyed on the genome's integer
   content.  The GA's elites, duplicate children, and genomes repeated
   across seeds / brackets / rounds are never re-simulated.  Safe because
   the jitted batch evaluator is vmapped element-wise: a config's result
   is bitwise identical regardless of the batch it rides in (pinned by
   tests/test_engine.py).
3. **Vectorized genome→SoA decoding** — ``genomes_to_configs`` stacks the
   ``prepare_configs`` arrays directly from the integer genomes with pure
   numpy, without materializing per-genome Python ``ChipConfig`` /
   ``TileTemplate`` objects in the hot loop.  Bitwise parity with
   ``prepare_configs([decode(g)])`` is pinned by tests/test_engine.py;
   the reference ``decode()`` stays the finalist re-scoring path.
4. **Candidate-axis sharding** — with ``shard=True`` and more than one
   JAX device, the (B, MAX_TILES) config arrays are placed with a
   ``NamedSharding`` over the batch axis
   (``repro.launch.mesh.candidate_sharding``), so the sweep scales
   across whatever devices exist; on one device it is a no-op.  The
   sharding covers every evaluation path — the ``batch_eval`` scan AND
   the compile-free batched mapper+executor; ``_pad_size`` rounds batch
   shapes up to a mesh-size multiple (after bucket rounding) so uneven
   populations never fall back to per-device replication.

**Evaluation backends.**  Cache misses are simulated by one of four
backends sharing one set of cost formulas (``simulator.costs``):

* ``"scan"`` (default) — ``batch_eval``'s fused compile+simulate scan:
  exact orchestrator semantics but an in-scan greedy re-derivation of
  the Eq. 1-3 mapping (epsilon tie-breaks, ragged-remainder-free
  splits).  Retained as the approximate-search baseline;
* ``"exact"`` (the *search* grade of the exact path) — the
  class-specialized single-scan kernel
  (``compiler.batched_mapper.search_and_simulate``): exact Eq. 1-3
  mapping fused with exact plan execution in ONE scan, with only the
  op's class sub-models evaluated per step.  Metrics are bitwise equal
  to ``rescore()``, so a search running this backend never needs a
  post-hoc exact re-score — searching on an approximate objective and
  re-ranking finalists (the fidelity gap HARP-style taxonomies warn
  about) is retired for GA refinement;
* ``"batched"`` — the two-scan exact path:
  ``compiler.batched_mapper.map_and_simulate`` fuses the exact batched
  Eq. 1-3 mapping scan (placements pinned *bitwise* to ``map_graph``)
  with the vmapped/jitted ``simulator.batched`` plan executor.
  ``exact_mapper="python"`` falls back to the per-candidate
  ``map_graph`` -> ``lower_plan`` pipeline (the oracle-reference
  compile path, bitwise-identical results);
* ``"oracle"`` — ``map_graph`` + the per-candidate Python ``ChipSim``
  walk, kept as the ground truth the other two are pinned against.

Search uses the engine; finalists of approximate (``scan``) searches
are re-scored through ``rescore()`` (exact), and ``exact``-backend
searches are already exact at search time.  Every ``evaluate()`` result
carries a ``"meta"`` entry reporting the backend, the schedule mode,
and the call's cache hit/miss/skip counts.

**Schedule modes** (§3.2, the serving-vs-latency scenario axis).
``mode="latency"`` (default) scores the one-batch makespan;
``mode="throughput"`` scores the pipelined steady state — the
``latency`` column becomes the initiation interval (II), ``energy`` the
per-inference steady-state energy (leakage charged over II), and
``tops_w`` the TOPS/W at the pipelined rate.  All three backends model
both modes through the shared ``costs.pipeline_bounds`` composition
(oracle/batched at 0 rel err; the scan backend on its in-scan greedy
placements); memo entries are keyed on (mode, genome).

An optional ``keep`` predicate lets a frontend skip simulation for
genomes it will discard anyway (e.g. the GA's out-of-bracket children,
whose fitness is -inf regardless of their metrics): skipped genomes get
``inf`` latency/energy and are *not* memoized, so a later unfiltered
request still simulates them.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import operator
import threading
import time
import warnings
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..arch import (KNOB_GRID, MAX_TILE_TYPES, MAX_TILES, prec_mask)
from ..calibrate.asap7 import CalibrationTable, DEFAULT_CALIB
from ..simulator.costs import COST_MODEL_VERSION, FIDELITIES, grid_dims
from ..simulator.orchestrator import CACHE_FRAC, SCHEDULE_MODES, noc_hops
from ..workloads import build
from .api import (BACKENDS, EngineConfig, META_VERSION, context_digest)
from .batch_eval import (_CHIP_KEYS, _TILE_KEYS, batch_evaluate,
                         prepare_configs, prepare_workload)
from .encoding import (FIELDS_PER_TILE, GENOME_LEN, IDX_ASPECT, IDX_DRAM,
                       IDX_DRAM_CH, IDX_ICONN, IDX_NOC_BPC, IDX_TOPO,
                       _TILE_FIELDS, decode)
from .store import MemoryLRUStore, ResultStore, TieredStore

__all__ = ["EvalEngine", "EngineStats", "EngineConfig",
           "NonFiniteMetricsError", "genomes_to_configs", "genome_areas",
           "genome_area_fn", "canonical_genomes", "prepared_workload",
           "BACKENDS", "SCHEDULE_MODES"]


class NonFiniteMetricsError(RuntimeError):
    """A freshly simulated metric row contained NaN (or a non-finite
    TOPS/W) — raised *before* the row can enter any memo, store, or
    Pareto front, naming the offending canonical genome.  ``retryable``
    because the poisoned batch was never memoized: a retry re-simulates
    it cleanly when the corruption was transient (the chaos suite's
    injected-NaN case)."""

    retryable = True

    def __init__(self, canon: np.ndarray, mode: str,
                 row: Tuple[np.ndarray, np.ndarray, np.ndarray]):
        self.canon = np.asarray(canon, np.int64).copy()
        self.mode = str(mode)
        self.row = tuple(np.asarray(a, np.float64).copy() for a in row)
        super().__init__(
            f"non-finite metrics for canonical genome "
            f"{self.canon.tolist()} (mode={self.mode}): lat="
            f"{self.row[0].tolist()} en={self.row[1].tolist()} "
            f"tops_w={self.row[2].tolist()}; pass nonfinite='skip' to "
            f"score such rows -inf instead")

# metric keys each §3.2 schedule mode scores on: latency-critical
# deployment uses the one-batch makespan; serving (throughput) uses the
# pipelined steady state — initiation interval, per-inference energy with
# leakage charged over II, and steady-state achieved TOPS
_MODE_KEYS = {
    "latency": ("latency_s", "energy_pj", "achieved_tops"),
    "throughput": ("ii_s", "energy_ss_pj", "achieved_tops_ss"),
}


@functools.lru_cache(maxsize=128)
def _prepared_graph(name: str, aggressive_int4: bool = False,
                    enable_fusion: bool = True):
    """Config-independent compiler passes 1-2 on one workload, cached so
    the exact backends re-run only the per-chip mapping.  Callers must
    treat the returned graph as read-only (map_graph does)."""
    import copy as _copy
    from ..compiler.fusion import fuse
    from ..compiler.precision import assign_precision
    g = _copy.deepcopy(build(name))
    g = assign_precision(g, aggressive_int4=aggressive_int4)
    if enable_fusion:
        g = fuse(g)
    return g


# =============================================================================
# workload preparation cache (cache 1)
# =============================================================================

@functools.lru_cache(maxsize=128)
def prepared_workload(name: str, aggressive_int4: bool = False,
                      enable_fusion: bool = True) -> Dict[str, np.ndarray]:
    """Config-independent compile of one workload, cached process-wide.
    Callers must treat the returned arrays as read-only."""
    return prepare_workload(build(name), aggressive_int4=aggressive_int4,
                            enable_fusion=enable_fusion)


# =============================================================================
# vectorized genome -> SoA config stacking (cache 3's fast path)
# =============================================================================
# Grid lookup tables.  Index order matches KNOB_GRID, and the modulo used
# per field matches decode() exactly.

_ARRAY_DIM = np.asarray(KNOB_GRID["array_dim"], np.float64)
_SRAM_KB = np.asarray(KNOB_GRID["sram_kb"], np.float64)
_COUNT = np.asarray(KNOB_GRID["count"], np.int64)
_SFU = np.asarray(KNOB_GRID["sfu_mask"], np.float64)
_ENGINE = np.asarray([int(e) for e in KNOB_GRID["engine"]], np.float64)
_SPARSITY = np.asarray([int(s) for s in KNOB_GRID["sparsity"]], np.float64)
_DATAFLOW = np.asarray([int(d) for d in KNOB_GRID["dataflow"]], np.float64)
_PIPE = np.asarray(KNOB_GRID["pipeline_depth"], np.float64)
_DB = np.asarray([float(b) for b in KNOB_GRID["double_buffer"]], np.float64)
_ASYM = np.asarray([int(a) for a in KNOB_GRID["asym_mac"]], np.float64)
_PREC_MASK = np.asarray([prec_mask(sorted(s))
                         for s in KNOB_GRID["precision_set"]], np.float64)
_PREC_MAX = np.asarray([int(max(s, key=int))
                        for s in KNOB_GRID["precision_set"]], np.int64)
_DRAM = np.asarray(KNOB_GRID["dram_gbps"], np.float64)
_ICONN = [ic for ic in KNOB_GRID["interconnect"]]
# interconnect-structure gene grids (PR 9 topology genes)
_TOPO = np.asarray([float(b) for b in KNOB_GRID["noc_topology"]], np.float64)
_ASPECT = np.asarray(KNOB_GRID["grid_aspect"], np.float64)
_NOC_BPC = np.asarray(KNOB_GRID["noc_bpc"], np.float64)
_DRAM_CH = np.asarray(KNOB_GRID["dram_channels"], np.float64)
# hop counts tabulated over (interconnect, num_tiles): 4 x (MAX_TILES+1)
_HOPS_TABLE = np.asarray(
    [[float(noc_hops(ic, max(n, 1))) for n in range(MAX_TILES + 1)]
     for ic in _ICONN], np.float64)

_FIELD_COL = {f: i for i, f in enumerate(_TILE_FIELDS)}


def _tile_cols(genomes: np.ndarray, t: int, field: str) -> np.ndarray:
    return genomes[:, 1 + t * FIELDS_PER_TILE + _FIELD_COL[field]]


def _per_type_values(genomes: np.ndarray, calib: CalibrationTable):
    """(B, MAX_TILE_TYPES) arrays of per-tile-type template values,
    replicating decode()'s knob lookups (including its modulo wrapping) and
    tile_area()'s arithmetic term-for-term so parity is bitwise."""
    B = len(genomes)
    T = MAX_TILE_TYPES
    v: Dict[str, np.ndarray] = {}
    f64 = lambda a: np.asarray(a, np.float64)

    sfu_idx = np.stack([_tile_cols(genomes, t, "sfu") % len(_SFU)
                        for t in range(T)], axis=1)
    sfu = _SFU[sfu_idx]
    special = sfu > 0

    rows = np.stack([_ARRAY_DIM[_tile_cols(genomes, t, "rows") % 5]
                     for t in range(T)], axis=1)
    cols = np.stack([_ARRAY_DIM[_tile_cols(genomes, t, "cols") % 5]
                     for t in range(T)], axis=1)
    rows = np.where(special, 0.0, rows)
    cols = np.where(special, 0.0, cols)
    big = rows * cols >= 1024.0
    v["rows"], v["cols"] = rows, cols
    v["num_macs"] = rows * cols
    v["clock_mhz"] = np.where(special, 800.0, np.where(big, 1200.0, 500.0))
    v["dsp_count"] = np.where(special, 1.0, np.where(big, 2.0, 1.0))
    v["dsp_simd"] = np.full((B, T), 64.0)
    v["sfu_mask"] = sfu
    v["sfu_parallel"] = np.full((B, T), 16.0)
    v["sram_bpc"] = np.full((B, T), 8 * 16.0)   # default sram_banks=8

    v["engine"] = np.stack([_ENGINE[_tile_cols(genomes, t, "engine") % 4]
                            for t in range(T)], axis=1)
    prec_idx = np.stack([_tile_cols(genomes, t, "prec") % 4
                         for t in range(T)], axis=1)
    v["prec_mask"] = _PREC_MASK[prec_idx]
    max_prec = _PREC_MAX[prec_idx]
    v["max_prec"] = f64(max_prec)
    v["sparsity"] = np.stack([_SPARSITY[_tile_cols(genomes, t, "sparsity") % 3]
                              for t in range(T)], axis=1)
    v["dataflow"] = np.stack([_DATAFLOW[_tile_cols(genomes, t, "dataflow") % 3]
                              for t in range(T)], axis=1)
    v["sram_kb"] = np.stack([_SRAM_KB[_tile_cols(genomes, t, "sram") % 7]
                             for t in range(T)], axis=1)
    v["double_buffer"] = np.stack([_DB[_tile_cols(genomes, t, "db") % 2]
                                   for t in range(T)], axis=1)
    v["pipeline_depth"] = np.stack([_PIPE[_tile_cols(genomes, t, "pipe") % 4]
                                    for t in range(T)], axis=1)
    v["asym_mac"] = np.stack([_ASYM[_tile_cols(genomes, t, "asym") % 4]
                              for t in range(T)], axis=1)
    v["cache_cap"] = v["sram_kb"] * 1024.0 * CACHE_FRAC
    v["dsp_lanes"] = v["dsp_count"] * v["dsp_simd"]
    v["clock_hz"] = v["clock_mhz"] * 1e6

    # tile_area (Eq. 7), same term order as simulator.area.area_breakdown
    a_mac_mm2 = np.asarray(calib.a_mac_mm2, np.float64)
    eng_a = np.asarray(calib.engine_a_mult, np.float64)
    sp_a = np.asarray(calib.sparsity_a_mult, np.float64)
    eng_idx = np.asarray(v["engine"], np.int64)
    sp_idx = np.asarray(v["sparsity"], np.int64)
    a_mac_unit = a_mac_mm2[max_prec] * eng_a[eng_idx]
    a_mac = v["num_macs"] * a_mac_unit * sp_a[sp_idx]
    a_sram = v["sram_kb"] * calib.a_sram_mm2_per_kb
    a_dsp = v["dsp_count"] * v["dsp_simd"] * calib.a_dsp_mm2_per_lane
    sfu_i = np.asarray(sfu, np.int64)
    a_spec = np.where(sfu_i & 1, calib.a_fft_mm2, 0.0)
    a_spec = a_spec + np.where(sfu_i & 2, calib.a_lif_mm2, 0.0)
    a_spec = a_spec + np.where(sfu_i & 4, calib.a_poly_mm2, 0.0)
    a_ports = calib.a_ports_base_mm2 \
        + (rows + cols) * calib.a_ports_per_lane_mm2
    v["area_mm2"] = a_mac + a_sram + a_dsp + a_spec + a_ports

    counts = np.stack([_COUNT[_tile_cols(genomes, t, "count") % 8]
                       for t in range(T)], axis=1)
    n_types = (genomes[:, 0] + 1)[:, None]  # decode: genome[0] + 1
    counts = np.where(np.arange(T)[None, :] < n_types, counts, 0)
    v["counts"] = counts
    return v


def genomes_to_configs(genomes: np.ndarray,
                       calib: CalibrationTable = DEFAULT_CALIB
                       ) -> Dict[str, Dict[str, np.ndarray]]:
    """Vectorized equivalent of ``prepare_configs([decode(g) for g in
    genomes], calib)`` — bitwise identical output, no per-genome Python
    object materialization."""
    genomes = np.asarray(genomes, dtype=np.int64).reshape(-1, GENOME_LEN)
    B = len(genomes)
    v = _per_type_values(genomes, calib)
    counts = v["counts"]                        # (B, T) ints
    starts = np.zeros_like(counts)
    starts[:, 1:] = np.cumsum(counts, axis=1)[:, :-1]
    ends = starts + counts

    slots = np.arange(MAX_TILES)                # (S,)
    # (B, T, S) membership of each instance slot in each tile type
    member = (slots[None, None, :] >= starts[:, :, None]) \
        & (slots[None, None, :] < ends[:, :, None])

    tile_f = {}
    for f in ("num_macs", "rows", "cols", "engine", "prec_mask", "asym_mac",
              "sparsity", "dataflow", "sram_kb", "dsp_lanes", "dsp_count",
              "sfu_mask", "sfu_parallel", "double_buffer", "pipeline_depth",
              "clock_hz", "cache_cap", "sram_bpc", "area_mm2", "max_prec"):
        # exactly one membership per occupied slot -> the masked sum is the
        # per-type value itself, bit-for-bit
        tile_f[f] = np.sum(np.where(member, v[f][:, :, None], 0.0), axis=1)
    tile_f["exists"] = member.any(axis=1).astype(np.float64)

    num_tiles = counts.sum(axis=1)              # (B,) ints
    chip_f = {f: np.zeros(B) for f in _CHIP_KEYS}
    chip_f["dram_gbps"] = _DRAM[genomes[:, IDX_DRAM] % 6].copy()
    iconn_idx = np.asarray(genomes[:, IDX_ICONN] % 4)
    chip_f["hops"] = _HOPS_TABLE[iconn_idx, num_tiles]
    chip_f["noc_bpc"] = _NOC_BPC[genomes[:, IDX_NOC_BPC] % 4].copy()
    chip_f["noc_base_cycles"] = np.full(B, 8.0)  # ChipConfig defaults
    chip_f["ref_clock_hz"] = np.full(B, 1000 * 1e6)
    # interconnect-structure genes (decode()'s knob lookups, vectorized)
    chip_f["torus"] = _TOPO[genomes[:, IDX_TOPO] % 2].copy()
    chip_f["dram_channels"] = _DRAM_CH[genomes[:, IDX_DRAM_CH] % 4].copy()
    aspect = _ASPECT[genomes[:, IDX_ASPECT] % 3]
    gw, gh = grid_dims(np, np.asarray(num_tiles, np.float64), aspect)
    chip_f["grid_w"], chip_f["grid_h"] = gw, gh

    # peak_tops: sequential per-instance sum, matching prepare_configs
    term = tile_f["num_macs"] * tile_f["clock_hz"]
    acc = np.zeros(B)
    for s in range(MAX_TILES):
        acc = acc + term[:, s]
    chip_f["peak_tops"] = acc / 1e12

    # chip_area: per-type tile_area * count summed in type order + NoC
    # (router/link width + torus scale) + extra DRAM-channel PHYs —
    # term-for-term simulator.area.chip_area
    area = np.zeros(B)
    for t in range(MAX_TILE_TYPES):
        area = area + v["area_mm2"][:, t] * counts[:, t]
    noc_scale = (0.5 + 0.5 * chip_f["noc_bpc"] / 64.0) \
        * np.where(chip_f["torus"] > 0, 1.25, 1.0)
    area = area + num_tiles * calib.a_noc_mm2_per_tile * noc_scale
    chip_f["chip_area"] = area \
        + (chip_f["dram_channels"] - 1) * calib.a_dram_phy_mm2
    return {"tile": tile_f, "chip": chip_f}


def genome_areas(genomes: np.ndarray,
                 calib: CalibrationTable = DEFAULT_CALIB) -> np.ndarray:
    """(N,) chip areas straight from genomes (== chip_area(decode(g)))."""
    return genomes_to_configs(genomes, calib)["chip"]["chip_area"]


@functools.lru_cache(maxsize=4)
def _area_tables_host(calib: CalibrationTable):
    """Host-precomputed Eq. 7 area tables over the full (discrete) knob
    grid: per-type tile area, tile area x count, and NoC area by tile
    count.  ``ga_device`` gathers from them on the device,
    ``genome_area_fn`` on the host.  XLA:CPU contracts mul+add chains
    into FMAs under jit — no flag or ``optimization_barrier`` prevents
    it — which skips the host stack's per-product rounding and breaks
    the device port's bitwise-parity contract.  So the device does NO
    area arithmetic: every area value is a gather from these tables,
    each entry computed by the exact numpy expressions
    ``_per_type_values`` runs (identical rounding by construction).  Grid: prec(4) x engine(4) x sparsity(3)
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


# genes genome_area_fn reads per tile type, in its unpacking order
_AREA_GENES = tuple(
    operator.itemgetter(*(1 + t * FIELDS_PER_TILE + _FIELD_COL[f]
                          for f in ("prec", "engine", "sparsity", "rows",
                                    "cols", "sfu", "sram", "count")))
    for t in range(MAX_TILE_TYPES))


@functools.lru_cache(maxsize=4)
def genome_area_fn(calib: CalibrationTable = DEFAULT_CALIB
                   ) -> Callable[[Sequence[int]], float]:
    """Scalar chip area of one genome: ``genome_area_fn(calib)(g) ==
    chip_area(decode(g), calib)`` bitwise, for ``g`` any sequence of ints
    (a Python list is fastest).  It follows ``ga_device._chip_area_device``
    step for step over ``_area_tables_host``: each active type's count
    term summed left to right from 0.0, then the NoC term, then the DRAM
    PHY term.  The count terms are read through a read-only memoryview of
    that table (42 K x 8 float64, ~2.7 MB per calibration, no copy);
    only the small NoC and DRAM tables become Python lists.  The
    stratified sampler scores every repair step with it, where ``decode``
    + ``chip_area`` cost ~50 us a call."""
    _, count_tab, noc_tab, dram_tab = _area_tables_host(calib)
    n_sfu, n_cnt = len(_SFU), len(_COUNT)
    count_terms = memoryview(count_tab.reshape(-1)).toreadonly()
    counts = _COUNT.tolist()
    noc_tab, dram_tab = noc_tab.tolist(), dram_tab.tolist()

    def area(g: Sequence[int]) -> float:
        acc = 0.0
        num_tiles = 0
        for genes in _AREA_GENES[:g[0] + 1]:
            prec, eng, sp, rows, cols, sfu, sram, cnt = genes(g)
            flat = (((((prec % 4 * 4 + eng % 4) * 3 + sp % 3) * 5 + rows % 5)
                     * 5 + cols % 5) * n_sfu + sfu % n_sfu) * 7 + sram % 7
            acc = acc + count_terms[flat * n_cnt + cnt % n_cnt]
            num_tiles += counts[cnt % n_cnt]
        acc = acc + noc_tab[num_tiles][g[IDX_NOC_BPC] % 4][g[IDX_TOPO] % 2]
        return acc + dram_tab[g[IDX_DRAM_CH] % 4]

    return area


_SFU_COL = _FIELD_COL["sfu"]
# genes decode() ignores on a Special-Function tile (rows/cols are forced
# to 0) plus the MAC-path knobs whose values only feed terms that a
# zero-MAC tile multiplies or gates away (engine/precision/sparsity/
# dataflow/asym/pipeline) — bitwise inertness is pinned by
# tests/test_engine.py::test_special_tile_inert_genes
_SPECIAL_INERT_COLS = tuple(
    _FIELD_COL[f] for f in ("rows", "cols", "engine", "prec", "sparsity",
                            "dataflow", "asym", "pipe"))
_PREC_COL = _FIELD_COL["prec"]
_ASYM_COL = _FIELD_COL["asym"]
# asym_mac acts only through supports_precision, so per precision-set the
# four variants collapse into equivalence classes (row = prec gene, col =
# asym gene): {INT8} gains INT4 from W4A8/W2A8 and nothing from W4A16;
# {INT4,INT8} and the full set gain nothing; {INT8,FP16} gains INT4 from
# any variant.  Pinned bitwise by tests/test_engine.py.
_ASYM_CANON = np.asarray([[0, 1, 1, 0],
                          [0, 0, 0, 0],
                          [0, 1, 1, 1],
                          [0, 0, 0, 0]], np.int64)


def canonical_genomes(genomes: np.ndarray) -> np.ndarray:
    """Zero every don't-care gene so genomes that decode() maps to the
    same chip (or to chips with bitwise-identical metrics) share one memo
    entry: the tile-type blocks beyond ``n_tile_types``, and the inert
    genes of Special-Function tiles.  Crossover and mutation constantly
    touch these genes — without canonicalization every such child looks
    novel and gets re-simulated."""
    g = np.asarray(genomes, dtype=np.int64).reshape(-1, GENOME_LEN).copy()
    n_types = g[:, 0] + 1
    for t in range(MAX_TILE_TYPES):
        base = 1 + t * FIELDS_PER_TILE
        inactive = t >= n_types
        block = g[:, base:base + FIELDS_PER_TILE]
        g[:, base:base + FIELDS_PER_TILE] = \
            np.where(inactive[:, None], 0, block)
        special = (_SFU[g[:, base + _SFU_COL] % len(_SFU)] > 0) & ~inactive
        for col in _SPECIAL_INERT_COLS:
            g[:, base + col] = np.where(special, 0, g[:, base + col])
        g[:, base + _ASYM_COL] = _ASYM_CANON[g[:, base + _PREC_COL] % 4,
                                             g[:, base + _ASYM_COL] % 4]
    return g


# =============================================================================
# the engine
# =============================================================================

@dataclasses.dataclass
class EngineStats:
    """Counters over the engine's lifetime.  ``requests`` counts genome
    scoring requests (one per genome per evaluate() call); a request is a
    hit (memoized), a skip (rejected by the ``keep`` predicate), or a
    miss (simulated now, on every workload)."""

    requests: int = 0
    hits: int = 0
    skips: int = 0
    misses: int = 0
    eval_seconds: float = 0.0
    workloads: int = 0
    # fused miss-batch dispatches: one per simulated micro-batch (the unit
    # the serving layer's cross-request coalescing reduces)
    dispatches: int = 0

    def hit_rate(self) -> float:
        return self.hits / max(self.requests, 1)

    def throughput(self) -> float:
        """Scored (config x workload) pairs per second of evaluate() time,
        counting cache hits as scored work (that is the point)."""
        pairs = (self.hits + self.misses) * self.workloads
        return pairs / max(self.eval_seconds, 1e-12)


# sentinel distinguishing "caller passed this legacy kwarg" (deprecation
# shim fires) from "default" on EvalEngine.__init__
_UNSET = object()


def _bucket(n: int, step: int = 4, floor: int = 16) -> int:
    """Pad batch sizes to multiples of ``step`` (>= ``floor``): CPU
    vectorization of the vmapped scan saturates around B=16, so cost is
    ~linear in B beyond that and coarse power-of-two padding would waste
    up to 2x the work.  The bounded shape set keeps jit retraces finite
    (see ``warmup``)."""
    return max(((n + step - 1) // step) * step, floor)


class EvalEngine:
    """Unified cached scorer: genomes x fixed workload list -> metrics.

    ``evaluate`` has the same output contract as the legacy
    ``sweep.evaluate_genomes``: dict of ``latency`` (N, W), ``energy``
    (N, W), ``tops_w`` (N, W), ``area`` (N,).

    ``memoize=False`` / ``vectorized=False`` disable cache 2 / cache 3
    (the decode()-based reference path) — used by parity tests and the
    perf benchmark as the pre-refactor baseline.
    """

    def __init__(self, workloads: Sequence[str],
                 calib: CalibrationTable = DEFAULT_CALIB,
                 batch=_UNSET, memoize=_UNSET,
                 vectorized=_UNSET, shard=_UNSET,
                 aggressive_int4=_UNSET, enable_fusion=_UNSET,
                 memo_max=_UNSET, backend=_UNSET,
                 exact_mapper=_UNSET, mode=_UNSET,
                 memo_limit=_UNSET,
                 store=_UNSET,
                 nonfinite=_UNSET, fidelity=_UNSET,
                 config: Optional[EngineConfig] = None):
        # ``config=EngineConfig(...)`` is the canonical construction; the
        # per-knob kwargs are the pre-PR-9 surface, kept working behind a
        # deprecation shim (they warn, then assemble the same config).
        if memo_limit is not _UNSET:
            warnings.warn(
                "EvalEngine(memo_limit=...) is deprecated; pass "
                "config=EngineConfig(memo_max=...) (memo_limit is the "
                "pre-PR-5 alias of memo_max)", DeprecationWarning,
                stacklevel=2)
            if memo_max is not _UNSET:
                raise ValueError("pass memo_max or its legacy alias "
                                 "memo_limit, not both")
            memo_max = memo_limit
        legacy = {k: v for k, v in [
            ("batch", batch), ("memoize", memoize),
            ("vectorized", vectorized), ("shard", shard),
            ("aggressive_int4", aggressive_int4),
            ("enable_fusion", enable_fusion), ("memo_max", memo_max),
            ("backend", backend), ("exact_mapper", exact_mapper),
            ("mode", mode), ("store", store), ("nonfinite", nonfinite),
            ("fidelity", fidelity)] if v is not _UNSET}
        if config is not None:
            if legacy:
                raise ValueError(
                    f"pass config=EngineConfig(...) or the legacy per-knob "
                    f"kwargs, not both (got both config= and "
                    f"{sorted(legacy)})")
        else:
            if legacy:
                warnings.warn(
                    f"EvalEngine per-knob kwargs ({sorted(legacy)}) are "
                    f"deprecated; pass config=EngineConfig(...) instead",
                    DeprecationWarning, stacklevel=2)
            config = EngineConfig(**legacy)
        self.config = config
        self.exact_mapper = config.exact_mapper
        self.mode = config.mode
        self.fidelity = config.fidelity
        self.workloads = list(workloads)
        self.calib = calib
        self.batch = config.batch
        self.memoize = config.memoize
        self.vectorized = config.vectorized
        self.shard = config.shard
        self.aggressive_int4 = config.aggressive_int4
        self.enable_fusion = config.enable_fusion
        self.backend = config.backend
        self.nonfinite = config.nonfinite
        # rebind the locals the rest of the ctor reads off the config
        batch, shard = config.batch, config.shard
        memo_max = config.memo_max
        store = config.store
        self.stats = EngineStats(workloads=len(self.workloads))
        # genome key -> (lat (W,), en (W,), tw (W,)); areas are always
        # recomputed from the (cheap, bitwise-reproducible) config stack.
        # Bounded LRU (hits refresh recency): a paper-scale multi-seed
        # random sweep sees millions of unique genomes with near-zero
        # reuse, and an unbounded memo would hold them all for nothing.
        # The default cap holds ~6 full paper-scale GA refinements
        # (population 200 x 101 generations of novel canonical genomes
        # per (bracket, seed)) before recency eviction kicks in, so long
        # multi-seed multi-bracket runs stay bounded without evicting the
        # live refinement's working set.  >= batch so entries stored in
        # one call can't evict each other.
        explicit_cap = memo_max is not None
        self.memo_max = max(memo_max if explicit_cap else 131_072, batch)
        # Caching policy lives behind the pluggable ResultStore interface
        # (dse.store): the default is the historical in-process LRU; pass
        # a TieredStore(MemoryLRUStore(), SqliteStore(path)) to accumulate
        # exact metrics across processes/CI runs/users.  The store is
        # bound to this engine's content context (workloads x calib x
        # flags x backend fidelity x cost-model version), so persistent
        # entries can never be served across incompatible engines.
        #
        # An *explicit* memo_max combined with a caller-supplied store is
        # applied to the store's in-memory LRU tier (re-capped eagerly);
        # a store with no LRU tier to cap makes the combination an error
        # rather than a silent no-op.
        if store is None:
            self.store: ResultStore = MemoryLRUStore(self.memo_max)
        else:
            self.store = store
            if explicit_cap:
                lru = store if isinstance(store, MemoryLRUStore) else (
                    store.front if isinstance(store, TieredStore)
                    and isinstance(store.front, MemoryLRUStore) else None)
                if lru is None:
                    raise ValueError(
                        "memo_max cannot cap a store without an in-memory "
                        "LRU tier — size the store yourself and drop "
                        "memo_max, or wrap it in a TieredStore with a "
                        "MemoryLRUStore front")
                lru.resize(self.memo_max)
        self.store.bind(self.context_key())
        self._sharding = None
        if shard:
            self._sharding = self._make_sharding()
        self._shapes: set = set()   # batch sizes this engine has emitted
        self._shape_lock = threading.Lock()
        # export_memo bulk views keyed on the LRU tier's mutation stamp
        # (see _memo_stamp): a seed-boundary preload over an unchanged
        # store costs O(1) host work instead of a full dict walk
        self._export_cache: Dict[str, Tuple[tuple, Tuple[np.ndarray,
                                                         np.ndarray]]] = {}

    def context_key(self) -> bytes:
        """Digest of everything a memoized metric row depends on besides
        the (canonical genome, mode) pair the short store key carries —
        ``api.context_digest`` over this engine's config (workloads,
        calibration, compile flags, backend mapping class, NoC/DRAM
        fidelity tier, cost-model version).  Persistent stores fold this
        into their content address, so results accumulated by one engine
        are served to another exactly when every one of these
        matches."""
        return context_digest(self.workloads, self.calib,
                              self.aggressive_int4, self.enable_fusion,
                              self.backend, self.fidelity)

    @property
    def _memo(self) -> Dict[bytes, Tuple[np.ndarray, np.ndarray,
                                         np.ndarray]]:
        """Legacy view of the in-memory cache tier (PR 1-5 name): the
        LRU-ordered dict of the store's front tier, or an unshared empty
        dict when the configured store has no in-memory tier."""
        d = self.store.lru_dict()
        return d if d is not None else {}

    def _pad_size(self, n: int) -> int:
        """Batch padding: the jit bucket, rounded up — AFTER bucket
        rounding — so a sharded batch axis divides evenly across devices
        (an indivisible batch makes XLA fall back to whole-batch
        per-device replication).  Unwarmed engines reuse the smallest
        previously-emitted shape within 1.5x instead of minting a new
        one — miss counts vary every GA generation, and without this an
        unwarmed search loop would trigger a fresh XLA compile per new
        count (the shape set converges after a few generations; warmup()
        pre-populates it so padding is then always minimal).  Reused
        shapes are filtered to mesh-size multiples too, so a shape minted
        before sharding context changed can never leak back in.  The
        shape set is lock-guarded: reentrant ``score_batch`` callers
        (the evaluation service's dispatch thread racing a local caller)
        must not corrupt it."""
        pad = _bucket(n)
        ndev = self._sharding.mesh.size if self._sharding is not None else 1
        pad = ((pad + ndev - 1) // ndev) * ndev
        with self._shape_lock:
            reusable = [s for s in self._shapes
                        if pad <= s <= pad * 3 // 2 and s % ndev == 0]
            if reusable:
                return min(reusable)
            self._shapes.add(pad)
            return pad

    # ------------------------------------------------------------- sharding
    @staticmethod
    def _make_sharding():
        """NamedSharding over the candidate batch axis; None on one device."""
        from ...launch.mesh import candidate_sharding
        return candidate_sharding()

    def _shard_cfgs(self, cfgs):
        if self._sharding is None:
            return cfgs
        import jax
        put = lambda a: jax.device_put(a, self._sharding)
        return {"tile": {k: put(cfgs["tile"][k]) for k in _TILE_KEYS},
                "chip": {k: (put(cfgs["chip"][k]) if k in _CHIP_KEYS
                             else cfgs["chip"][k])
                         for k in cfgs["chip"]}}

    # ------------------------------------------------------------- plumbing
    def check_workloads(self, workloads: Sequence[str],
                        calib: Optional[CalibrationTable] = None
                        ) -> "EvalEngine":
        """Guard for shared-engine frontends: metric columns follow
        *this* engine's workload order and calibration, so a caller
        holding a different list (or passing a different calib) would get
        silently mislabeled or miscalibrated numbers."""
        if list(workloads) != self.workloads:
            raise ValueError(
                f"engine workloads {self.workloads} != caller workloads "
                f"{list(workloads)}")
        if calib is not None and calib != self.calib:
            raise ValueError("caller calib differs from the shared "
                             "engine's calib — results would not match")
        return self

    def _prepared(self, wname: str) -> Dict[str, np.ndarray]:
        return prepared_workload(wname, self.aggressive_int4,
                                 self.enable_fusion)

    def _configs(self, genomes: np.ndarray):
        if self.vectorized:
            return genomes_to_configs(genomes, self.calib)
        chips = [decode(g, f"g{i}") for i, g in enumerate(genomes)]
        return prepare_configs(chips, self.calib)

    @staticmethod
    def _key(genome: np.ndarray) -> bytes:
        return np.ascontiguousarray(genome, dtype=np.int64).tobytes()

    @staticmethod
    def _take(cfgs, idx):
        return {"tile": {k: v[idx] for k, v in cfgs["tile"].items()},
                "chip": {k: v[idx] for k, v in cfgs["chip"].items()}}

    def _simulate(self, cfgs, n: int, genomes: Optional[np.ndarray] = None,
                  mode: Optional[str] = None):
        """(n, W) lat/en/tw for the first n rows of a (possibly padded)
        config stack, through this engine's backend.  In throughput mode
        the three metrics are the steady-state surface: II (s),
        per-inference energy (pJ), and TOPS/W at the steady-state rate."""
        mode = self.mode if mode is None else mode
        self.stats.dispatches += 1
        if self.backend != "scan":
            return self._simulate_exact(genomes[:n],
                                        oracle=self.backend == "oracle",
                                        pad_to=len(cfgs["chip"]["chip_area"]),
                                        cfgs=cfgs, mode=mode)
        lkey, ekey, akey = _MODE_KEYS[mode]
        W = len(self.workloads)
        pad_n = len(cfgs["chip"]["chip_area"])
        lat = np.zeros((pad_n, W))
        en = np.zeros((pad_n, W))
        tw = np.zeros((pad_n, W))
        cfgs = self._shard_cfgs(cfgs)
        for j, wname in enumerate(self.workloads):
            res = batch_evaluate(self._prepared(wname), cfgs, self.calib,
                                 fidelity=self.fidelity)
            lat[:, j] = res[lkey]
            en[:, j] = res[ekey]
            power = res[ekey] * 1e-12 / np.maximum(res[lkey], 1e-30)
            tw[:, j] = res[akey] / np.maximum(power, 1e-30)
        return lat[:n], en[:n], tw[:n]

    def _simulate_exact(self, genomes: np.ndarray, oracle: bool = False,
                        pad_to: Optional[int] = None, cfgs=None,
                        mode: Optional[str] = None):
        """Exact scoring.  Default (``exact_mapper="batched"``): the
        compile-free path — one fused batched-mapping + plan-execution
        dispatch per workload, placements bitwise equal to ``map_graph``.
        ``exact_mapper="python"`` compiles per candidate with the real
        Python mapper instead; ``oracle=True`` additionally walks the
        per-candidate ``ChipSim``.  Unmappable (genome, workload) pairs
        score inf latency/energy on every path.  ``cfgs``, when given,
        is the caller's already-built (``pad_to``-row) config stack for
        these genomes, so ``evaluate()`` misses don't stack twice.
        ``mode`` selects the §3.2 schedule mode (plans are emitted with
        it, so every exact path scores the same steady state)."""
        from ..compiler.mapper import UnmappableError, map_graph
        from ..compiler.pipeline import lower_plan
        from ..compiler.schedule import emit_schedule
        from ..simulator.batched import simulate_plans
        from ..simulator.orchestrator import simulate as oracle_simulate

        mode = self.mode if mode is None else mode
        genomes = np.asarray(genomes, np.int64).reshape(-1, GENOME_LEN)
        n, W = len(genomes), len(self.workloads)
        if not oracle and self.exact_mapper == "batched":
            return self._simulate_exact_fused(genomes, pad_to, cfgs, mode,
                                              lean=self.backend == "exact")
        lkey, ekey, akey = _MODE_KEYS[mode]
        chips = [decode(g, f"x{i}") for i, g in enumerate(genomes)]
        lat = np.full((n, W), np.inf)
        en = np.full((n, W), np.inf)
        tw = np.zeros((n, W))
        for j, wname in enumerate(self.workloads):
            g = _prepared_graph(wname, self.aggressive_int4,
                                self.enable_fusion)
            plans, rows = [], []
            for i, chip in enumerate(chips):
                try:
                    placements = map_graph(g, chip, self.calib)
                except UnmappableError:
                    continue
                plans.append(emit_schedule(g, placements, mode=mode))
                rows.append(i)
            if not rows:
                continue
            if oracle:
                for i, plan in zip(rows, plans):
                    r = oracle_simulate(chips[i], plan, self.calib,
                                        fidelity=self.fidelity)
                    if mode == "throughput":
                        lat[i, j] = r.pipeline["ii_s"]
                        en[i, j] = r.pipeline["energy_ss_pj"]
                        a = r.pipeline["achieved_tops_ss"]
                    else:
                        lat[i, j], en[i, j] = r.latency_s, r.energy_pj
                        a = r.achieved_tops
                    power = en[i, j] * 1e-12 / max(lat[i, j], 1e-30)
                    tw[i, j] = a / max(power, 1e-30)
                continue
            sel = list(rows)
            tables = [lower_plan(p, chips[i].num_tiles)
                      for i, p in zip(rows, plans)]
            if pad_to is not None and len(sel) < pad_to:
                # repeat row 0 so the jitted executor keeps a stable batch
                # shape across calls (compile once per (bucket, max_ops))
                reps = pad_to - len(sel)
                sel = sel + [rows[0]] * reps
                tables = tables + [tables[0]] * reps
            res = simulate_plans([chips[i] for i in sel], tables, self.calib,
                                 fidelity=self.fidelity)
            for r, i in enumerate(rows):
                lat[i, j] = res[lkey][r]
                en[i, j] = res[ekey][r]
                power = res[ekey][r] * 1e-12 / max(res[lkey][r], 1e-30)
                tw[i, j] = res[akey][r] / max(power, 1e-30)
        return lat, en, tw

    def _simulate_exact_fused(self, genomes: np.ndarray,
                              pad_to: Optional[int] = None, cfgs=None,
                              mode: Optional[str] = None,
                              lean: bool = False):
        """The compile-free exact path: per workload, ONE fused
        batched-mapper + plan-executor dispatch over all candidates,
        sharded over the candidate axis when the engine shards.
        ``lean=True`` (the ``"exact"`` search backend) dispatches the
        class-specialized single-scan search kernel
        (``compiler.batched_mapper.search_and_simulate``); ``lean=False``
        (the ``"batched"`` backend and ``rescore()``) keeps the two-scan
        ``map_and_simulate`` dispatch — metrics are bitwise identical
        either way (and to the per-candidate compile path).  The
        per-workload compiler passes 1-2 + tensorization come from the
        process-wide ``prepared_workload`` cache (``self._prepared``) —
        nothing runs per (workload, candidate) on the host."""
        from ..compiler.batched_mapper import (map_and_simulate,
                                               place_configs,
                                               search_population)

        mode = self.mode if mode is None else mode
        lkey, ekey, akey = _MODE_KEYS[mode]
        n, W = len(genomes), len(self.workloads)
        lat = np.full((n, W), np.inf)
        en = np.full((n, W), np.inf)
        tw = np.zeros((n, W))
        # pad to the jit bucket (a mesh-size multiple under sharding) by
        # repeating row 0, so shapes stay stable and shards stay even
        pad = pad_to if pad_to is not None else self._pad_size(n)
        if cfgs is None:
            cfgs = self._configs(genomes)
            if pad > n:
                sel = np.concatenate([np.arange(n),
                                      np.zeros(pad - n, np.int64)])
                cfgs = self._take(cfgs, sel)
        # device placement (and sharding) once, not once per workload
        placed = place_configs(cfgs, self._sharding)
        if lean:
            # the search grade: ONE class-specialized dispatch scores the
            # batch on every workload (no per-workload host round trips),
            # fetching only the mode's metric columns
            results = search_population(
                [self._prepared(w) for w in self.workloads], cfgs,
                self.calib, placed=placed, mode=mode,
                out_keys=(lkey, ekey, akey), fidelity=self.fidelity)
        else:
            results = [map_and_simulate(self._prepared(w), cfgs, self.calib,
                                        placed=placed, mode=mode,
                                        fidelity=self.fidelity)
                       for w in self.workloads]
        for j, res in enumerate(results):
            ok = res["ok"][:n]
            l, e = res[lkey][:n], res[ekey][:n]
            lat[ok, j] = l[ok]
            en[ok, j] = e[ok]
            power = e[ok] * 1e-12 / np.maximum(l[ok], 1e-30)
            tw[ok, j] = res[akey][:n][ok] / np.maximum(power, 1e-30)
        return lat, en, tw

    # ----------------------------------------------------------- score_batch
    def score_batch(self, genomes: np.ndarray,
                    mode: Optional[str] = None) -> Dict[str, np.ndarray]:
        """The reentrant engine core: canonical (or raw) genomes in,
        exact-per-backend metrics out, one fused dispatch per padded
        micro-batch — no cache interaction, no keep predicate, no
        request/hit/miss accounting.  This is what the coalescing
        evaluation service (``repro.serve.dse_service``) drives and what
        ``evaluate()`` composes with the caching policy.

        Pure up to process-global compile caches, the engine's emitted
        shape set (lock-guarded), and the monotonic ``stats.dispatches``
        telemetry counter; concurrent callers get independent, bitwise
        batch-composition-independent results (pinned by
        tests/test_engine.py / tests/test_service.py).

        Returns ``latency``/``energy``/``tops_w`` (N, W) and ``area``
        (N,) arrays (no ``meta``: nothing request-scoped happens here).
        """
        mode = self.mode if mode is None else mode
        if mode not in SCHEDULE_MODES:
            raise ValueError(f"mode {mode!r} not in {SCHEDULE_MODES}")
        genomes = np.asarray(genomes, dtype=np.int64).reshape(-1, GENOME_LEN)
        n = len(genomes)
        cfgs = self._configs(genomes)
        area = np.asarray(cfgs["chip"]["chip_area"], np.float64).copy()
        lat = np.zeros((n, len(self.workloads)))
        en = np.zeros_like(lat)
        tw = np.zeros_like(lat)
        for s in range(0, n, self.batch):
            chunk = np.arange(s, min(s + self.batch, n))
            pad = self._pad_size(len(chunk))
            sel = np.concatenate(
                [chunk, np.full(pad - len(chunk), chunk[0], np.int64)])
            l, e, t = self._simulate(self._take(cfgs, sel), len(chunk),
                                     genomes[sel], mode=mode)
            lat[chunk], en[chunk], tw[chunk] = l, e, t
        return {"latency": lat, "energy": en, "tops_w": tw, "area": area}

    # ------------------------------------------------------------- evaluate
    def evaluate(self, genomes: np.ndarray,
                 keep: Optional[Callable[[np.ndarray], np.ndarray]] = None,
                 mode: Optional[str] = None,
                 canonical: Optional[np.ndarray] = None
                 ) -> Dict[str, np.ndarray]:
        """Score every genome on every workload.

        ``keep(areas) -> (N,) bool`` optionally pre-filters by chip area:
        genomes with ``keep == False`` (and no memoized result) are not
        simulated and come back with inf latency/energy and zero TOPS/W.

        ``mode`` overrides the engine's §3.2 schedule mode for this call
        (``"latency"`` or ``"throughput"``).  In throughput mode the
        ``latency`` column holds the steady-state initiation interval,
        ``energy`` the per-inference steady-state energy, and ``tops_w``
        the TOPS/W at the pipelined rate; memo entries are keyed on
        (mode, genome), so the two modes never cross-contaminate.

        ``canonical`` optionally supplies the rows'
        ``canonical_genomes`` forms when the caller already computed
        them (the device GA loop canonicalizes children on device in the
        same dispatch as the genetics, so memo keys cost it no extra
        host pass).  Must be bitwise equal to
        ``canonical_genomes(genomes)`` — pinned for the device
        canonicalizer by tests/test_ga_device.py.
        """
        mode = self.mode if mode is None else mode
        if mode not in SCHEDULE_MODES:
            raise ValueError(f"mode {mode!r} not in {SCHEDULE_MODES}")
        t0 = time.perf_counter()
        pre = dataclasses.replace(self.stats)
        genomes = np.asarray(genomes, dtype=np.int64).reshape(-1, GENOME_LEN)
        n, W = len(genomes), len(self.workloads)
        lat = np.zeros((n, W))
        en = np.zeros((n, W))
        tw = np.zeros((n, W))
        cfgs = self._configs(genomes)
        area = np.asarray(cfgs["chip"]["chip_area"], np.float64).copy()
        self.stats.requests += n

        tag = mode.encode() + b":"
        canon = canonical_genomes(genomes) if canonical is None else \
            np.asarray(canonical, np.int64).reshape(-1, GENOME_LEN)
        keys = [tag + self._key(g) for g in canon]
        keep_mask = np.ones(n, bool) if keep is None else \
            np.asarray(keep(area), bool)

        miss_idx: List[int] = []
        dup_idx: List[int] = []
        seen_this_call: Dict[bytes, int] = {}
        for i, k in enumerate(keys):
            row = self.store.get(k) if self.memoize else None
            if row is not None:
                lat[i], en[i], tw[i] = row
                self.stats.hits += 1
            elif not keep_mask[i]:
                lat[i] = np.inf
                en[i] = np.inf
                self.stats.skips += 1
            elif self.memoize and k in seen_this_call:
                dup_idx.append(i)       # resolved from the first copy's row
                self.stats.hits += 1
            else:
                seen_this_call[k] = i
                miss_idx.append(i)
                self.stats.misses += 1

        # simulate misses in _bucket-padded batches (bounded jit shapes)
        nonfinite = 0
        for s in range(0, len(miss_idx), self.batch):
            chunk = miss_idx[s:s + self.batch]
            pad = self._pad_size(len(chunk))
            sel = chunk + [chunk[0]] * (pad - len(chunk))
            l, e, t = self._simulate(self._take(cfgs, np.asarray(sel)),
                                     len(chunk), genomes[np.asarray(sel)],
                                     mode=mode)
            for r, i in enumerate(chunk):
                # Guard fresh rows before they can reach the memo/store/
                # Pareto front.  Unmappable candidates legitimately score
                # (inf, inf, 0); NaN anywhere — or a non-finite TOPS/W —
                # is cost-model corruption and must not be cached.
                if (np.isnan(l[r]).any() or np.isnan(e[r]).any()
                        or np.isnan(t[r]).any() or np.isinf(t[r]).any()):
                    nonfinite += 1
                    if self.nonfinite == "raise":
                        raise NonFiniteMetricsError(
                            canon[i], mode, (l[r], e[r], t[r]))
                    # skip: score like an area-filtered candidate (-inf
                    # fitness downstream), never memoize the bad row
                    lat[i], en[i] = np.inf, np.inf
                    tw[i] = 0.0
                    continue
                lat[i], en[i], tw[i] = l[r], e[r], t[r]
                if self.memoize:
                    self.store.put(
                        keys[i], (l[r].copy(), e[r].copy(), t[r].copy()))
        # duplicates copy their first occurrence's output row directly —
        # never via the store, whose LRU bound may already have evicted the
        # entry within a single paper-scale call
        for i in dup_idx:
            j = seen_this_call[keys[i]]
            lat[i], en[i], tw[i] = lat[j], en[j], tw[j]
        self.stats.eval_seconds += time.perf_counter() - t0
        meta = {"meta_version": META_VERSION, "backend": self.backend,
                "mode": mode, "fidelity": self.fidelity, "requests": n,
                "hits": self.stats.hits - pre.hits,
                "misses": self.stats.misses - pre.misses,
                "skips": self.stats.skips - pre.skips,
                "nonfinite": nonfinite,
                "dispatches": self.stats.dispatches - pre.dispatches}
        meta["hit_rate"] = meta["hits"] / max(n, 1)
        return {"latency": lat, "energy": en, "tops_w": tw, "area": area,
                "meta": meta}

    def rescore(self, genomes: np.ndarray, oracle: bool = False,
                mode: Optional[str] = None) -> Dict[str, np.ndarray]:
        """Exact re-scoring of finalists through the engine's exact
        mapper — by default the compile-free batched Eq. 1-3 pass fused
        with the batched plan executor (bitwise ``map_graph`` placements,
        no per-candidate compile); ``exact_mapper="python"`` compiles
        per candidate instead, and ``oracle=True`` walks the Python
        ChipSim.  Bypasses the memo — results are exact regardless of
        this engine's search backend.  ``mode`` overrides the engine's
        schedule mode (throughput: II / steady-state energy / steady-state
        TOPS/W in the latency/energy/tops_w columns)."""
        mode = self.mode if mode is None else mode
        if mode not in SCHEDULE_MODES:
            raise ValueError(f"mode {mode!r} not in {SCHEDULE_MODES}")
        genomes = np.asarray(genomes, dtype=np.int64).reshape(-1, GENOME_LEN)
        lat, en, tw = self._simulate_exact(genomes, oracle=oracle, mode=mode)
        mapper = "python" if oracle else self.exact_mapper
        return {"latency": lat, "energy": en, "tops_w": tw,
                "area": self.areas(genomes),
                "meta": {"meta_version": META_VERSION,
                         "backend": "oracle" if oracle else "batched",
                         "mapper": mapper, "mode": mode,
                         "fidelity": self.fidelity,
                         "requests": len(genomes), "hits": 0,
                         "misses": len(genomes), "skips": 0,
                         "hit_rate": 0.0}}

    # --------------------------------------------------- device-memo sync
    def export_memo(self, mode: Optional[str] = None
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """Bulk view of the store's in-memory tier for one schedule mode:
        ``(canon (N, GENOME_LEN) int64, rows (N, 3, W) float64)`` in LRU
        order — what ``dse.device_memo.memo_from_store`` preloads into
        the device-resident table at a seed boundary.

        Only the enumerable LRU tier exports (a persistent sqlite back
        tier is content-addressed — its keys are digests, not genomes —
        so its entries surface here only after promotion into the
        front); an engine whose store has no in-memory tier exports
        empty.  No stats or recency side effects.

        Bulk views are cached per mode against the tier's mutation
        stamp (accepted puts + evictions), so back-to-back exports over
        an unchanged store — a pipeline replaying against a warm
        persistent store — skip the dict walk.  Callers must treat the
        returned arrays as read-only.
        """
        mode = self.mode if mode is None else mode
        if mode not in SCHEDULE_MODES:
            raise ValueError(f"mode {mode!r} not in {SCHEDULE_MODES}")
        stamp = self._memo_stamp()
        cached = self._export_cache.get(mode)
        if stamp is not None and cached is not None and cached[0] == stamp:
            return cached[1]
        tag = mode.encode() + b":"
        W = len(self.workloads)
        d = self.store.lru_dict()
        genomes: List[np.ndarray] = []
        rows: List[np.ndarray] = []
        for k, row in (list(d.items()) if d else ()):
            if not k.startswith(tag):
                continue
            genomes.append(np.frombuffer(k[len(tag):], np.int64))
            rows.append(np.stack([np.asarray(a, np.float64) for a in row]))
        if not genomes:
            out = (np.zeros((0, GENOME_LEN), np.int64),
                   np.zeros((0, 3, W), np.float64))
        else:
            out = (np.asarray(genomes, np.int64),
                   np.asarray(rows, np.float64))
        if stamp is not None:
            self._export_cache[mode] = (stamp, out)
        return out

    def _memo_stamp(self) -> Optional[tuple]:
        """Mutation stamp of the store's enumerable LRU tier: changes
        exactly when the tier's *membership* changes (accepted puts and
        evictions; recency reorders don't count — export order is not
        load-bearing, every consumer is order-independent).  None when
        the tier keeps no stats, which disables the export cache."""
        front = getattr(self.store, "front", self.store)
        stats = getattr(front, "stats", None)
        if stats is None:
            return None
        return (id(front), stats.puts, stats.evictions)

    def import_memo(self, canon: np.ndarray, rows: np.ndarray,
                    mode: Optional[str] = None) -> int:
        """Offer drained device-memo entries to the host store
        (put-if-absent; a persistent tier makes them durable).  ``canon``:
        (N, GENOME_LEN) canonical genomes; ``rows``: (N, 3, W) metric
        rows, bitwise the values ``evaluate`` would have stored.  Returns
        the number of rows offered."""
        mode = self.mode if mode is None else mode
        if mode not in SCHEDULE_MODES:
            raise ValueError(f"mode {mode!r} not in {SCHEDULE_MODES}")
        tag = mode.encode() + b":"
        canon = np.asarray(canon, np.int64).reshape(-1, GENOME_LEN)
        rows = np.asarray(rows, np.float64)
        if rows.shape[:1] != (len(canon),) or rows.ndim != 3 \
                or rows.shape[1] != 3:
            raise ValueError(f"rows shape {rows.shape} does not match "
                             f"{len(canon)} genomes x (3, W)")
        for g, r in zip(canon, rows):
            self.store.put(tag + self._key(g),
                           (r[0].copy(), r[1].copy(), r[2].copy()))
        return len(canon)

    def reserve_shapes(self, max_batch: int = 64) -> None:
        """Pre-register the search-loop batch buckets in the emitted-shape
        set WITHOUT compiling, so ``_pad_size`` always pads minimally
        instead of reusing a previously-minted larger shape (up to 1.5x
        wasted rows per dispatch).  Each shape still jit-compiles lazily
        on first use — the device GA loop calls this because its jits are
        process-global and its per-generation miss counts sweep the whole
        bucket range; ``warmup()`` remains the compile-ahead variant."""
        for b in range(16, _bucket(max_batch) + 4, 4):
            self._pad_size(b)

    def warmup(self, buckets: Sequence[int] = tuple(range(16, 68, 4))
               ) -> None:
        """Pre-compile the jitted evaluator for the search-loop batch
        shapes (miss batches up to a GA-population-sized 64), so loop
        latency is steady-state from the first generation and padding is
        always minimal.  One-off larger batches (e.g. a whole sweep)
        compile once on first use, exactly as the pre-refactor path did."""
        g = np.zeros((1, GENOME_LEN), np.int64)
        cfgs = self._configs(g)
        for b in sorted({self._pad_size(b) for b in buckets}):
            self._simulate(self._take(cfgs, np.zeros(b, np.int64)), 1,
                           np.repeat(g, b, axis=0))

    def areas(self, genomes: np.ndarray) -> np.ndarray:
        """Chip areas only — no simulation, no cache interaction.  The
        scalar decode path wins below ~batch 16 (numpy dispatch overhead),
        and both paths are bitwise identical, so pick by batch size."""
        genomes = np.asarray(genomes, dtype=np.int64).reshape(-1, GENOME_LEN)
        if self.vectorized and len(genomes) >= 16:
            return genome_areas(genomes, self.calib)
        from ..simulator.area import chip_area
        return np.asarray([chip_area(decode(g), self.calib)
                           for g in genomes])
