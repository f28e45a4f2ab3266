"""Stage 1 — multi-seed stratified random sweep (paper §3.5, §4.5).

Strata = area bracket x architecture family ({Homo, Hetero-BL,
Hetero-BLS}).  Per seed, a genome pool is sampled per family, assigned to
area brackets, and every in-bracket config is scored on every workload
with the jitted batch evaluator.  Per-workload savings are computed
against the *best homogeneous design at the same bracket* found in the
same sweep (the iso-area baseline of Eq. 8).

Paper scale is 3 seeds x ~980 K samples; ``samples_per_family`` keeps CPU
runs tractable and ``--paper-scale`` in the benchmarks restores the full
counts (DESIGN.md §2 "assumptions changed").
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from .. import telemetry
from ..calibrate.asap7 import CalibrationTable, DEFAULT_CALIB
from ..workloads import build
from .batch_eval import batch_evaluate, prepare_configs, prepare_workload
from .encoding import FAMILIES, decode, sample_in_bracket
from .api import EngineConfig
from .engine import EvalEngine, genome_area_fn
from .objective import ALPHA, AREA_BRACKETS, area_bracket

__all__ = ["SweepResult", "run_sweep", "run_sweeps", "evaluate_genomes",
           "evaluate_genomes_reference"]


@dataclasses.dataclass
class SweepResult:
    """All sampled configs of one seed, plus per-workload metrics."""

    seed: int
    workloads: List[str]
    genomes: np.ndarray          # (N, GENOME_LEN)
    family: np.ndarray           # (N,) index into FAMILIES
    bracket: np.ndarray          # (N,) mm^2 bracket value
    area: np.ndarray             # (N,)
    latency: np.ndarray          # (N, W) seconds
    energy: np.ndarray           # (N, W) pJ
    tops_w: np.ndarray           # (N, W)

    def valid_mask(self) -> np.ndarray:
        ok = np.isfinite(self.latency).all(axis=1) & (self.latency > 0).all(axis=1)
        return ok & np.isfinite(self.energy).all(axis=1)

    def homo_baseline(self) -> Dict[float, np.ndarray]:
        """Per bracket: per-workload minimum energy over valid Homo configs
        with area <= bracket.  Cumulative over brackets because the largest
        single-type homo chip tops out near ~220 mm^2 on the paper's knob
        grid — at 400/800 mm^2 the baseline is "the biggest homo chip"."""
        out: Dict[float, np.ndarray] = {}
        valid = self.valid_mask()
        best: Optional[np.ndarray] = None
        for b in AREA_BRACKETS:
            sel = valid & (self.family == 0) & (self.bracket == b)
            if sel.any():
                cur = self.energy[sel].min(axis=0)
                best = cur if best is None else np.minimum(best, cur)
            if best is not None:
                out[b] = best
        return out

    def savings(self) -> np.ndarray:
        """(N, W) iso-area fractional savings vs the homo baseline; NaN when
        the bracket has no homogeneous baseline."""
        base = self.homo_baseline()
        sav = np.full_like(self.energy, np.nan)
        for b, e_h in base.items():
            sel = self.bracket == b
            sav[sel] = (e_h[None, :] - self.energy[sel]) / np.maximum(e_h, 1e-30)
        sav[~self.valid_mask()] = np.nan
        return sav

    def fitness(self, alpha: float = ALPHA) -> np.ndarray:
        """(N,) Eq. 8 fitness (NaN-safe; invalid configs get -inf)."""
        sav = self.savings()
        mean_sav = np.nanmean(sav, axis=1)
        peak_tw = np.nanmax(np.where(np.isfinite(self.tops_w), self.tops_w, np.nan),
                            axis=1)
        max_tw = np.nanmax(peak_tw) if np.isfinite(peak_tw).any() else 1.0
        fit = mean_sav + alpha * peak_tw / max(max_tw, 1e-30)
        fit[~np.isfinite(fit)] = -np.inf
        return fit


def evaluate_genomes(genomes: np.ndarray, workloads: Sequence[str],
                     calib: CalibrationTable = DEFAULT_CALIB,
                     batch: int = 1024) -> Dict[str, np.ndarray]:
    """Score genomes on every workload (one-shot ``EvalEngine``).

    Search loops should hold their own engine so the genome memo and
    workload-prep cache persist across calls; this wrapper exists for
    single-batch scoring and backwards compatibility."""
    return EvalEngine(workloads, calib,
                      config=EngineConfig(batch=batch)).evaluate(genomes)


def evaluate_genomes_reference(genomes: np.ndarray, workloads: Sequence[str],
                               calib: CalibrationTable = DEFAULT_CALIB,
                               batch: int = 1024) -> Dict[str, np.ndarray]:
    """Pre-engine host loop, kept verbatim as the parity/benchmark
    baseline: re-prepares every workload per batch and decodes every
    genome into Python ChipConfig objects."""
    chips = [decode(g, f"g{i}") for i, g in enumerate(genomes)]
    n, w = len(chips), len(workloads)
    lat = np.zeros((n, w))
    en = np.zeros((n, w))
    tw = np.zeros((n, w))
    area = np.zeros(n)
    for s in range(0, n, batch):
        cfgs = prepare_configs(chips[s:s + batch], calib)
        area[s:s + batch] = cfgs["chip"]["chip_area"]
        for j, wname in enumerate(workloads):
            ws = prepare_workload(build(wname))
            res = batch_evaluate(ws, cfgs, calib)
            lat[s:s + batch, j] = res["latency_s"]
            en[s:s + batch, j] = res["energy_pj"]
            power = res["energy_pj"] * 1e-12 / np.maximum(res["latency_s"], 1e-30)
            tw[s:s + batch, j] = res["achieved_tops"] / np.maximum(power, 1e-30)
    return {"latency": lat, "energy": en, "tops_w": tw, "area": area}


def run_sweep(workloads: Sequence[str], samples_per_stratum: int = 64,
              seed: int = 0, calib: CalibrationTable = DEFAULT_CALIB,
              brackets: Sequence[float] = AREA_BRACKETS,
              verbose: bool = False,
              engine: Optional[EvalEngine] = None,
              exact: bool = False) -> SweepResult:
    """One seed of the stratified sweep (strata = bracket x family).

    Pass a shared ``engine`` to reuse its caches across seeds and into
    the downstream GA refinement (repeated genomes are free).  The
    engine's §3.2 schedule mode flows through unchanged: with
    ``EvalEngine(..., mode="throughput")`` the latency/energy matrices
    hold the pipelined steady state (II, energy per inference), so the
    same sweep ranks serving-deployment designs — see
    ``objective.serving_fitness`` and ``examples/serve_lm.py --dse``.

    ``exact=True`` (only meaningful without a shared ``engine``) scores
    the sweep through the exact search backend
    (``EvalEngine(backend="exact")``): every metric matrix — and hence
    the homogeneous baselines the GA's Eq. 8 fitness is measured
    against — holds exact fused-mapper numbers instead of the in-scan
    approximate mapping's.

    The stratified sampler scores its repair steps with
    ``genome_area_fn`` (the host Eq. 7 tables, bitwise ``chip_area``),
    whatever the engine's ``vectorized`` setting: that setting chooses
    how ``engine.evaluate`` stacks configs, not how strata are drawn."""
    engine = (engine.check_workloads(workloads, calib)
              if engine is not None
              else EvalEngine(workloads, calib, config=EngineConfig(
                  backend="exact" if exact else "scan")))
    rng = np.random.default_rng(seed)
    area_fn = genome_area_fn(engine.calib)
    genomes_all, fam_all = [], []
    with telemetry.span("sweep.sample"):
        for fi, fam in enumerate(FAMILIES):
            for b in brackets:
                g = sample_in_bracket(rng, samples_per_stratum, fam, b,
                                      area_fn)
                genomes_all.append(g)
                fam_all.append(np.full(len(g), fi))
    genomes = np.concatenate(genomes_all)
    family = np.concatenate(fam_all)

    t0 = time.time()
    m = engine.evaluate(genomes)
    bracket = np.array([area_bracket(a) for a in m["area"]])
    if verbose:
        print(f"[sweep seed {seed}] {len(genomes)} configs x "
              f"{len(workloads)} workloads in {time.time() - t0:.1f}s "
              f"(cache hit rate {engine.stats.hit_rate():.0%})")
    return SweepResult(seed=seed, workloads=list(workloads), genomes=genomes,
                       family=family, bracket=bracket, area=m["area"],
                       latency=m["latency"], energy=m["energy"],
                       tops_w=m["tops_w"])


def run_sweeps(workloads: Sequence[str], seeds: Sequence[int] = (0, 1, 2),
               samples_per_stratum: int = 64,
               calib: CalibrationTable = DEFAULT_CALIB,
               brackets: Sequence[float] = AREA_BRACKETS,
               verbose: bool = False,
               engine: Optional[EvalEngine] = None,
               exact: bool = False) -> Dict[int, SweepResult]:
    """The paper's multi-seed Stage 1: one stratified sweep per seed,
    sharing one engine (and hence one memo/store — repeated genomes
    across seeds are free).  Returns ``{seed: SweepResult}`` in seed
    order; ``dse.pipeline.run_pipeline`` is the fused Stage-1+2+merge
    frontend over this."""
    engine = (engine.check_workloads(workloads, calib)
              if engine is not None
              else EvalEngine(workloads, calib, config=EngineConfig(
                  backend="exact" if exact else "scan")))
    return {s: run_sweep(workloads, samples_per_stratum, seed=s, calib=calib,
                         brackets=brackets, verbose=verbose, engine=engine)
            for s in seeds}
