"""The comparison that decides ``correct``.

What the timed path produced for a sample of (genome, workload) pairs is
held against the frozen numpy oracle (``bench/reference``), scored in
worker processes after the window has closed.  Per pair the error is the
largest relative error of its three metrics (latency or initiation
interval, energy, TOPS/W); a pair that one side finds unmappable and the
other does not reads infinite.  The numbers compared:

``off_share``     share of pairs whose error exceeds ``PAIR_TOL``
``area_err_max``  the largest relative error of the chip area the device
                  computed (cells whose timed path computes it)

A driver adds the numbers that its window's own counts give
(``Window.checked``: ``stale_share``, ``repeat_share``).  Each is held
to the limit in ``bench/checks/<cell>.json``.  The largest pair error is
logged but not compared: a near-tie in the mapper's argmin, which the
chip's emulated float64 resolves the other way on a few pairs, moves a
pair by up to a few percent, as much as the float32 control moves its
worst pair; what the control moves is every pair.
"""
from __future__ import annotations

import dataclasses
import multiprocessing
import os
from concurrent.futures import ProcessPoolExecutor
from typing import Dict, List, Optional

import numpy as np

__all__ = ["Sample", "PAIR_TOL", "pair_errors", "numbers", "reference",
           "judge", "off_pairs"]

# A pair within this relative error agrees with the oracle: float64 on the
# chip is emulated in float32 pairs and reads ~1e-14 after ~1000 chained
# scan steps, float32 reads ~1e-7 and more.
PAIR_TOL = 1e-9


@dataclasses.dataclass
class Sample:
    """What the timed path produced for the sampled genomes."""

    genomes: np.ndarray          # (N, GENOME_LEN)
    workloads: List[str]
    mode: str
    fidelity: str
    rows: np.ndarray             # (N, 3, W) latency, energy, TOPS/W
    area: Optional[np.ndarray] = None   # (N,) device-computed areas


def _rel(dev: np.ndarray, ref: np.ndarray) -> np.ndarray:
    dev, ref = np.asarray(dev, np.float64), np.asarray(ref, np.float64)
    err = np.zeros(np.broadcast(dev, ref).shape)
    fin = np.isfinite(dev) & np.isfinite(ref)
    err[fin] = np.abs(dev[fin] - ref[fin]) / np.maximum(np.abs(ref[fin]),
                                                        1e-300)
    err[np.isfinite(dev) != np.isfinite(ref)] = np.inf
    err[np.isnan(dev) | np.isnan(ref)] = np.inf
    return err


def pair_errors(rows: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """(N, W) largest relative error over the three metrics of a pair."""
    return _rel(rows, ref).max(axis=1)


def numbers(sample: Sample, ref_rows: np.ndarray,
            ref_area: Optional[np.ndarray]) -> Dict[str, float]:
    err = pair_errors(sample.rows, ref_rows)
    # pairs that both sides find unmappable carry nothing to compare
    live = (np.isfinite(ref_rows[:, 0]) | np.isfinite(sample.rows[:, 0])
            | ~np.isfinite(err))
    out = {"off_share": float((err[live] > PAIR_TOL).mean())
           if live.any() else 1.0}
    if sample.area is not None:
        out["area_err_max"] = float(_rel(sample.area, ref_area).max())
    return out


def off_pairs(sample: Sample, ref_rows: np.ndarray, top: int = 12):
    """The pairs beyond ``PAIR_TOL``, largest first: (workload, sample
    row, error, which metrics), for the log."""
    rel = _rel(sample.rows, ref_rows)                 # (N, 3, W)
    err = rel.max(axis=1)
    out = []
    for i, j in zip(*np.nonzero(err > PAIR_TOL)):
        which = "".join("let"[m] for m in range(3) if rel[i, m, j] > PAIR_TOL)
        out.append((sample.workloads[j], int(i), float(err[i, j]), which))
    return sorted(out, key=lambda t: -t[2])[:top]


def _score(args):
    from reference import oracle
    genome, workloads, mode, fidelity = args
    return oracle.score_rows(genome, workloads, mode, fidelity), \
        oracle.area(genome)


def reference(sample: Sample, workers: Optional[int] = None):
    """Oracle rows (N, 3, W) and areas (N,) for the sample, one genome per
    task over ``workers`` processes (spawned: they import no JAX and never
    touch the chip)."""
    tasks = [(g.tolist(), list(sample.workloads), sample.mode,
              sample.fidelity) for g in np.asarray(sample.genomes)]
    workers = workers or max(1, min(8, (os.cpu_count() or 2) - 1,
                                    len(tasks)))
    if workers == 1:
        out = [_score(t) for t in tasks]
    else:
        ctx = multiprocessing.get_context("spawn")
        with ProcessPoolExecutor(max_workers=workers, mp_context=ctx) as ex:
            out = list(ex.map(_score, tasks))
    return (np.stack([o[0] for o in out]),
            np.asarray([o[1] for o in out], np.float64))


def judge(values: Dict[str, float], limits: Dict[str, float]
          ) -> Dict[str, Dict[str, float]]:
    """Each compared number beside its limit; a number without a limit is
    an error in the cell's checks file."""
    missing = sorted(set(values) - set(limits))
    if missing:
        raise KeyError(f"no limit for {missing}")
    return {k: {"value": values[k], "limit": float(limits[k]),
                "ok": bool(values[k] <= float(limits[k]))}
            for k in sorted(values)}
