"""Score (genome, workload) pairs with the frozen numpy oracle.

Per pair: decode the genome into a chip, build the workload graph, run
precision assignment and fusion, map it with ``map_graph``, emit the
schedule in the configuration's mode and walk it with ``ChipSim`` at the
configuration's fidelity.  Latency mode reports (latency s, energy pJ,
TOPS/W); throughput mode reports (initiation interval s, steady-state
energy pJ, steady-state TOPS/W).  An unmappable pair scores (inf, inf, 0).
"""
from __future__ import annotations

import copy
import functools
from typing import Sequence, Tuple

import numpy as np

from .mosaic.calibrate.asap7 import DEFAULT_CALIB
from .mosaic.compiler.fusion import fuse
from .mosaic.compiler.mapper import UnmappableError, map_graph
from .mosaic.compiler.precision import assign_precision
from .mosaic.compiler.schedule import emit_schedule
from .mosaic.dse.encoding import decode
from .mosaic.simulator.area import chip_area
from .mosaic.simulator.orchestrator import simulate
from .mosaic.workloads.suite import build

__all__ = ["score_pair", "score_rows", "area"]


@functools.lru_cache(maxsize=64)
def _graph(name: str):
    g = copy.deepcopy(build(name))
    return fuse(assign_precision(g, aggressive_int4=False))


def score_pair(genome: Sequence[int], workload: str, mode: str,
               fidelity: str) -> Tuple[float, float, float]:
    """(latency-or-II, energy, TOPS/W) of one genome on one workload."""
    chip = decode(np.asarray(genome, np.int64))
    g = _graph(workload)
    try:
        placements = map_graph(g, chip, DEFAULT_CALIB)
    except UnmappableError:
        return float("inf"), float("inf"), 0.0
    r = simulate(chip, emit_schedule(g, placements, mode=mode),
                 DEFAULT_CALIB, fidelity=fidelity)
    if mode == "throughput":
        lat, en = r.pipeline["ii_s"], r.pipeline["energy_ss_pj"]
        ops = r.pipeline["achieved_tops_ss"]
    else:
        lat, en, ops = r.latency_s, r.energy_pj, r.achieved_tops
    power = en * 1e-12 / max(lat, 1e-30)
    return float(lat), float(en), float(ops / max(power, 1e-30))


def score_rows(genome: Sequence[int], workloads: Sequence[str], mode: str,
               fidelity: str) -> np.ndarray:
    """(3, W) rows (latency, energy, TOPS/W) of one genome."""
    return np.asarray([score_pair(genome, w, mode, fidelity)
                       for w in workloads], np.float64).T


def area(genome: Sequence[int]) -> float:
    """Chip area in mm^2 under the 7 nm calibration."""
    return float(chip_area(decode(np.asarray(genome, np.int64)),
                           DEFAULT_CALIB))
