"""Backend-neutral cost formulas shared by every simulator implementation.

One set of calibrated per-module models (paper §3.3.1, Eqs. 2/4-6) written
against an array namespace ``xp`` so the same code serves three backends:

* ``xp = numpy`` — the Python reference oracle (``TileSim`` / ``ChipSim``),
  evaluating one (op, tile) pair at a time on float64 scalars;
* ``xp = jax.numpy`` under ``vmap``/``jit`` — the batched plan executor
  (``simulator.batched``) and the in-scan mapping evaluator
  (``dse.batch_eval``), evaluating (op, MAX_TILES) lanes at once.

Because all backends execute literally the same arithmetic (same formulas,
same operation order, float64 throughout), they cannot drift apart: a cost
edit lands in every simulator at once and the parity suites
(tests/test_batched_parity.py, tests/test_batch_eval.py) only have to
guard the *orchestration*, not the math.

This module also owns the activation-cache semantics (§3.3.4): a per-tile
FIFO bounded in both bytes (``CACHE_FRAC`` of SRAM) and entries
(``ACT_CACHE_SLOTS``).  ``ActivationCache`` is the Python reference;
``simulator.batched.fifo_insert`` is the array mirror, pinned bitwise by
the parity suite.

No jax import happens here — ``xp`` is always passed in, so the reference
simulator stays importable without touching XLA.
"""
from __future__ import annotations

import collections
import functools
from typing import Dict, Tuple

import numpy as np

from ..arch import Dataflow, Engine, MAX_TILES, Sparsity
from ..calibrate.asap7 import CalibrationTable
from ..ir import OpClass, OpType, PRECISION_BYTES

__all__ = [
    "CACHE_FRAC", "ACT_CACHE_SLOTS", "ACC_BYTES", "DSP_OPS_PER_ELEM",
    "DSP_OPS_TABLE", "SFU_NEED", "TILE_COST_KEYS", "OP_COST_KEYS",
    "COST_MODEL_VERSION", "FIDELITIES", "MAX_DRAM_CHANNELS", "MAX_LINKS",
    "CostModel", "cost_model", "ActivationCache",
    "noc_transfer_seconds", "noc_transfer_energy_pj", "split_op_fields",
    "grid_dims", "xy_route_link_mask", "dram_channel_one_hot",
    "pipeline_bounds", "steady_state_energy", "div_exact",
]

def div_exact(xp, a, b):
    """``a / b``, exact whenever the quotient is an integer — what every
    ``ceil``/``floor`` of a quotient below relies on (``floor_divide`` is
    exact on every backend).  IEEE division
    (math, numpy, XLA:CPU) is correctly rounded, so an integer quotient
    comes out exact and this is ``a / b``.  The TPU emulates float64 with
    float32 pairs and its division is not correctly rounded: 4096 / 64
    may come out a hair off 64, and a ceil or floor of it moves a tile
    count by one.  Where ``r * b == a`` for the integer ``r`` nearest the
    quotient, the quotient is ``r`` and is returned as such.  (``r`` is
    ``floor(q + 0.5)``: the TPU's float64 ``round`` is off by one above
    2**22; its ``floor`` is exact.)"""
    q = a / b
    if xp.__name__ != "jax.numpy":      # the host oracle: left as it was
        return q
    r = xp.floor(q + 0.5)
    return xp.where(r * b == a, r, q)


# Version tag of the cost formulas below.  The persistent DSE result store
# (``dse.store``) folds this into every content-addressed key, so bumping
# it invalidates all previously accumulated metrics at once — REQUIRED
# whenever an edit in this module (or in the mapping/orchestration
# semantics it feeds) changes any metric bit.  Format: "<pr>.<rev>".
COST_MODEL_VERSION = "9.0"

# Throughput-II fidelity tiers shared by every execution surface:
# ``aggregate`` keeps the historical one-shared-link NoC / one-channel
# DRAM bounds; ``link`` adds per-link XY-routed NoC occupancy on the tile
# grid and per-channel (address-interleaved) DRAM queues on top.
FIDELITIES = ("aggregate", "link")

# Fixed per-channel DRAM queue width of the link-fidelity tier.  Chips
# declare ``dram_channels`` in [1, MAX_DRAM_CHANNELS]; unused channel
# lanes stay zero so the vectors keep a static shape under jit.
MAX_DRAM_CHANNELS = 8

# Link-occupancy vector width: one horizontal link (to the right of each
# grid position) + one vertical link (below each grid position) on the
# largest admissible tile grid.  Positions outside a chip's actual
# ``grid_w x grid_h`` footprint never match a route and stay zero.
MAX_LINKS = 2 * MAX_TILES

# fraction of per-tile SRAM reserved for the activation cache (§3.3.4)
CACHE_FRAC = 0.25
# tag-array depth of the activation cache: at most this many outputs are
# tracked per tile, evicted FIFO (hardware: an 8-way tag RAM)
ACT_CACHE_SLOTS = 8

# Accumulator width (partial sums) per input precision index.
ACC_BYTES = (4.0, 4.0, 4.0, 4.0, 4.0)
_ACC = ACC_BYTES[0]

_BURST = 64.0  # DRAM burst alignment (bytes)

# Lane-ops each DSP-class operator spends per element (14-instruction SIMD
# ISA of §3.3.1: vadd, vmul, vexp, vreduce, vlut, ...).
DSP_OPS_PER_ELEM: Dict[int, float] = {
    int(OpType.ADD): 1.0,
    int(OpType.MUL): 1.0,
    int(OpType.SOFTMAX): 5.0,      # vmax, vsub+vexp, vreduce, vdiv
    int(OpType.LAYERNORM): 7.0,
    int(OpType.RMSNORM): 5.0,
    int(OpType.GELU): 8.0,         # tanh polynomial
    int(OpType.SILU): 5.0,
    int(OpType.RELU): 1.0,
    int(OpType.SIGMOID): 4.0,
    int(OpType.POOL): 1.0,
    int(OpType.REDUCE): 1.0,
    int(OpType.GATHER): 2.0,       # address gen + move
    int(OpType.SCATTER): 3.0,      # address gen + read-modify-write
    int(OpType.SSM_SCAN): 6.0,     # per-element recurrence work
    int(OpType.ROPE): 4.0,
}

# Dense table indexed by op_type (23 entries; default 2.0 lane-ops).
DSP_OPS_TABLE = np.array(
    [DSP_OPS_PER_ELEM.get(t, 2.0) for t in range(23)], dtype=np.float64)

# SFU-mask bit each special op needs (1 is harmless for non-special ops).
SFU_NEED = np.ones(23, dtype=np.float64)
SFU_NEED[int(OpType.FFT)] = 1.0
SFU_NEED[int(OpType.SNN_LIF)] = 2.0
SFU_NEED[int(OpType.POLY)] = 4.0

# Tile fields every CostModel entry point reads (subset of the SoA config
# stack emitted by ``simulator.batched.stack_chip_configs``).
TILE_COST_KEYS = (
    "exists", "num_macs", "rows", "cols", "engine", "prec_mask", "asym_mac",
    "sparsity", "dataflow", "sram_kb", "dsp_lanes", "dsp_count", "sfu_mask",
    "sfu_parallel", "double_buffer", "pipeline_depth", "clock_hz",
    "sram_bpc", "max_prec",
)

# Operator fields every CostModel entry point reads.
OP_COST_KEYS = (
    "op_type", "op_cls", "macs", "elems", "m", "k", "n", "precision",
    "bytes_in", "bytes_w", "bytes_out", "act_sparsity", "w_sparsity",
    "fft_n", "poly_degree", "snn_timesteps", "seq_len",
)


# =============================================================================
# NoC transfer cost (shared by mapper / orchestrator / batched / batch_eval)
# =============================================================================

def noc_transfer_seconds(xp, nbytes, noc_bpc, hops, base_cycles, ref_clock_hz):
    """Eq. 3 context: ceil(B / B_NoC) + hops * Delta_NoC cycles at the chip
    reference clock."""
    return ((xp.ceil(div_exact(xp, nbytes, noc_bpc)) + hops * base_cycles)
            / ref_clock_hz)


def noc_transfer_energy_pj(xp, nbytes, e_noc_pj_per_byte_hop, hops):
    return nbytes * e_noc_pj_per_byte_hop * hops


# =============================================================================
# link-fidelity tier: XY-routed per-link NoC + per-channel DRAM queues
# =============================================================================

def grid_dims(xp, num_tiles, grid_aspect):
    """(grid_w, grid_h) of the 2D tile layout: width tracks
    ``sqrt(n) * aspect`` (clipped to [1, n]); the last row may be
    partial.  Same float64 arithmetic on both backends."""
    n = xp.maximum(xp.asarray(num_tiles, getattr(xp, "float64")), 1.0)
    w = xp.clip(xp.round(xp.sqrt(n) * grid_aspect), 1.0, n)
    return w, xp.ceil(div_exact(xp, n, w))


def xy_route_link_mask(xp, src, dst, grid_w, grid_h, torus):
    """0/1 occupancy mask over the ``MAX_LINKS`` grid links used by an
    XY route from tile ``src`` to tile ``dst``.

    Tiles are laid out row-major on a ``grid_w x grid_h`` grid.  Link
    ``i < MAX_TILES`` is the horizontal link to the *right* of grid
    position ``i``; link ``MAX_TILES + i`` is the vertical link *below*
    position ``i``.  Links are undirected shared channels — a leftward
    hop occupies the same link as the rightward one.  XY (dimension-
    ordered) routing moves horizontally along the source row first, then
    vertically along the destination column.  On a torus each dimension
    independently takes the wrap-around direction when strictly shorter
    (``2*delta > extent``; ties go the mesh way), using the wrap links at
    the grid edge.  A negative ``src``/``dst`` (no tile) yields an empty
    route.  All inputs broadcast; the link axis is appended last.
    """
    f64 = getattr(xp, "float64")
    links = xp.arange(MAX_TILES, dtype=f64)
    s = xp.asarray(src, f64)[..., None]
    d = xp.asarray(dst, f64)[..., None]
    w = xp.maximum(xp.asarray(grid_w, f64), 1.0)[..., None]
    h = xp.maximum(xp.asarray(grid_h, f64), 1.0)[..., None]
    wrap_ok = xp.asarray(torus, f64)[..., None] > 0
    sr = xp.floor_divide(s, w)
    sc = s - sr * w
    dr = xp.floor_divide(d, w)
    dc = d - dr * w
    lr = xp.floor_divide(links, w)
    lc = links - lr * w
    valid = (s >= 0) & (d >= 0)
    # horizontal segment: along the source row
    cmin = xp.minimum(sc, dc)
    cmax = xp.maximum(sc, dc)
    hwrap = wrap_ok & (2.0 * (cmax - cmin) > w)
    inside_h = (lc >= cmin) & (lc < cmax)
    outside_h = (lc >= cmax) | (lc < cmin)
    use_h = valid & (lr == sr) & xp.where(hwrap, outside_h, inside_h)
    # vertical segment: along the destination column
    rmin = xp.minimum(sr, dr)
    rmax = xp.maximum(sr, dr)
    vwrap = wrap_ok & (2.0 * (rmax - rmin) > h)
    inside_v = (lr >= rmin) & (lr < rmax)
    outside_v = (lr >= rmax) | (lr < rmin)
    use_v = valid & (lc == dc) & (lr < h) \
        & xp.where(vwrap, outside_v, inside_v)
    return xp.concatenate([xp.where(use_h, 1.0, 0.0),
                           xp.where(use_v, 1.0, 0.0)], axis=-1)


def dram_channel_one_hot(xp, tile_idx, dram_channels):
    """One-hot (..., MAX_DRAM_CHANNELS) selector of the DRAM channel that
    serves ``tile_idx``'s traffic: addresses interleave across channels
    by owner tile (``tile mod dram_channels``), the way NeuPIMs-style
    channel/rank models stripe a tensor across the memory system.  A
    negative tile index selects no channel."""
    f64 = getattr(xp, "float64")
    ch = xp.arange(MAX_DRAM_CHANNELS, dtype=f64)
    t = xp.asarray(tile_idx, f64)[..., None]
    n = xp.clip(xp.asarray(dram_channels, f64), 1.0,
                float(MAX_DRAM_CHANNELS))[..., None]
    sel = t - xp.floor_divide(t, n) * n
    return xp.where((ch == sel) & (t >= 0), 1.0, 0.0)


def split_op_fields(xp, op, axis, kf):
    """Array mirror of ``ir.slice_op``: even 1/k slice of a MAC op along
    OC (axis 0), B (1) or IC (2).  ``op`` is an ``OP_COST_KEYS`` dict;
    ``axis`` the integer ``AXIS_CODES`` value; ``kf`` the (float) split
    width.  Shared by the batched plan executor (replaying a compiled
    split) and the batched mapper (evaluating all three axes) so the
    slice arithmetic matches ``slice_op`` bitwise in every backend."""
    sub = {f: op[f] for f in OP_COST_KEYS}

    def part(v):
        return xp.floor(div_exact(xp, v, kf))

    sub_m = xp.where(axis == 1, xp.maximum(part(op["m"]), 1.0), op["m"])
    sub_n = xp.where(axis == 0, xp.maximum(part(op["n"]), 1.0), op["n"])
    sub_k = xp.where(axis == 2, xp.maximum(part(op["k"]), 1.0), op["k"])
    sub["m"], sub["n"], sub["k"] = sub_m, sub_n, sub_k
    sub["macs"] = xp.where(op["macs"] > 0, sub_m * sub_k * sub_n, op["macs"])
    sub["bytes_in"] = xp.where(axis == 1, part(op["bytes_in"]),
                               op["bytes_in"])
    sub["bytes_w"] = xp.where(axis != 1, part(op["bytes_w"]),
                              op["bytes_w"])
    sub["bytes_out"] = xp.where(axis != 2, part(op["bytes_out"]),
                                op["bytes_out"])
    return sub


# =============================================================================
# throughput-mode steady state (§3.2 schedule modes)
# =============================================================================

def pipeline_bounds(xp, makespan_s, tile_busy_max_s, dram_bytes, dram_gbps,
                    noc_busy_s, chan_bytes=None, dram_channels=None,
                    link_busy_s=None):
    """Steady-state initiation interval of a pipelined (throughput-mode)
    schedule: successive inference batches replay the same plan, and in
    steady state the batch rate is set by the busiest *resource*, not the
    dependence critical path.

    Three per-batch occupancy lower bounds are composed:

    * ``tile_busy_max_s`` — the bottleneck tile's summed execution time
      (every op serializes on its owner tile);
    * DRAM channel — total burst-aligned DRAM bytes of one batch at the
      full ``dram_gbps`` (steady state overlaps transfers perfectly, so
      the channel bound uses the undivided bandwidth);
    * NoC — summed cross-tile acquisition + split-reduce transfer time
      (the NoC modeled as one shared link).

    ``II = min(makespan, max(bounds))``: the serial replay (one batch per
    makespan) is always an admissible schedule, so pipelining can never be
    slower per batch — the clamp keeps the two modes consistent wherever
    the latency model's dynamic-bandwidth optimism lets overlapping tiles
    exceed a shared-resource bound.  All backends call this one function,
    so the II arithmetic cannot drift between them.

    The ``fidelity="link"`` tier passes two extra occupancy vectors and
    the chip's channel count:

    * ``chan_bytes`` — (..., MAX_DRAM_CHANNELS) per-channel DRAM bytes
      (address-interleaved by owner tile); each channel serves its queue
      at ``dram_gbps / dram_channels``, so the channel bound is the max
      channel queue at the per-channel bandwidth.  With one channel it
      reduces exactly to the aggregate DRAM bound.
    * ``link_busy_s`` — (..., MAX_LINKS) per-link XY-routed transfer
      occupancy; the link bound is the busiest single link.

    Both are *additional* lower bounds max'd into the bottleneck (the
    aggregate bounds model injection/front-end serialization and are kept)
    — so ``II(link) >= II(aggregate)`` always, and the aggregate keys keep
    their historical bits.
    """
    dram_bound = dram_bytes / (dram_gbps * 1e9)
    bottleneck = xp.maximum(xp.maximum(tile_busy_max_s, dram_bound),
                            noc_busy_s)
    out = {
        "ii_tile_bound_s": tile_busy_max_s,
        "ii_dram_bound_s": dram_bound,
        "ii_noc_bound_s": noc_busy_s,
    }
    if chan_bytes is not None:
        n_ch = xp.clip(dram_channels, 1.0, float(MAX_DRAM_CHANNELS))
        chan_bound = xp.max(chan_bytes, axis=-1) \
            / ((dram_gbps / n_ch) * 1e9)
        link_bound = xp.max(link_busy_s, axis=-1)
        bottleneck = xp.maximum(xp.maximum(bottleneck, chan_bound),
                                link_bound)
        out["ii_chan_bound_s"] = chan_bound
        out["ii_link_bound_s"] = link_bound
    out["ii_s"] = xp.minimum(makespan_s, bottleneck)
    return out


def steady_state_energy(energy_total_pj, leakage_pj, leak_rate_pj_per_s,
                        ii_s):
    """Per-inference energy in the pipelined steady state: dynamic energy
    is per batch regardless of mode, but each batch occupies only ``II``
    of wall time, so leakage is re-charged over the initiation interval
    instead of the fill makespan."""
    return energy_total_pj - leakage_pj + leak_rate_pj_per_s * ii_s


# =============================================================================
# the seven-module cost model
# =============================================================================

class CostModel:
    """Per-(op, tile) cycle/energy model bound to one (calib, xp) pair.

    ``T`` arguments are dicts of per-tile scalars or (..., MAX_TILES)
    arrays (keys: ``TILE_COST_KEYS``); ``op`` arguments are dicts of per-op
    scalars or broadcast-compatible arrays (keys: ``OP_COST_KEYS``).  All
    methods are branch-free so the same code runs on numpy scalars and
    under jit/vmap.
    """

    def __init__(self, calib: CalibrationTable, xp=np):
        self.xp = xp
        self.c = calib
        f64 = getattr(xp, "float64")
        self.e_mac = xp.asarray(calib.e_mac_pj, f64)
        self.eng_e = xp.asarray(calib.engine_e_mult, f64)
        self.dsp_ops_t = xp.asarray(DSP_OPS_TABLE, f64)
        self.sfu_need = xp.asarray(SFU_NEED, f64)
        self.bpe_t = xp.asarray(PRECISION_BYTES, f64)
        # 2 ** precision as a gather: the TPU's float64 ``pow`` is not
        # exact even at integer exponents, and the bit test below needs
        # the exact power
        self.prec_bit = xp.asarray(2.0 ** np.arange(len(PRECISION_BYTES)),
                                   f64)

    # ---------------------------------------------------------------- helpers
    def _i32(self, v):
        return self.xp.asarray(v, self.xp.int32)

    def _sel(self, conds, vals, default):
        """``xp.select`` semantics (first true condition wins) as nested
        ``where`` — an order of magnitude cheaper on the numpy scalar path
        and identical bits on both backends."""
        out = default
        for c, v in zip(reversed(conds), reversed(vals)):
            out = self.xp.where(c, v, out)
        return out

    def mac_energy_pj(self, T, prec_idx):
        """Op-precision MAC energy on this tile's datapath, including the
        clock-gating residual of the wide path (CalibrationTable.mac_energy)."""
        xp = self.xp
        dp_idx = self._i32(T["max_prec"])
        e = self.e_mac[prec_idx]
        e_wide = self.e_mac[dp_idx]
        e = xp.where(e_wide > e, e + self.c.datapath_residual * (e_wide - e), e)
        return e * self.eng_e[self._i32(T["engine"])]

    def eta(self, sparsity, act_sp, w_sp):
        """Sparsity throughput multiplier eta_T (CalibrationTable.eta)."""
        xp = self.xp
        act_sp = xp.clip(act_sp, 0.0, 0.95)
        w_sp = xp.clip(w_sp, 0.0, 0.95)
        e_act = 1.0 / (1.0 - act_sp)
        e_w = 1.0 / (1.0 - w_sp)
        e_two = 1.0 / xp.maximum((1.0 - act_sp) * (1.0 - w_sp), 1e-3)
        e_nm = xp.where(w_sp >= 0.5, 2.0, 1.0)
        e = self._sel(
            [sparsity == int(Sparsity.NONE), sparsity == int(Sparsity.ACT),
             sparsity == int(Sparsity.WEIGHT),
             sparsity == int(Sparsity.TWO_SIDED)],
            [xp.ones_like(e_act), e_act, e_w, e_two], e_nm)
        return xp.minimum(e, self.c.eta_cap)

    def supports_precision(self, T, prec):
        """Per-tile precision filter incl. asymmetric-MAC variants
        (TileTemplate.supports_precision)."""
        xp = self.xp
        native = xp.floor_divide(T["prec_mask"],
                                 self.prec_bit[self._i32(prec)]) % 2 >= 1
        int8_ok = xp.floor_divide(T["prec_mask"], 2.0) % 2 >= 1
        fp16_ok = xp.floor_divide(T["prec_mask"], 4.0) % 2 >= 1
        asym48 = ((T["asym_mac"] == 1.0) | (T["asym_mac"] == 2.0)) \
            & (prec == 0) & int8_ok
        asym416 = (T["asym_mac"] == 3.0) & (prec <= 1) & fp16_ok
        return native | asym48 | asym416

    def sfu_native(self, T, op):
        return self.xp.floor_divide(
            T["sfu_mask"], self.sfu_need[self._i32(op["op_type"])]) % 2 >= 1

    def supports(self, T, op):
        """Compatibility filter (paper §3.2; TileSim.supports)."""
        xp = self.xp
        prec_ok = self.supports_precision(T, op["precision"])
        has_dsp = T["dsp_count"] > 0
        mac_ok = ((T["num_macs"] > 0) & prec_ok) | has_dsp
        spec_ok = self.sfu_native(T, op) \
            | ((op["op_type"] == int(OpType.FFT)) & (T["num_macs"] > 0)
               & prec_ok) \
            | has_dsp
        cls_ok = self._sel(
            [op["op_cls"] == int(OpClass.MAC),
             op["op_cls"] == int(OpClass.DSP)],
            [mac_ok, has_dsp], spec_ok)
        return (T["exists"] > 0) & cls_ok

    # ---------------------------------------------------------- MAC sub-models
    def mac_tiling(self, T, m, k, n, bpe, cache_frac=CACHE_FRAC):
        """SRAM-budget tiling pass (§3.3.1): returns (m_t, k_t, n_t)."""
        xp = self.xp
        budget = T["sram_kb"] * 1024.0 * (1.0 - cache_frac)
        m_t = xp.minimum(m, T["rows"])
        n_t = xp.maximum(xp.minimum(n, T["cols"]), 1.0)
        db = xp.where(T["double_buffer"] > 0, 2.0, 1.0)
        out_b = m_t * n_t * _ACC
        k_fit = (budget - out_b) / xp.maximum((m_t + n_t) * bpe * db, 1.0)
        k_t = xp.maximum(xp.minimum(k, k_fit), xp.minimum(k, 16.0))
        return m_t, k_t, n_t

    def mac_cycles(self, T, m, k, n, eta, m_t, k_t, n_t):
        """Engine-specific compute-cycle model (Eq. 4)."""
        xp = self.xp
        D = T["pipeline_depth"]
        tn = xp.ceil(div_exact(xp, n, n_t))
        tk = xp.ceil(div_exact(xp, k, xp.maximum(k_t, 1.0)))
        tm = xp.ceil(div_exact(xp, m, xp.maximum(m_t, 1.0)))
        m_eff = m / xp.maximum(tm, 1.0)
        k_eff = (k / xp.maximum(tk, 1.0)) / eta
        nm = xp.maximum(T["num_macs"], 1.0)
        sys = tn * tk * (D + tm * (m_eff + k_eff + D - 2.0))
        ideal = (m * k * n / eta) / nm
        util = (m_eff / xp.maximum(m_t, 1.0)) \
            * (xp.minimum(n, n_t) / xp.maximum(n_t, 1.0))
        spatial = ideal / xp.maximum(xp.minimum(util, 1.0), 0.25) + D * tn * tk
        cim = 2.0 * ideal + D * tn * tk
        cyc = self._sel(
            [T["engine"] == int(Engine.SYSTOLIC),
             T["engine"] == int(Engine.SPATIAL),
             T["engine"] == int(Engine.DOT)],
            [sys, spatial, spatial], cim)
        return xp.where((m > 0) & (k > 0) & (n > 0), cyc, 0.0)

    def sram_traffic(self, T, m, k, n, bpe, m_t, k_t, n_t):
        """Tiling-aware SRAM traffic (bytes in, weights, out, k-tiles) from
        dataflow reuse, including the AUTO rule (§3.2)."""
        xp = self.xp
        tm = xp.ceil(div_exact(xp, m, xp.maximum(m_t, 1.0)))
        tk = xp.ceil(div_exact(xp, k, xp.maximum(k_t, 1.0)))
        tn = xp.ceil(div_exact(xp, n, xp.maximum(n_t, 1.0)))
        auto_os = (m * n > 4.0 * k * n) & (m * n > 4.0 * m * k)
        df = xp.where(T["dataflow"] == int(Dataflow.AUTO),
                      xp.where(auto_os, float(Dataflow.OS),
                               float(Dataflow.WS)),
                      T["dataflow"])
        in_b = self._sel(
            [df == int(Dataflow.WS), df == int(Dataflow.OS)],
            [m * k * bpe * tn, m * k * bpe * tn], m * k * bpe * xp.sqrt(tn))
        w_b = self._sel(
            [df == int(Dataflow.WS), df == int(Dataflow.OS)],
            [k * n * bpe, k * n * bpe * tm], k * n * bpe * xp.sqrt(tm))
        out_b = self._sel(
            [df == int(Dataflow.WS), df == int(Dataflow.OS)],
            [m * n * _ACC * (2.0 * tk - 1.0), m * n * _ACC],
            m * n * _ACC * xp.sqrt(tk))
        return in_b, w_b, out_b, tk

    # ----------------------------------------------------------- vector paths
    def dsp_cycles_energy(self, T, op_type, elems, seq_len):
        """Vector-DSP path; the SSM scan parallelizes only per-step work."""
        xp = self.xp
        ops_pe = self.dsp_ops_t[self._i32(op_type)]
        lane_ops = elems * ops_pe
        lanes = xp.maximum(T["dsp_lanes"], 1.0)
        is_scan = (op_type == int(OpType.SSM_SCAN)) & (seq_len > 1)
        per_step = (elems / xp.maximum(seq_len, 1.0)) * ops_pe
        cyc = xp.where(is_scan,
                       seq_len * xp.ceil(div_exact(xp, per_step, lanes)),
                       xp.ceil(div_exact(xp, lane_ops, lanes)))
        ok = (T["dsp_count"] > 0) & (elems > 0)
        return xp.where(ok, cyc, 0.0), \
            xp.where(ok, lane_ops * self.c.e_dsp_pj_per_lane_op, 0.0)

    def sfu_cycles_energy(self, T, op_type, elems, fft_n, poly_d, snn_t):
        """Native special-function path: radix-2 FFT, LIF array, Horner."""
        xp = self.xp
        c = self.c
        par = xp.maximum(T["sfu_parallel"], 1.0)
        n = xp.maximum(fft_n, 2.0)
        transforms = xp.maximum(elems / n, 1.0)
        lg = xp.log2(n)
        c_fft = transforms * xp.ceil(div_exact(xp, n * lg, par))
        e_fft = transforms * (n / 2.0) * lg * c.e_fft_pj_per_butterfly
        t_ = xp.maximum(snn_t, 1.0)
        c_lif = xp.ceil(div_exact(xp, elems, par)) * t_
        e_lif = elems * t_ * c.e_lif_pj_per_neuron_step
        d = xp.maximum(poly_d, 1.0)
        c_pol = elems * d / par
        e_pol = elems * d * c.e_poly_pj_per_fma
        cyc = self._sel([op_type == int(OpType.FFT),
                         op_type == int(OpType.SNN_LIF)], [c_fft, c_lif],
                        c_pol)
        en = self._sel([op_type == int(OpType.FFT),
                        op_type == int(OpType.SNN_LIF)], [e_fft, e_lif],
                       e_pol)
        return cyc, en

    def lowered_cycles_energy(self, T, op, prec_idx):
        """Lowered cost (paper §2.5): FFT->MAC O(N^2) when a MAC array
        exists; LIF/poly/FFT->DSP with sequential multipliers."""
        xp = self.xp
        c = self.c
        lanes = xp.maximum(T["dsp_lanes"], 1.0)
        n = xp.maximum(op["fft_n"], 2.0)
        transforms = xp.maximum(op["elems"] / n, 1.0)
        macs = 4.0 * n * n * transforms
        c_fft_mac = macs / xp.maximum(T["num_macs"], 1.0)
        e_fft_mac = macs * self.mac_energy_pj(T, prec_idx)
        tsteps = xp.maximum(op["snn_timesteps"], 1.0)
        lif_ops = op["elems"] * 4.0
        # divergence + membrane round-trips (§2.5): ~4x lane-efficiency loss
        c_lif = tsteps * (
            xp.ceil(div_exact(xp, lif_ops, lanes / 4.0))
            + xp.ceil(div_exact(xp, op["elems"] * 8.0, T["sram_bpc"])))
        e_lif = lif_ops * tsteps * c.e_dsp_pj_per_lane_op
        d = xp.maximum(op["poly_degree"], 1.0)
        pol_ops = op["elems"] * 2.0
        c_pol = d * (
            xp.ceil(div_exact(xp, pol_ops, lanes))
            + xp.ceil(div_exact(xp, op["elems"] * 2.0, T["sram_bpc"])))
        e_pol = d * pol_ops * c.e_dsp_pj_per_lane_op
        c_fft_dsp = xp.ceil(div_exact(xp, op["elems"] * 10.0 * xp.log2(n),
                                      lanes))
        e_fft_dsp = op["elems"] * 10.0 * xp.log2(n) * c.e_dsp_pj_per_lane_op
        is_fft = op["op_type"] == int(OpType.FFT)
        # The MAC lowering (and its DFT twiddle-weight SRAM surcharge)
        # requires the datapath to accept the op's precision; a
        # precision-mismatched MAC tile falls through to DSP butterfly
        # emulation.  (The pre-unification TileSim charged the twiddle
        # stream while costing butterfly cycles on such tiles — an
        # inconsistency this shared model resolves the batch_eval way.)
        fft_on_mac = is_fft & (T["num_macs"] > 0) \
            & self.supports_precision(T, op["precision"])
        cyc = self._sel(
            [fft_on_mac, op["op_type"] == int(OpType.SNN_LIF),
             op["op_type"] == int(OpType.POLY)],
            [c_fft_mac, c_lif, c_pol], c_fft_dsp)
        en = self._sel(
            [fft_on_mac, op["op_type"] == int(OpType.SNN_LIF),
             op["op_type"] == int(OpType.POLY)],
            [e_fft_mac, e_lif, e_pol], e_fft_dsp)
        # DFT twiddle weights streamed through SRAM on the MAC lowering
        extra_sram = xp.where(fft_on_mac, 2.0 * n * n * self.bpe_t[prec_idx]
                              * c.e_sram_pj_per_byte, 0.0)
        return cyc, en, extra_sram, fft_on_mac

    # -------------------------------------------------------------- roofline
    def roofline_cycles(self, T, op, bw_gbps):
        """Mapper's cycle estimate (Eq. 2): max of compute- and
        bandwidth-bound counts (TileSim.roofline_cycles)."""
        xp = self.xp
        total_b = op["bytes_in"] + op["bytes_w"] + op["bytes_out"]
        bpc = bw_gbps * 1e9 / T["clock_hz"]
        c_bw = total_b / xp.maximum(bpc, 1e-9)
        eta = self.eta(T["sparsity"], op["act_sparsity"], op["w_sparsity"])
        c_mac = xp.where(
            (T["num_macs"] > 0) & self.supports_precision(T, op["precision"]),
            op["macs"] / xp.maximum(T["num_macs"] * eta, 1e-9),
            xp.ceil(div_exact(xp, 2.0 * op["macs"],
                              xp.maximum(T["dsp_lanes"], 1.0))))
        c_dsp, _ = self.dsp_cycles_energy(T, op["op_type"], op["elems"],
                                          op["seq_len"])
        c_sfu_nat, _ = self.sfu_cycles_energy(
            T, op["op_type"], op["elems"], op["fft_n"], op["poly_degree"],
            op["snn_timesteps"])
        prec_idx = self._i32(op["precision"])
        c_low, _, _, _ = self.lowered_cycles_energy(T, op, prec_idx)
        c_spec = xp.where(self.sfu_native(T, op), c_sfu_nat, c_low)
        c_cmp = self._sel(
            [op["op_cls"] == int(OpClass.MAC),
             op["op_cls"] == int(OpClass.SPECIAL)],
            [c_mac, c_spec], c_dsp)
        return xp.maximum(c_cmp, c_bw)

    # --------------------------------------------------------------- execute
    def execute_static(self, T, op, cache_frac=CACHE_FRAC):
        """The state-independent half of :meth:`execute`: every quantity
        that depends only on the (tile, op) pair — compute/memory cycle
        counts, per-module energies, execution-path routing, and the
        path-selected non-DRAM energy sum.

        Splitting this out lets the oracle orchestrator evaluate it for a
        whole plan in ONE vectorized call (one record per (op, tile)
        execution) before its sequential walk, leaving only the cheap
        bandwidth/DRAM combine (:meth:`execute_dynamic`) inside the
        per-op loop.  ``execute`` composes the two halves, so all
        backends still run literally the same arithmetic.
        """
        xp = self.xp
        c = self.c
        prec_idx = self._i32(op["precision"])
        bpe = self.bpe_t[prec_idx]

        # ---- MAC path ----------------------------------------------------
        eta = self.eta(T["sparsity"], op["act_sparsity"], op["w_sparsity"])
        m_t, k_t, n_t = self.mac_tiling(T, op["m"], op["k"], op["n"], bpe,
                                        cache_frac)
        c_mac = self.mac_cycles(T, op["m"], op["k"], op["n"], eta,
                                m_t, k_t, n_t)
        e_mac_path = (op["macs"] / eta) * self.mac_energy_pj(T, prec_idx)
        in_b, w_b, out_b, tk = self.sram_traffic(
            T, op["m"], op["k"], op["n"], bpe, m_t, k_t, n_t)
        e_sram_mac = (in_b + w_b + out_b) * c.e_sram_pj_per_byte
        irf_w = xp.ceil(div_exact(xp, in_b, 32.0)) * 32.0
        irf_r = in_b * (1.0 - xp.minimum(op["act_sparsity"], 0.95))
        e_irf = (irf_w + irf_r) * c.e_irf_pj_per_byte
        orf_b = op["m"] * op["n"] * _ACC * (2.0 * tk - 1.0)
        e_orf = orf_b * c.e_orf_pj_per_byte
        c_mem_mac = xp.ceil(div_exact(xp, in_b + w_b + out_b, T["sram_bpc"]))

        # ---- DSP path ----------------------------------------------------
        c_dsp, e_dsp = self.dsp_cycles_energy(T, op["op_type"], op["elems"],
                                              op["seq_len"])
        stream_b = op["bytes_in"] + op["bytes_out"]
        e_sram_stream = stream_b * c.e_sram_pj_per_byte
        c_mem_stream = xp.ceil(div_exact(xp, stream_b, T["sram_bpc"]))

        # ---- MAC op lowered onto the DSP ---------------------------------
        lanes = xp.maximum(T["dsp_lanes"], 1.0)
        c_mac_on_dsp = xp.ceil(div_exact(xp, 2.0 * op["macs"], lanes))
        e_mac_on_dsp = 2.0 * op["macs"] * c.e_dsp_pj_per_lane_op

        # ---- SPECIAL path ------------------------------------------------
        c_sfu, e_sfu = self.sfu_cycles_energy(
            T, op["op_type"], op["elems"], op["fft_n"], op["poly_degree"],
            op["snn_timesteps"])
        c_low, e_low, extra_sram_low, fft_on_mac = self.lowered_cycles_energy(
            T, op, prec_idx)
        native = self.sfu_native(T, op)
        c_spec = xp.where(native, c_sfu, c_low)
        e_spec = xp.where(native, e_sfu, e_low)
        e_spec_sram = e_sram_stream + xp.where(native, 0.0, extra_sram_low)

        is_mac_cls = op["op_cls"] == int(OpClass.MAC)
        is_spec_cls = op["op_cls"] == int(OpClass.SPECIAL)
        prec_ok = self.supports_precision(T, op["precision"])
        on_mac = is_mac_cls & (T["num_macs"] > 0) & prec_ok
        on_dsp_low = is_mac_cls & ~on_mac
        spec_lowered_mac = is_spec_cls & ~native & fft_on_mac

        c_cmp = self._sel([on_mac, on_dsp_low, is_spec_cls],
                          [c_mac, c_mac_on_dsp, c_spec], c_dsp)
        c_mem = self._sel([on_mac, on_dsp_low, is_spec_cls],
                          [c_mem_mac, c_mem_stream, c_mem_stream],
                          c_mem_stream)

        # per-module energy routing (mirrors TileSim's EnergyBreakdown fills)
        zero = xp.zeros_like(c_cmp)
        e_compute = self._sel(
            [on_mac, spec_lowered_mac], [e_mac_path, e_spec], zero)
        e_dsp_mod = self._sel(
            [on_mac, on_dsp_low, is_spec_cls],
            [zero, e_mac_on_dsp,
             xp.where(native | fft_on_mac, zero, e_spec)], e_dsp)
        e_special = xp.where(is_spec_cls & native, e_spec, 0.0)
        e_sram = self._sel(
            [on_mac, on_dsp_low, is_spec_cls],
            [e_sram_mac, e_sram_stream, e_spec_sram], e_sram_stream)
        e_irf_mod = xp.where(on_mac, e_irf, 0.0)
        e_orf_mod = xp.where(on_mac, e_orf, 0.0)

        # non-DRAM energy summed in the historical per-path order so the
        # jitted backends reproduce the pre-refactor bits exactly
        e_static = self._sel(
            [on_mac, on_dsp_low, is_spec_cls],
            [e_mac_path + e_sram_mac + e_irf + e_orf,
             e_mac_on_dsp + e_sram_stream,
             e_spec + e_spec_sram],
            e_dsp + e_sram_stream)

        path = self._sel([on_mac | spec_lowered_mac, is_spec_cls & native],
                         [xp.zeros_like(c_cmp), 2.0 + zero], 1.0 + zero)
        return {
            "c_cmp": c_cmp, "c_mem": c_mem,
            "e_compute": e_compute, "e_dsp": e_dsp_mod,
            "e_special": e_special, "e_sram": e_sram, "e_irf": e_irf_mod,
            "e_orf": e_orf_mod, "e_static": e_static, "path": path,
        }

    def execute_dynamic(self, st, T, bw_gbps, dram_rd, dram_wr):
        """The state-dependent half of :meth:`execute`: burst-aligned DRAM
        staging at the dynamically shared bandwidth, the Eq. 5 total-cycle
        combine, and the roofline code.  ``st`` is an
        :meth:`execute_static` result (or one row of a vectorized one);
        ``T`` only needs ``clock_hz`` and ``double_buffer``."""
        xp = self.xp
        c = self.c
        c_cmp, c_mem = st["c_cmp"], st["c_mem"]

        # ---- DRAM + ports + Eq. 5 combine --------------------------------
        rd_al = xp.where(dram_rd > 0,
                         xp.ceil(div_exact(xp, dram_rd, _BURST)) * _BURST, 0.0)
        wr_al = xp.where(dram_wr > 0,
                         xp.ceil(div_exact(xp, dram_wr, _BURST)) * _BURST, 0.0)
        total_dram = rd_al + wr_al
        bpc = bw_gbps * 1e9 / T["clock_hz"]
        c_dram = xp.where(total_dram > 0,
                          total_dram / xp.maximum(bpc, 1e-9)
                          + c.dram_latency_cycles, 0.0)
        e_dram = total_dram * c.e_dram_pj_per_byte
        c_lp = xp.ceil(div_exact(xp, dram_rd, 64.0))
        c_sp = xp.ceil(div_exact(xp, dram_wr, 64.0))
        c_tot = xp.where(T["double_buffer"] > 0,
                         xp.maximum(xp.maximum(c_cmp, c_mem), c_dram)
                         + c_lp + c_sp,
                         c_cmp + c_mem + c_dram + c_lp + c_sp)

        energy_total = st["e_static"] + e_dram
        roofline = xp.where(c_cmp >= xp.maximum(c_mem, c_dram), 0.0, 1.0)
        return {
            "cycles": c_tot, "seconds": c_tot / T["clock_hz"],
            "e_compute": st["e_compute"], "e_dsp": st["e_dsp"],
            "e_special": st["e_special"], "e_sram": st["e_sram"],
            "e_irf": st["e_irf"], "e_orf": st["e_orf"], "e_dram": e_dram,
            "energy_total": energy_total, "path": st["path"],
            "roofline": roofline, "dram_bytes": total_dram,
        }

    def execute(self, T, op, bw_gbps, dram_rd, dram_wr,
                cache_frac=CACHE_FRAC):
        """Full seven-module execution (Eq. 4-6; TileSim.execute).

        ``dram_rd`` / ``dram_wr`` are the effective DRAM bytes after the
        orchestrator's activation-cache adjustment (§3.3.4).  Returns a
        dict with ``cycles``, ``seconds``, per-module energies
        (``e_compute``, ``e_dsp``, ``e_special``, ``e_sram``, ``e_irf``,
        ``e_orf``, ``e_dram``), their ``energy_total``, and integer
        ``path`` (0 MAC / 1 DSP / 2 SFU) and ``roofline`` (0 compute /
        1 memory) codes.  Composition of :meth:`execute_static` and
        :meth:`execute_dynamic` — bitwise identical to the historical
        fused implementation.
        """
        return self.execute_dynamic(self.execute_static(T, op, cache_frac),
                                    T, bw_gbps, dram_rd, dram_wr)

    # ----------------------------------------------- class-specialized halves
    # ``op_cls`` is a *workload* property — identical for every candidate
    # chip in a batched evaluation.  The restrictions below are
    # :meth:`execute_static` / :meth:`roofline_cycles` / :meth:`supports`
    # with the class selects resolved at the call site: when the caller
    # already knows the class (the fused search kernel branches on the
    # op-table value with ``lax.cond``, so only the taken class runs), the
    # other classes' sub-models are never evaluated.  Each restriction is
    # term-for-term the corresponding ``_sel`` branch of the full method,
    # so the bits are identical — pinned by the batched-mapper parity
    # suite and the engine's exact-search/rescore property tests.

    def _stream_static(self, T, op):
        """Streaming (non-MAC-array) SRAM terms shared by all classes."""
        xp = self.xp
        stream_b = op["bytes_in"] + op["bytes_out"]
        e_sram_stream = stream_b * self.c.e_sram_pj_per_byte
        c_mem_stream = xp.ceil(div_exact(xp, stream_b, T["sram_bpc"]))
        return e_sram_stream, c_mem_stream

    def _bw_cycles(self, T, op, bw_gbps):
        xp = self.xp
        total_b = op["bytes_in"] + op["bytes_w"] + op["bytes_out"]
        bpc = bw_gbps * 1e9 / T["clock_hz"]
        return total_b / xp.maximum(bpc, 1e-9)

    def execute_static_mac(self, T, op, cache_frac=CACHE_FRAC):
        """:meth:`execute_static` restricted to ``OpClass.MAC`` operators
        (on-array execution or DSP lowering; no SFU terms evaluated)."""
        xp = self.xp
        c = self.c
        prec_idx = self._i32(op["precision"])
        bpe = self.bpe_t[prec_idx]
        eta = self.eta(T["sparsity"], op["act_sparsity"], op["w_sparsity"])
        m_t, k_t, n_t = self.mac_tiling(T, op["m"], op["k"], op["n"], bpe,
                                        cache_frac)
        c_mac = self.mac_cycles(T, op["m"], op["k"], op["n"], eta,
                                m_t, k_t, n_t)
        e_mac_path = (op["macs"] / eta) * self.mac_energy_pj(T, prec_idx)
        in_b, w_b, out_b, tk = self.sram_traffic(
            T, op["m"], op["k"], op["n"], bpe, m_t, k_t, n_t)
        e_sram_mac = (in_b + w_b + out_b) * c.e_sram_pj_per_byte
        irf_w = xp.ceil(div_exact(xp, in_b, 32.0)) * 32.0
        irf_r = in_b * (1.0 - xp.minimum(op["act_sparsity"], 0.95))
        e_irf = (irf_w + irf_r) * c.e_irf_pj_per_byte
        orf_b = op["m"] * op["n"] * _ACC * (2.0 * tk - 1.0)
        e_orf = orf_b * c.e_orf_pj_per_byte
        c_mem_mac = xp.ceil(div_exact(xp, in_b + w_b + out_b, T["sram_bpc"]))
        e_sram_stream, c_mem_stream = self._stream_static(T, op)
        lanes = xp.maximum(T["dsp_lanes"], 1.0)
        c_mac_on_dsp = xp.ceil(div_exact(xp, 2.0 * op["macs"], lanes))
        e_mac_on_dsp = 2.0 * op["macs"] * c.e_dsp_pj_per_lane_op
        on_mac = (T["num_macs"] > 0) \
            & self.supports_precision(T, op["precision"])
        zero = xp.zeros_like(c_mac)
        return {
            "c_cmp": xp.where(on_mac, c_mac, c_mac_on_dsp),
            "c_mem": xp.where(on_mac, c_mem_mac, c_mem_stream),
            "e_compute": xp.where(on_mac, e_mac_path, 0.0),
            "e_dsp": xp.where(on_mac, 0.0, e_mac_on_dsp),
            "e_special": zero,
            "e_sram": xp.where(on_mac, e_sram_mac, e_sram_stream),
            "e_irf": xp.where(on_mac, e_irf, 0.0),
            "e_orf": xp.where(on_mac, e_orf, 0.0),
            "e_static": xp.where(
                on_mac, e_mac_path + e_sram_mac + e_irf + e_orf,
                e_mac_on_dsp + e_sram_stream),
            "path": xp.where(on_mac, zero, 1.0 + zero),
        }

    def execute_static_dsp(self, T, op):
        """:meth:`execute_static` restricted to ``OpClass.DSP`` operators
        (the cheap vector path: no MAC tiling, no SFU lowering)."""
        xp = self.xp
        c_dsp, e_dsp = self.dsp_cycles_energy(T, op["op_type"], op["elems"],
                                              op["seq_len"])
        e_sram_stream, c_mem_stream = self._stream_static(T, op)
        zero = xp.zeros_like(c_dsp)
        return {
            "c_cmp": c_dsp, "c_mem": c_mem_stream + zero,
            "e_compute": zero, "e_dsp": e_dsp, "e_special": zero,
            "e_sram": e_sram_stream + zero, "e_irf": zero, "e_orf": zero,
            "e_static": e_dsp + e_sram_stream,
            "path": 1.0 + zero,
        }

    def execute_static_special(self, T, op):
        """:meth:`execute_static` restricted to ``OpClass.SPECIAL``
        operators (native SFU or §2.5 lowering; no MAC tiling pass)."""
        xp = self.xp
        prec_idx = self._i32(op["precision"])
        c_sfu, e_sfu = self.sfu_cycles_energy(
            T, op["op_type"], op["elems"], op["fft_n"], op["poly_degree"],
            op["snn_timesteps"])
        c_low, e_low, extra_sram_low, fft_on_mac = self.lowered_cycles_energy(
            T, op, prec_idx)
        native = self.sfu_native(T, op)
        e_sram_stream, c_mem_stream = self._stream_static(T, op)
        c_spec = xp.where(native, c_sfu, c_low)
        e_spec = xp.where(native, e_sfu, e_low)
        e_spec_sram = e_sram_stream + xp.where(native, 0.0, extra_sram_low)
        spec_lowered_mac = ~native & fft_on_mac
        zero = xp.zeros_like(c_spec)
        return {
            "c_cmp": c_spec, "c_mem": c_mem_stream + zero,
            "e_compute": xp.where(spec_lowered_mac, e_spec, 0.0),
            "e_dsp": xp.where(native | fft_on_mac, 0.0, e_spec),
            "e_special": xp.where(native, e_spec, 0.0),
            "e_sram": e_spec_sram, "e_irf": zero, "e_orf": zero,
            "e_static": e_spec + e_spec_sram,
            "path": xp.where(spec_lowered_mac, zero,
                             xp.where(native, 2.0 + zero, 1.0 + zero)),
        }

    def roofline_cycles_mac(self, T, op, bw_gbps):
        """:meth:`roofline_cycles` restricted to ``OpClass.MAC``."""
        xp = self.xp
        eta = self.eta(T["sparsity"], op["act_sparsity"], op["w_sparsity"])
        c_mac = xp.where(
            (T["num_macs"] > 0) & self.supports_precision(T, op["precision"]),
            op["macs"] / xp.maximum(T["num_macs"] * eta, 1e-9),
            xp.ceil(div_exact(xp, 2.0 * op["macs"],
                              xp.maximum(T["dsp_lanes"], 1.0))))
        return xp.maximum(c_mac, self._bw_cycles(T, op, bw_gbps))

    def roofline_cycles_dsp(self, T, op, bw_gbps):
        """:meth:`roofline_cycles` restricted to ``OpClass.DSP``."""
        xp = self.xp
        c_dsp, _ = self.dsp_cycles_energy(T, op["op_type"], op["elems"],
                                          op["seq_len"])
        return xp.maximum(c_dsp, self._bw_cycles(T, op, bw_gbps))

    def roofline_cycles_special(self, T, op, bw_gbps):
        """:meth:`roofline_cycles` restricted to ``OpClass.SPECIAL``."""
        xp = self.xp
        c_sfu_nat, _ = self.sfu_cycles_energy(
            T, op["op_type"], op["elems"], op["fft_n"], op["poly_degree"],
            op["snn_timesteps"])
        prec_idx = self._i32(op["precision"])
        c_low, _, _, _ = self.lowered_cycles_energy(T, op, prec_idx)
        c_spec = xp.where(self.sfu_native(T, op), c_sfu_nat, c_low)
        return xp.maximum(c_spec, self._bw_cycles(T, op, bw_gbps))

    def supports_mac(self, T, op):
        """:meth:`supports` restricted to ``OpClass.MAC``."""
        prec_ok = self.supports_precision(T, op["precision"])
        has_dsp = T["dsp_count"] > 0
        return (T["exists"] > 0) & (((T["num_macs"] > 0) & prec_ok) | has_dsp)

    def supports_dsp(self, T, op):
        """:meth:`supports` restricted to ``OpClass.DSP``."""
        return (T["exists"] > 0) & (T["dsp_count"] > 0)

    def supports_special(self, T, op):
        """:meth:`supports` restricted to ``OpClass.SPECIAL``."""
        prec_ok = self.supports_precision(T, op["precision"])
        has_dsp = T["dsp_count"] > 0
        spec_ok = self.sfu_native(T, op) \
            | ((op["op_type"] == int(OpType.FFT)) & (T["num_macs"] > 0)
               & prec_ok) \
            | has_dsp
        return (T["exists"] > 0) & spec_ok


@functools.lru_cache(maxsize=32)
def _cached_model(calib: CalibrationTable, backend: str) -> CostModel:
    if backend == "numpy":
        return CostModel(calib, np)
    import jax.numpy as jnp  # deferred: the oracle never pays the import
    return CostModel(calib, jnp)


def cost_model(calib: CalibrationTable, xp=np) -> CostModel:
    """Cached CostModel factory; ``xp`` is ``numpy`` or ``jax.numpy``."""
    return _cached_model(calib, "numpy" if xp is np else "jax")


# =============================================================================
# activation cache (§3.3.4): byte- and slot-bounded FIFO, Python reference
# =============================================================================

class ActivationCache:
    """Per-tile FIFO activation cache.

    Holds at most ``ACT_CACHE_SLOTS`` producer outputs totalling at most
    ``cap_bytes``; inserting evicts oldest-first until the new output fits
    (outputs larger than the capacity are never inserted).  Mirrored
    bitwise by ``simulator.batched.fifo_insert`` — keep the two in sync.
    """

    def __init__(self, tile_index: int, cap_bytes: float,
                 slots: int = ACT_CACHE_SLOTS):
        self.tile_index = tile_index
        self.cap = cap_bytes
        self.slots = slots
        self.entries: collections.deque = collections.deque()  # (op, bytes)
        self.used = 0.0

    def insert(self, op_idx: int, nbytes: float,
               cached_at: Dict[int, int]) -> None:
        """Insert ``op_idx``'s output, updating the shared op->tile map."""
        if nbytes > self.cap:
            return
        while self.entries and (self.used + nbytes > self.cap
                                or len(self.entries) == self.slots):
            old_op, old_b = self.entries.popleft()
            self.used -= old_b
            cached_at.pop(old_op, None)
        self.entries.append((op_idx, nbytes))
        self.used += nbytes
        cached_at[op_idx] = self.tile_index
