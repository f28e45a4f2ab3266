"""Batched plan executor: ``ChipSim.run`` re-expressed as jittable array ops.

Executes compiled plans (``PlanTensor`` op-tables: ops padded to a fixed
row count, placements as integer arrays) as one ``lax.scan`` over
operators, ``vmap``-ed across the candidate axis and jitted — so a
64-candidate GA population costs one device dispatch instead of 64 walks
of the per-operator Python loop.

Semantics are the *exact* orchestrator rules, not the search heuristic:

* dynamic DRAM bandwidth sharing (BW_total / N_active at each op start);
* the byte- and slot-bounded FIFO activation cache (§3.3.4) with local
  hit / cross-tile NoC DMA / DRAM miss accounting — ``fifo_insert`` below
  mirrors ``costs.ActivationCache`` bitwise;
* power gating of idle tiles at the 5 % residual;
* Eq. 3 split-op execution with the explicit NoC reduce cost.

Per-(op, tile) costs come from the shared ``costs.CostModel`` — literally
the same code the reference ``TileSim`` runs — so the two backends share
one set of calibrated formulas and parity reduces to the orchestration
above, pinned by golden traces (tests/golden/) and the hypothesis suite
(tests/test_batched_parity.py).  ``ChipSim`` remains the oracle: it keeps
the per-op trace, per-tile energy breakdowns, and chrome-trace output.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import numpy as np

import jax

jax.config.update("jax_enable_x64", True)  # cycle counts overflow f32 ULPs

import jax.numpy as jnp

from ..arch import MAX_TILES, ChipConfig
from ..calibrate.asap7 import CalibrationTable, DEFAULT_CALIB
from ..ir import MAX_PREDS, PlanTensor
from .area import chip_area, tile_area
from .costs import (ACT_CACHE_SLOTS, CACHE_FRAC, FIDELITIES,
                    MAX_DRAM_CHANNELS, MAX_LINKS, OP_COST_KEYS, cost_model,
                    div_exact, dram_channel_one_hot, grid_dims,
                    noc_transfer_energy_pj, noc_transfer_seconds,
                    pipeline_bounds, split_op_fields, steady_state_energy,
                    xy_route_link_mask)
from .orchestrator import SCHEDULE_MODES, noc_hops

__all__ = ["stack_chip_configs", "stack_plan_tables", "batch_simulate",
           "simulate_plans", "fifo_insert", "TILE_KEYS", "CHIP_KEYS"]

_F = jnp.float64

TILE_KEYS = ("exists", "num_macs", "rows", "cols", "engine", "prec_mask",
             "asym_mac", "sparsity", "dataflow", "sram_kb", "dsp_lanes",
             "dsp_count", "sfu_mask", "sfu_parallel", "double_buffer",
             "pipeline_depth", "clock_hz", "cache_cap", "sram_bpc",
             "area_mm2", "max_prec")
CHIP_KEYS = ("dram_gbps", "hops", "noc_bpc", "noc_base_cycles",
             "ref_clock_hz", "grid_w", "grid_h", "torus", "dram_channels")

_OP_TABLE_KEYS = OP_COST_KEYS + (
    "valid", "fused", "num_preds", "per_pred_bytes", "fused_lane_ops",
    "fused_refund_bytes")


# =============================================================================
# host-side stacking
# =============================================================================

def stack_chip_configs(chips: Sequence[ChipConfig],
                       calib: CalibrationTable = DEFAULT_CALIB
                       ) -> Dict[str, Dict[str, np.ndarray]]:
    """Stack chips into (B, MAX_TILES) tile / (B,) chip arrays.

    This is the single config-stacking implementation —
    ``dse.batch_eval.prepare_configs`` and the DSE engine's vectorized
    genome path both emit this exact layout.
    """
    B = len(chips)
    tile_f = {f: np.zeros((B, MAX_TILES)) for f in TILE_KEYS}
    chip_f = {f: np.zeros(B) for f in CHIP_KEYS + ("peak_tops", "chip_area")}
    for b, chip in enumerate(chips):
        inst = chip.instances()
        for i, t in enumerate(inst):
            tile_f["exists"][b, i] = 1.0
            tile_f["num_macs"][b, i] = t.num_macs
            tile_f["rows"][b, i] = t.rows
            tile_f["cols"][b, i] = t.cols
            tile_f["engine"][b, i] = int(t.engine)
            tile_f["prec_mask"][b, i] = t.precision_mask
            tile_f["asym_mac"][b, i] = int(t.asym_mac)
            tile_f["sparsity"][b, i] = int(t.sparsity)
            tile_f["dataflow"][b, i] = int(t.dataflow)
            tile_f["sram_kb"][b, i] = t.sram_kb
            tile_f["dsp_lanes"][b, i] = t.dsp_count * t.dsp_simd
            tile_f["dsp_count"][b, i] = t.dsp_count
            tile_f["sfu_mask"][b, i] = t.sfu_mask
            tile_f["sfu_parallel"][b, i] = t.sfu_parallel
            tile_f["double_buffer"][b, i] = float(t.double_buffer)
            tile_f["pipeline_depth"][b, i] = t.pipeline_depth
            tile_f["clock_hz"][b, i] = t.clock_mhz * 1e6
            tile_f["cache_cap"][b, i] = t.sram_kb * 1024.0 * CACHE_FRAC
            tile_f["sram_bpc"][b, i] = max(t.sram_banks, 1) * 16.0
            tile_f["area_mm2"][b, i] = tile_area(t, calib)
            tile_f["max_prec"][b, i] = int(t.max_precision)
        chip_f["dram_gbps"][b] = chip.dram_gbps
        chip_f["hops"][b] = noc_hops(chip.interconnect, len(inst))
        chip_f["noc_bpc"][b] = chip.noc_bytes_per_cycle
        chip_f["noc_base_cycles"][b] = chip.noc_base_cycles
        chip_f["ref_clock_hz"][b] = chip.ref_clock_mhz * 1e6
        gw, gh = grid_dims(np, float(len(inst)), chip.grid_aspect)
        chip_f["grid_w"][b] = gw
        chip_f["grid_h"][b] = gh
        chip_f["torus"][b] = float(chip.torus)
        chip_f["dram_channels"][b] = chip.dram_channels
        chip_f["peak_tops"][b] = sum(t.num_macs * t.clock_mhz * 1e6
                                     for t in inst) / 1e12
        chip_f["chip_area"][b] = chip_area(chip, calib)
    return {"tile": tile_f, "chip": chip_f}


def stack_plan_tables(tables: Sequence[PlanTensor]) -> Dict[str, np.ndarray]:
    """Stack per-candidate plan tables into (B, max_ops, ...) arrays.

    All tables must share ``max_ops`` (lower them with the same bucket);
    split masks are padded from each chip's ``num_tiles`` to MAX_TILES.
    """
    if not tables:
        raise ValueError("stack_plan_tables needs at least one plan table")
    caps = {t.max_ops for t in tables}
    if len(caps) != 1:
        raise ValueError(f"plan tables disagree on max_ops: {sorted(caps)}")
    (cap,) = caps
    modes = {t.mode for t in tables}
    if len(modes) != 1:
        raise ValueError(f"plan tables disagree on schedule mode: "
                         f"{sorted(modes)}")
    B = len(tables)
    out: Dict[str, np.ndarray] = {}
    for f in _OP_TABLE_KEYS:
        src = [t.aux[f] if f in t.aux else t.ops.arrays[f] for t in tables]
        out[f] = np.stack([np.asarray(a, np.float64) for a in src])
    out["preds"] = np.stack([t.ops.preds for t in tables]).astype(np.int32)
    out["owner"] = np.stack([t.owner for t in tables]).astype(np.int32)
    out["n_split"] = np.stack([t.n_split for t in tables]).astype(np.float64)
    out["split_axis"] = np.stack([t.split_axis
                                  for t in tables]).astype(np.int32)
    mask = np.zeros((B, cap, MAX_TILES), np.float64)
    for b, t in enumerate(tables):
        mask[b, :, :t.split_mask.shape[1]] = t.split_mask
    out["split_mask"] = mask
    out["total_macs"] = np.asarray([t.aux["total_macs"] for t in tables],
                                   np.float64)
    out["mode"] = modes.pop()
    return out


def tile_sum(v):
    """Sum over the (MAX_TILES,) tile lanes left to right from 0.0 — the
    order ChipSim accumulates per-tile terms in.  ``jnp.sum`` leaves the
    reduction order to XLA, which picks it per fusion, so the same sum
    in two programs can differ in the last bit."""
    acc = jnp.zeros((), v.dtype)
    for t in range(v.shape[-1]):
        acc = acc + v[..., t]
    return acc


# =============================================================================
# FIFO activation cache — array mirror of costs.ActivationCache
# =============================================================================

def fifo_insert(fifo_ops, fifo_bytes, cached_at, tile, op_idx, nbytes, cap,
                enable):
    """Insert op ``op_idx``'s output (``nbytes``) into ``tile``'s FIFO row,
    evicting oldest-first until it fits in bytes (``cap``) and slots.

    ``fifo_ops`` / ``fifo_bytes`` are (MAX_TILES, ACT_CACHE_SLOTS) arrays,
    right-packed (newest at the last slot, -1 / 0.0 padding on the left);
    ``cached_at`` maps op index -> holding tile (-1 when absent).  Keep in
    bitwise sync with ``costs.ActivationCache.insert``.
    """
    S = fifo_ops.shape[1]
    row_ops = fifo_ops[tile]
    row_b = fifo_bytes[tile]
    count = jnp.sum(row_ops >= 0)
    # rem[j] = bytes kept when slots [j:] survive; monotone nonincreasing
    rem = jnp.concatenate([jnp.cumsum(row_b[::-1])[::-1],
                           jnp.zeros((1,), row_b.dtype)])
    fits = rem + nbytes <= cap
    a = jnp.maximum(jnp.argmax(fits), S - count)       # first surviving slot
    a = jnp.maximum(a, jnp.where(count == S, 1, 0))    # full row: evict >= 1
    do = enable & (nbytes <= cap)

    shifted_ops = jnp.concatenate(
        [row_ops[1:], jnp.full((1,), op_idx, row_ops.dtype)])
    shifted_b = jnp.concatenate([row_b[1:], jnp.reshape(nbytes, (1,))])
    keep_pos = jnp.arange(S) >= a - 1
    new_ops = jnp.where(keep_pos, shifted_ops, -1)
    new_b = jnp.where(keep_pos, shifted_b, 0.0)

    pos = jnp.arange(S)
    evicted = (pos >= S - count) & (pos < a) & do
    oob = cached_at.shape[0]  # scatter mode="drop" discards these
    evict_ids = jnp.where(evicted, row_ops, oob)
    cached_at = cached_at.at[evict_ids].set(-1, mode="drop")
    cached_at = cached_at.at[op_idx].set(
        jnp.where(do, tile, cached_at[op_idx]).astype(cached_at.dtype))
    fifo_ops = fifo_ops.at[tile].set(jnp.where(do, new_ops, row_ops))
    fifo_bytes = fifo_bytes.at[tile].set(jnp.where(do, new_b, row_b))
    return fifo_ops, fifo_bytes, cached_at


# =============================================================================
# the plan-execution scan (mirrors ChipSim.run op-for-op)
# =============================================================================

def _build_plan_exec(calib: CalibrationTable, max_ops: int,
                     fidelity: str = "aggregate"):
    cm = cost_model(calib, jnp)
    c = calib
    link = fidelity == "link"

    def exec_plan(tile, chip, xs, total_macs):
        T = tile

        def noc_seconds(nbytes):
            return noc_transfer_seconds(jnp, nbytes, chip["noc_bpc"],
                                        chip["hops"],
                                        chip["noc_base_cycles"],
                                        chip["ref_clock_hz"])

        def noc_energy(nbytes):
            return noc_transfer_energy_pj(jnp, nbytes,
                                          c.e_noc_pj_per_byte_hop,
                                          chip["hops"])

        def link_seconds(nbytes):
            # one grid link's store-and-forward occupancy (hops = 1)
            return noc_transfer_seconds(jnp, nbytes, chip["noc_bpc"], 1.0,
                                        chip["noc_base_cycles"],
                                        chip["ref_clock_hz"])

        # per-tile DRAM-channel one-hot of the link-fidelity tier
        # (chip-constant, hoisted out of the scan)
        tidx_f = jnp.arange(MAX_TILES, dtype=_F)
        ch_oh = dram_channel_one_hot(jnp, tidx_f, chip["dram_channels"])

        def step(carry, op):
            (tile_finish, op_finish, cached_at, fifo_ops, fifo_bytes,
             tile_ops, tile_active, tile_macs, e_mod, cache_ev,
             res_occ) = carry[:11]
            if link:
                link_occ, chan_occ = carry[11], carry[12]
            idx = jnp.asarray(op["index"], jnp.int32)
            active = (op["valid"] > 0) & (op["fused"] == 0)
            owner = jnp.asarray(op["owner"], jnp.int32)
            k = op["n_split"]
            mask = op["split_mask"] > 0
            is_split = k > 1.0
            axis = op["split_axis"]
            onehot = jnp.arange(MAX_TILES) == owner

            # ---- dependency-ready time + input acquisition --------------
            preds = jnp.asarray(op["preds"], jnp.int32)
            pred_ok = preds >= 0
            pidx = jnp.maximum(preds, 0)
            per_pred = op["per_pred_bytes"]
            t_dep = jnp.max(jnp.where(pred_ok, op_finish[pidx], 0.0))
            src = jnp.where(pred_ok, cached_at[pidx], -1)
            hit = pred_ok & (src == owner)
            via_noc = pred_ok & (src >= 0) & (src != owner)
            miss = pred_ok & (src < 0)
            dram_rd = op["bytes_w"] \
                + jnp.sum(jnp.where(miss, per_pred, 0.0)) \
                + jnp.where(op["num_preds"] == 0, op["bytes_in"], 0.0)
            extra_noc_s = jnp.sum(jnp.where(via_noc, noc_seconds(per_pred),
                                            0.0))
            e_noc_in = jnp.sum(jnp.where(via_noc, noc_energy(per_pred), 0.0))
            # write-back: outputs fitting the owner's cache partition skip
            # the DRAM round-trip; oversized outputs spill (§3.3.4)
            dram_wr = jnp.where(op["bytes_out"] > T["cache_cap"][owner],
                                op["bytes_out"], 0.0)

            # ---- dynamic DRAM bandwidth share ----------------------------
            t_start0 = jnp.maximum(tile_finish[owner], t_dep)
            n_active = jnp.maximum(jnp.sum(
                jnp.where(T["exists"] > 0, tile_finish > t_start0, False)),
                1.0)
            bw_share = chip["dram_gbps"] / n_active

            # ---- single-tile execution (on all tiles; owner selected) ----
            ex = cm.execute(T, op, bw_share, dram_rd, dram_wr)
            fin_single = t_start0 + extra_noc_s + ex["seconds"][owner]

            # ---- Eq. 3 split execution (slice_op semantics) --------------
            kf = jnp.maximum(k, 1.0)
            sub = split_op_fields(jnp, op, axis, kf)
            ex_sub = cm.execute(T, sub, bw_share, dram_rd / kf, dram_wr / kf)
            starts_sub = jnp.maximum(tile_finish, t_dep) + extra_noc_s
            fins_sub = jnp.where(mask, starts_sub + ex_sub["seconds"],
                                 -jnp.inf)
            slice_out = op["bytes_out"] / kf
            reduce_s = noc_seconds(slice_out)
            fin_split = jnp.max(fins_sub) + reduce_s
            e_noc_split = (kf - 1.0) * noc_energy(slice_out)

            fin_op = jnp.where(is_split, fin_split, fin_single)

            # ---- state updates ------------------------------------------
            tf_single = jnp.where(onehot, fin_single, tile_finish)
            tf_split = jnp.where(mask, fins_sub, tile_finish)
            tf_split = jnp.where(onehot,
                                 jnp.maximum(tf_split, fin_split), tf_split)
            new_tf = jnp.where(is_split, tf_split, tf_single)
            tile_finish = jnp.where(active, new_tf, tile_finish)

            exec_mask = jnp.where(is_split, mask, onehot)
            tile_ops = tile_ops + jnp.where(active & exec_mask, 1.0, 0.0)
            sec_each = jnp.where(is_split, ex_sub["seconds"], ex["seconds"])
            tile_active = tile_active + jnp.where(active & exec_mask,
                                                  sec_each, 0.0)
            macs_each = jnp.where(is_split, sub["macs"], op["macs"])
            tile_macs = tile_macs + jnp.where(active & exec_mask, macs_each,
                                              0.0)

            # per-module chip energy (ENERGY_MODULES order minus leakage)
            new_e = dict(e_mod)
            for mod, key in (("compute", "e_compute"), ("dram", "e_dram"),
                             ("sram", "e_sram"), ("irf", "e_irf"),
                             ("orf", "e_orf"), ("dsp", "e_dsp"),
                             ("special", "e_special")):
                # e_dram is tile-independent (op-scalar); broadcast before
                # the owner gather
                single_v = jnp.broadcast_to(ex[key], (MAX_TILES,))[owner]
                contrib = jnp.where(
                    is_split,
                    jnp.sum(jnp.where(mask, ex_sub[key], 0.0)),
                    single_v)
                new_e[mod] = e_mod[mod] + jnp.where(active, contrib, 0.0)
            e_noc_op = e_noc_in + jnp.where(is_split, e_noc_split, 0.0)
            new_e["noc"] = e_mod["noc"] + jnp.where(active, e_noc_op, 0.0)
            # PPM energy of fused children + Eq. 6 refund, credited to head
            new_e["dsp"] = new_e["dsp"] + jnp.where(
                active, op["fused_lane_ops"] * c.e_dsp_pj_per_lane_op, 0.0)
            new_e["fuse_savings"] = e_mod["fuse_savings"] + jnp.where(
                active,
                op["fused_refund_bytes"] * c.e_sram_pj_per_byte, 0.0)
            e_mod = new_e

            ev = jnp.stack([jnp.sum(hit), jnp.sum(via_noc), jnp.sum(miss)])
            cache_ev = cache_ev + jnp.where(active, ev.astype(_F),
                                            jnp.zeros(3, _F))

            # shared-resource occupancy per batch (throughput-mode II
            # inputs, mirroring the oracle walk's accumulators): aligned
            # DRAM bytes as charged, and NoC acquisition + reduce seconds
            dram_b_op = jnp.where(
                is_split,
                jnp.sum(jnp.where(mask,
                                  jnp.broadcast_to(ex_sub["dram_bytes"],
                                                   (MAX_TILES,)), 0.0)),
                jnp.broadcast_to(ex["dram_bytes"], (MAX_TILES,))[owner])
            noc_s_op = extra_noc_s + jnp.where(is_split, reduce_s, 0.0)
            occ = jnp.stack([dram_b_op, noc_s_op])
            res_occ = res_occ + jnp.where(active, occ, jnp.zeros(2, _F))

            if link:
                # --- link-fidelity occupancy (mirrors the oracle walk) ---
                # (a) XY-routed acquisition links, one route per via-NoC
                # pred; hit/miss/padded preds yield empty routes (src ==
                # owner / src < 0), so the unconditional adds stay exact.
                owner_f = jnp.asarray(owner, _F)
                acq_rt = xy_route_link_mask(
                    jnp, jnp.asarray(src, _F), owner_f, chip["grid_w"],
                    chip["grid_h"], chip["torus"])
                acq_t = link_seconds(per_pred)
                for p in range(MAX_PREDS):
                    link_occ = link_occ + jnp.where(active,
                                                    acq_rt[p] * acq_t, 0.0)
                # (b) split-reduce links: every split tile sends its output
                # slice to the owner (the owner's own route is empty)
                red_rt = xy_route_link_mask(
                    jnp, tidx_f, owner_f, chip["grid_w"], chip["grid_h"],
                    chip["torus"])
                red_t = link_seconds(slice_out)
                for t in range(MAX_TILES):
                    link_occ = link_occ + jnp.where(
                        active & is_split & mask[t], red_rt[t] * red_t, 0.0)
                # (c) per-channel DRAM bytes, interleaved by executing tile
                dram_each = jnp.where(
                    is_split,
                    jnp.where(mask,
                              jnp.broadcast_to(ex_sub["dram_bytes"],
                                               (MAX_TILES,)), 0.0),
                    jnp.where(onehot,
                              jnp.broadcast_to(ex["dram_bytes"],
                                               (MAX_TILES,)), 0.0))
                for t in range(MAX_TILES):
                    chan_occ = chan_occ + jnp.where(active,
                                                    dram_each[t] * ch_oh[t],
                                                    0.0)

            op_finish = op_finish.at[idx].set(jnp.where(active, fin_op, 0.0))
            fifo_ops, fifo_bytes, cached_at = fifo_insert(
                fifo_ops, fifo_bytes, cached_at, owner, idx,
                op["bytes_out"], T["cache_cap"][owner], active)
            out_carry = (tile_finish, op_finish, cached_at, fifo_ops,
                         fifo_bytes, tile_ops, tile_active, tile_macs,
                         e_mod, cache_ev, res_occ)
            if link:
                out_carry = out_carry + (link_occ, chan_occ)
            return out_carry, None

        e0 = {m: jnp.asarray(0.0, _F)
              for m in ("compute", "dram", "sram", "irf", "orf", "dsp",
                        "special", "noc", "fuse_savings")}
        init = (jnp.zeros(MAX_TILES, _F), jnp.zeros(max_ops, _F),
                jnp.full(max_ops, -1, jnp.int32),
                jnp.full((MAX_TILES, ACT_CACHE_SLOTS), -1, jnp.int32),
                jnp.zeros((MAX_TILES, ACT_CACHE_SLOTS), _F),
                jnp.zeros(MAX_TILES, _F), jnp.zeros(MAX_TILES, _F),
                jnp.zeros(MAX_TILES, _F), e0, jnp.zeros(3, _F),
                jnp.zeros(2, _F))
        if link:
            init = init + (jnp.zeros(MAX_LINKS, _F),
                           jnp.zeros(MAX_DRAM_CHANNELS, _F))
        final, _ = jax.lax.scan(step, init, xs["per_op"])
        (tile_finish, op_finish, cached_at, _, _, tile_ops, tile_active,
         tile_macs, e_mod, cache_ev, res_occ) = final[:11]
        link_occ, chan_occ = (final[11], final[12]) if link else (None, None)

        makespan = jnp.max(tile_finish)
        gated = tile_ops <= 0
        resid = jnp.where(gated, c.power_gate_residual, 1.0)
        leak_t = jnp.where(T["exists"] > 0,
                           c.leak_mw_per_mm2 * T["area_mm2"] * makespan
                           * resid * 1e9, 0.0)
        leakage = tile_sum(leak_t)
        energy = (e_mod["compute"] + e_mod["dram"] + e_mod["sram"]
                  + e_mod["irf"] + e_mod["orf"] + e_mod["dsp"]
                  + e_mod["special"] + e_mod["noc"] + leakage
                  - e_mod["fuse_savings"])
        achieved = jnp.where(makespan > 0, total_macs / makespan / 1e12, 0.0)
        out = {"latency_s": makespan, "energy_pj": energy,
               "achieved_tops": achieved, "op_finish": op_finish,
               "tile_ops": tile_ops, "tile_active_s": tile_active,
               "tile_macs": tile_macs, "power_gated": gated,
               "cache_hits": cache_ev[0], "cache_noc": cache_ev[1],
               "cache_misses": cache_ev[2], "tile_leakage_pj": leak_t,
               "energy_leakage_pj": leakage}
        for m in e_mod:
            out[f"energy_{m}_pj"] = e_mod[m]

        # ---- throughput-mode steady state (§3.2): same composition as
        # ChipSim._steady_state, via the shared costs.pipeline_bounds ----
        dram_bytes, noc_busy = res_occ[0], res_occ[1]
        leak_rate = tile_sum(jnp.where(T["exists"] > 0,
                                       c.leak_mw_per_mm2 * T["area_mm2"]
                                       * resid * 1e9, 0.0))
        out.update(pipeline_bounds(
            jnp, makespan, jnp.max(tile_active), dram_bytes,
            chip["dram_gbps"], noc_busy, chan_bytes=chan_occ,
            dram_channels=chip["dram_channels"] if link else None,
            link_busy_s=link_occ))
        ii = out["ii_s"]
        out["fill_latency_s"] = makespan
        out["dram_bytes_per_batch"] = dram_bytes
        out["energy_ss_pj"] = steady_state_energy(energy, leakage,
                                                  leak_rate, ii)
        out["achieved_tops_ss"] = jnp.where(ii > 0,
                                            total_macs / ii / 1e12, 0.0)
        out["pipeline_depth"] = jnp.where(ii > 0, jnp.ceil(div_exact(jnp, makespan, ii)),
                                          1.0)
        return out

    return exec_plan


_CALIB_REGISTRY: Dict[int, CalibrationTable] = {}


@functools.lru_cache(maxsize=64)
def _jitted(calib_key: int, max_ops: int, fidelity: str = "aggregate"):
    calib = _CALIB_REGISTRY[calib_key]
    fn = _build_plan_exec(calib, max_ops, fidelity)
    batched = jax.vmap(fn, in_axes=({k: 0 for k in TILE_KEYS},
                                    {k: 0 for k in CHIP_KEYS}, 0, 0))
    return jax.jit(batched)


def batch_simulate(plans: Dict[str, np.ndarray],
                   cfgs: Dict[str, Dict[str, np.ndarray]],
                   calib: CalibrationTable = DEFAULT_CALIB,
                   mode: Optional[str] = None,
                   fidelity: str = "aggregate") -> Dict[str, np.ndarray]:
    """Execute stacked plan tables against stacked chip configs.

    ``plans`` comes from ``stack_plan_tables`` (candidate b's plan must
    target candidate b's chip); ``cfgs`` from ``stack_chip_configs`` (or
    the DSE engine's vectorized genome stack).  Returns (B,) arrays:
    ``latency_s``, ``energy_pj``, ``achieved_tops``, per-module
    ``energy_*_pj``, cache event counts, and (B, MAX_TILES) per-tile op /
    active-time / gating stats — the SimResult surface minus the per-op
    trace, which stays with the oracle.

    ``mode`` defaults to the stacked tables' stamped schedule mode
    (``PlanTensor.mode``); throughput-mode results additionally carry the
    pipeline steady state — ``ii_s``, ``fill_latency_s``, the three
    per-resource ``ii_*_bound_s``, ``energy_ss_pj``,
    ``achieved_tops_ss`` and ``pipeline_depth`` — matching
    ``ChipSim._steady_state`` through the shared
    ``costs.pipeline_bounds`` composition.  A mode outside
    ``SCHEDULE_MODES`` raises instead of silently returning latency
    numbers.
    """
    mode = mode if mode is not None else plans.get("mode", "latency")
    if mode not in SCHEDULE_MODES:
        raise ValueError(
            f"batched executor cannot model schedule mode {mode!r}; "
            f"supported modes: {SCHEDULE_MODES}")
    if fidelity not in FIDELITIES:
        raise ValueError(
            f"unknown fidelity {fidelity!r}; supported: {FIDELITIES}")
    key = id(calib)
    _CALIB_REGISTRY[key] = calib
    max_ops = plans["op_type"].shape[1]
    per_op = {f: jnp.asarray(plans[f], _F) for f in _OP_TABLE_KEYS}
    per_op["preds"] = jnp.asarray(plans["preds"], jnp.int32)
    per_op["owner"] = jnp.asarray(plans["owner"], jnp.int32)
    per_op["n_split"] = jnp.asarray(plans["n_split"], _F)
    per_op["split_axis"] = jnp.asarray(plans["split_axis"], jnp.int32)
    per_op["split_mask"] = jnp.asarray(plans["split_mask"], _F)
    B = per_op["op_type"].shape[0]
    per_op["index"] = jnp.broadcast_to(jnp.arange(max_ops, dtype=jnp.int32),
                                       (B, max_ops))
    xs = {"per_op": per_op}
    tile = {k: jnp.asarray(cfgs["tile"][k], _F) for k in TILE_KEYS}
    chip = {k: jnp.asarray(cfgs["chip"][k], _F) for k in CHIP_KEYS}
    fn = _jitted(key, max_ops, fidelity)
    out = fn(tile, chip, xs, jnp.asarray(plans["total_macs"], _F))
    res = {k: np.asarray(v) for k, v in out.items()}
    res["area_mm2"] = cfgs["chip"]["chip_area"]
    res["peak_tops"] = cfgs["chip"]["peak_tops"]
    res["mode"] = mode
    return res


def simulate_plans(chips: Sequence[ChipConfig], tables: Sequence[PlanTensor],
                   calib: CalibrationTable = DEFAULT_CALIB,
                   fidelity: str = "aggregate") -> Dict[str, np.ndarray]:
    """Convenience wrapper: stack ``chips`` + their ``tables`` and execute."""
    if len(chips) != len(tables):
        raise ValueError("one plan table per chip required")
    return batch_simulate(stack_plan_tables(tables),
                          stack_chip_configs(chips, calib), calib,
                          fidelity=fidelity)
