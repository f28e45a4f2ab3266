"""Exact batched Eq. 1-3 mapper: ``map_graph`` as a jitted/vmapped scan.

The compile-free exact path.  ``map_graph`` re-derives, per candidate
chip, the same placement decision sequence the Python mapper makes —
compatibility filtering, SPECIAL->SFU routing, earliest-start times with
NoC-crossing dependency delays (Eq. 1), the roofline completion-time
argmin with the mapper's *sequential* smallest-tile tie-break (Eq. 2),
and the OC/B/IC split decision with the explicit Eq. 3 reduce/concat
cost — but as one ``lax.scan`` over the op axis with ``(MAX_TILES,)``
tile-field lanes, ``vmap``-ed across candidates and jitted.  Placements
come out as the stacked integer arrays (``owner`` / ``n_split`` /
``split_axis`` / ``split_mask``) that ``simulator.batched`` executes, in
exactly the layout ``compiler.pipeline.lower_plan`` emits, and are
pinned *bitwise* against ``map_graph`` by tests/test_batched_mapper.py.

Why exactness holds: every per-(op, tile) quantity is evaluated through
the shared ``simulator.costs.CostModel`` (literally the code the Python
mapper calls through numpy), the slice arithmetic is the shared
``split_op_fields`` mirror of ``ir.slice_op``, and the one genuinely
sequential piece of ``map_graph`` — the completion-time argmin whose
1e-15 tie band *chains* (a tie-break win updates the incumbent time) —
is replicated as an unrolled fold over the tile axis in ascending index
order rather than approximated with an epsilon-weighted ``argmin`` (the
approximation ``dse.batch_eval`` makes in-scan).

``map_and_simulate`` fuses this mapping scan with the batched plan
executor into a single device dispatch: per-workload arrays are prepared
once (``dse.engine.prepared_workload``) and shared across the candidate
axis (``vmap in_axes=None``), so the whole exact path — compile *and*
simulate — runs without any per-candidate Python work.  With a
``NamedSharding`` over the candidate axis the same dispatch spans every
available device (``launch.mesh.candidate_sharding``).

``search_and_simulate`` is the *search-loop* variant of the same exact
path: mapping and execution fused into ONE ``lax.scan`` (each op is
placed and then immediately executed in the same step), with the cost
model **class-specialized** — ``op_cls`` / ``splittable`` are workload
properties shared across the candidate axis (``vmap in_axes=None``), so
the kernel branches on them with ``lax.cond`` and only the taken class's
sub-models run: MAC operators never evaluate the SFU/lowering math,
DSP operators skip the MAC tiling pass entirely, and the Eq. 3
three-axis split probe runs only for statically splittable MAC ops.
The taken-path arithmetic is term-for-term the full model
(``costs.CostModel.execute_static_{mac,dsp,special}`` /
``roofline_cycles_*`` / ``supports_*``), so the metric surface is
**bitwise identical** to ``map_and_simulate`` — at a fraction of the
compute, and returning only the (B,) scoring surface (no per-op
placement materialization).  This is what ``EvalEngine``'s exact search
backend and the device GA loop dispatch per generation.

The Python ``map_graph`` stays the oracle reference; unmappable
candidates (some op with no compatible tile, the ``UnmappableError``
case) are reported through the ``ok`` output instead of an exception.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import numpy as np

import jax

jax.config.update("jax_enable_x64", True)  # cycle counts overflow f32 ULPs

import jax.numpy as jnp

from ..arch import MAX_TILES
from ..calibrate.asap7 import CalibrationTable, DEFAULT_CALIB
from ..ir import MAX_PREDS, OpClass
from ..simulator.batched import (CHIP_KEYS, SCHEDULE_MODES, TILE_KEYS,
                                 _build_plan_exec, _OP_TABLE_KEYS,
                                 fifo_insert, tile_sum)
from ..simulator.costs import (ACT_CACHE_SLOTS, FIDELITIES,
                               MAX_DRAM_CHANNELS, MAX_LINKS, OP_COST_KEYS,
                               cost_model, div_exact, dram_channel_one_hot,
                               noc_transfer_energy_pj, noc_transfer_seconds,
                               pipeline_bounds, split_op_fields,
                               steady_state_energy, xy_route_link_mask)

__all__ = ["batched_map", "map_and_simulate", "search_and_simulate",
           "search_population", "place_configs"]

_F = jnp.float64

# map_graph's tie band: completion times within 1e-15 s prefer the
# smaller MAC array (compiler.mapper line-for-line).
_TIE = 1e-15

# workload fields the mapper scan consumes beyond the executor's op table
_WS_KEYS = _OP_TABLE_KEYS + ("splittable",)


# =============================================================================
# the mapping scan
# =============================================================================

def _build_mapper(calib: CalibrationTable, max_ops: int,
                  enable_split: bool = True):
    cm = cost_model(calib, jnp)

    def map_one(tile, chip, xs):
        """Map ONE workload onto ONE candidate chip.  tile: dict of
        (MAX_TILES,) arrays; chip: dict of scalars; xs["per_op"]: dict of
        (max_ops, ...) arrays.  Returns the stacked placement arrays plus
        the per-candidate ``ok`` mappability flag."""
        T = tile
        num_macs = T["num_macs"]
        n_tiles = jnp.sum(T["exists"])
        # static per-tile bandwidth share for the estimate domain (§3.2)
        bw_share = chip["dram_gbps"] / n_tiles

        def noc_s(nbytes):
            return noc_transfer_seconds(jnp, nbytes, chip["noc_bpc"],
                                        chip["hops"],
                                        chip["noc_base_cycles"],
                                        chip["ref_clock_hz"])

        def step(carry, op):
            tile_finish, op_finish, op_tile, ok = carry
            idx = jnp.asarray(op["index"], jnp.int32)
            active = (op["valid"] > 0) & (op["fused"] == 0)

            # ---- compatibility + SPECIAL->SFU routing (§3.2) -------------
            compat = cm.supports(T, op)
            native = cm.sfu_native(T, op) & compat
            is_spec = op["op_cls"] == int(OpClass.SPECIAL)
            compat = jnp.where(is_spec & jnp.any(native), native, compat)
            any_compat = jnp.any(compat)

            # ---- Eq. 1 earliest start per tile ---------------------------
            preds = jnp.asarray(op["preds"], jnp.int32)
            pred_ok = preds >= 0
            pidx = jnp.maximum(preds, 0)
            per_pred = op["per_pred_bytes"]
            pf = jnp.where(pred_ok, op_finish[pidx], 0.0)
            ptile = jnp.where(pred_ok, op_tile[pidx], -1)
            # fused / absent preds (op_tile == -1) count as local, exactly
            # like map_graph's op_tile.get(p, t)
            cross = (ptile[:, None] >= 0) \
                & (ptile[:, None] != jnp.arange(MAX_TILES)[None, :])
            dep = jnp.max(jnp.where(
                pred_ok[:, None],
                pf[:, None] + jnp.where(cross, noc_s(per_pred), 0.0),
                0.0), axis=0)
            t_start = jnp.maximum(tile_finish, dep)

            # ---- single-tile candidates (Eq. 2) --------------------------
            c_hat_s = cm.roofline_cycles(T, op, bw_share) / T["clock_hz"]
            fins = t_start + c_hat_s
            # map_graph's argmin is a *sequential* fold whose 1e-15 tie
            # band chains (a tie-break win replaces the incumbent best_fin
            # too); replicate it as an unrolled fold in tile-index order.
            best_t = jnp.asarray(-1, jnp.int32)
            best_fin = jnp.asarray(jnp.inf, _F)
            best_nm = jnp.asarray(0.0, _F)
            for t in range(MAX_TILES):
                fin, nm = fins[t], num_macs[t]
                better = fin < best_fin - _TIE
                tie = (jnp.abs(fin - best_fin) <= _TIE) & (best_t >= 0) \
                    & (nm < best_nm)
                upd = compat[t] & (better | tie)
                best_t = jnp.where(upd, t, best_t).astype(jnp.int32)
                best_fin = jnp.where(upd, fin, best_fin)
                best_nm = jnp.where(upd, nm, best_nm)

            # ---- split candidates (Eq. 3) --------------------------------
            mac_mask = compat & (num_macs > 0)
            ksplit = jnp.sum(mac_mask)
            kf = jnp.maximum(ksplit.astype(_F), 1.0)
            can_split = enable_split \
                & (op["op_cls"] == int(OpClass.MAC)) \
                & (op["splittable"] > 0) & (op["macs"] > 0) & (ksplit > 1)

            def axis_fin(axis):
                sub = split_op_fields(jnp, op, axis, kf)
                ch_s = cm.roofline_cycles(T, sub, bw_share / kf) \
                    / T["clock_hz"]
                fins_s = jnp.where(mac_mask, t_start + ch_s, -jnp.inf)
                # Eq. 3 reduce/concat cost over the NoC
                return jnp.max(fins_s) + noc_s(op["bytes_out"] / kf)

            fins3 = jnp.stack([axis_fin(0), axis_fin(1), axis_fin(2)])
            # sequential strict-< axis loop == first occurrence of the min
            best_axis = jnp.argmin(fins3).astype(jnp.int32)
            do_split = can_split & (fins3[best_axis] < best_fin)

            first_mac = jnp.argmax(mac_mask).astype(jnp.int32)
            owner = jnp.where(do_split, first_mac, best_t)
            choice_fin = jnp.where(do_split, fins3[best_axis], best_fin)

            # ---- state update (map_graph's finish bookkeeping) -----------
            placed = active & any_compat
            onehot = jnp.arange(MAX_TILES) == owner
            tf_single = jnp.where(onehot, choice_fin, tile_finish)
            tf_split = jnp.where(mac_mask,
                                 jnp.maximum(tile_finish, choice_fin),
                                 tile_finish)
            tile_finish = jnp.where(placed,
                                    jnp.where(do_split, tf_split, tf_single),
                                    tile_finish)
            op_finish = op_finish.at[idx].set(
                jnp.where(placed, choice_fin, 0.0))
            op_tile = op_tile.at[idx].set(
                jnp.where(placed, owner, -1).astype(jnp.int32))
            ok = ok & (any_compat | ~active)

            ys = {
                "owner": jnp.where(placed, owner, -1).astype(jnp.int32),
                "n_split": jnp.where(
                    placed, jnp.where(do_split, ksplit, 1),
                    0).astype(jnp.int32),
                "split_axis": jnp.where(placed & do_split, best_axis,
                                        -1).astype(jnp.int32),
                "split_mask": jnp.where(
                    placed, jnp.where(do_split, mac_mask, onehot), False),
            }
            return (tile_finish, op_finish, op_tile, ok), ys

        init = (jnp.zeros(MAX_TILES, _F), jnp.zeros(max_ops, _F),
                jnp.full(max_ops, -1, jnp.int32), jnp.asarray(True))
        (_, _, _, ok), ys = jax.lax.scan(step, init, xs["per_op"])
        ys["ok"] = ok
        return ys

    return map_one


# =============================================================================
# fused mapping + plan execution (one device dispatch per workload)
# =============================================================================

def _build_map_exec(calib: CalibrationTable, max_ops: int,
                    fidelity: str = "aggregate"):
    mapper = _build_mapper(calib, max_ops)
    exec_plan = _build_plan_exec(calib, max_ops, fidelity)

    def run(tile, chip, xs, total_macs):
        placed = mapper(tile, chip, xs)
        per_op = dict(xs["per_op"])
        # unmappable rows carry owner -1; clamp for the executor's gathers
        # (their lanes are discarded through ``ok`` host-side)
        per_op["owner"] = jnp.maximum(placed["owner"], 0)
        per_op["n_split"] = placed["n_split"].astype(_F)
        per_op["split_axis"] = placed["split_axis"]
        per_op["split_mask"] = placed["split_mask"].astype(_F)
        out = exec_plan(tile, chip, {"per_op": per_op}, total_macs)
        out["ok"] = placed["ok"]
        for f in ("owner", "n_split", "split_axis", "split_mask"):
            out[f] = placed[f]
        return out

    return run


# CalibrationTable is hashable (costs._cached_model already keys an LRU
# on it), so the jit caches key on the calib directly — no id()-keyed
# registry like the older jit wrappers carry.
@functools.lru_cache(maxsize=64)
def _jitted_map(calib: CalibrationTable, max_ops: int, enable_split: bool):
    fn = _build_mapper(calib, max_ops, enable_split)
    batched = jax.vmap(fn, in_axes=({k: 0 for k in TILE_KEYS},
                                    {k: 0 for k in CHIP_KEYS}, None))
    return jax.jit(batched)


@functools.lru_cache(maxsize=64)
def _jitted_map_exec(calib: CalibrationTable, max_ops: int,
                     fidelity: str = "aggregate"):
    fn = _build_map_exec(calib, max_ops, fidelity)
    batched = jax.vmap(fn, in_axes=({k: 0 for k in TILE_KEYS},
                                    {k: 0 for k in CHIP_KEYS}, None, None))
    return jax.jit(batched)


def _device_xs(ws: Dict[str, np.ndarray]) -> Tuple[dict, int]:
    max_ops = len(ws["op_type"])
    per_op = {k: jnp.asarray(ws[k], _F) for k in _WS_KEYS}
    per_op["preds"] = jnp.asarray(ws["preds"], jnp.int32)
    per_op["index"] = jnp.arange(max_ops, dtype=jnp.int32)
    return {"per_op": per_op}, max_ops


# Device staging of prepared-workload op tables, cached by identity: the
# search loop dispatches the same handful of ``prepared_workload`` dicts
# every generation, and re-uploading ~30 (max_ops,) arrays per dispatch
# is measurable host overhead.  Holding the ws references in the value
# pins their ids, so a dead dict can never alias a cached entry; the
# caches are FIFO-bounded (dropping an entry releases the pins with it).
_XS_CACHE: Dict[tuple, tuple] = {}
_SEARCH_XS_CACHE: Dict[tuple, tuple] = {}
_XS_CACHE_MAX = 64


def _staged(cache: Dict[tuple, tuple], wss: tuple, stage) -> tuple:
    """Identity-pinned FIFO memo of ``stage(wss)`` for a tuple of
    prepared-workload dicts, shared by the two staging caches."""
    key = tuple(map(id, wss))
    hit = cache.get(key)
    if hit is not None and all(a is b for a, b in zip(hit[0], wss)):
        return hit[1:]
    out = stage(wss)
    while len(cache) >= _XS_CACHE_MAX:
        cache.pop(next(iter(cache)))
    cache[key] = (wss,) + out
    return out


def _device_xs_cached(ws: Dict[str, np.ndarray]) -> Tuple[dict, int]:
    return _staged(_XS_CACHE, (ws,), lambda wss: _device_xs(wss[0]))


def place_configs(cfgs, sharding=None):
    """Stage a stacked config dict on device (optionally with the
    candidate-axis ``NamedSharding``) once, so callers looping over
    workloads don't re-place the same (B, MAX_TILES) arrays per
    workload.  Pass the result to ``batched_map`` / ``map_and_simulate``
    as ``placed``."""
    tile = {k: jnp.asarray(cfgs["tile"][k], _F) for k in TILE_KEYS}
    chip = {k: jnp.asarray(cfgs["chip"][k], _F) for k in CHIP_KEYS}
    if sharding is not None:
        put = lambda a: jax.device_put(a, sharding)
        tile = {k: put(v) for k, v in tile.items()}
        chip = {k: put(v) for k, v in chip.items()}
    return tile, chip


def batched_map(ws: Dict[str, np.ndarray],
                cfgs: Dict[str, Dict[str, np.ndarray]],
                calib: CalibrationTable = DEFAULT_CALIB,
                enable_split: bool = True,
                sharding=None, placed=None) -> Dict[str, np.ndarray]:
    """Exact Eq. 1-3 mapping of one workload onto B candidate chips.

    ``ws`` is a prepared-workload SoA dict (``dse.batch_eval
    .prepare_workload`` / the engine's ``prepared_workload`` cache);
    ``cfgs`` a stacked config dict (``stack_chip_configs`` or the
    engine's vectorized genome stack).  Returns ``owner`` (B, max_ops)
    int32, ``n_split`` (B, max_ops) int32, ``split_axis`` (B, max_ops)
    int32, ``split_mask`` (B, max_ops, MAX_TILES) int8 — bitwise the
    arrays ``lower_plan(emit_schedule(g, map_graph(g, chip)))`` produces
    for each candidate — and ``ok`` (B,) bool (False where ``map_graph``
    would raise ``UnmappableError``).
    """
    xs, max_ops = _device_xs_cached(ws)
    tile, chip = placed if placed is not None \
        else place_configs(cfgs, sharding)
    out = _jitted_map(calib, max_ops, enable_split)(tile, chip, xs)
    return {
        "owner": np.asarray(out["owner"], np.int32),
        "n_split": np.asarray(out["n_split"], np.int32),
        "split_axis": np.asarray(out["split_axis"], np.int32),
        "split_mask": np.asarray(out["split_mask"], np.int8),
        "ok": np.asarray(out["ok"], bool),
    }


def map_and_simulate(ws: Dict[str, np.ndarray],
                     cfgs: Dict[str, Dict[str, np.ndarray]],
                     calib: CalibrationTable = DEFAULT_CALIB,
                     sharding=None, placed=None,
                     mode: str = "latency",
                     fidelity: str = "aggregate") -> Dict[str, np.ndarray]:
    """The compile-free exact path: batched Eq. 1-3 mapping fused with the
    batched plan executor in one jitted dispatch.

    Equivalent to, per candidate, ``map_graph`` -> ``emit_schedule`` ->
    ``lower_plan`` -> ``batch_simulate`` (the PR 2 exact path), but with
    zero per-candidate Python work: the workload arrays are shared across
    the candidate axis and the mapping scan feeds the execution scan on
    device.  Returns the ``batch_simulate`` result surface plus the
    placement arrays and the ``ok`` (B,) mappability mask; rows with
    ``ok == False`` (an op with no compatible tile) carry garbage metrics
    and must be discarded by the caller.

    ``mode`` selects the §3.2 schedule mode the caller scores on.  The
    fused dispatch always evaluates both surfaces (the latency makespan
    and the pipelined steady state — ``ii_s``, ``energy_ss_pj``,
    ``achieved_tops_ss`` and the per-resource bounds — cost one shared
    scan), so mode only validates and tags the result; an unknown mode
    raises rather than silently returning latency numbers.
    """
    if mode not in SCHEDULE_MODES:
        raise ValueError(
            f"batched mapper+executor cannot model schedule mode {mode!r}; "
            f"supported modes: {SCHEDULE_MODES}")
    if fidelity not in FIDELITIES:
        raise ValueError(f"fidelity must be one of {FIDELITIES}, got {fidelity!r}")
    xs, max_ops = _device_xs_cached(ws)
    tile, chip = placed if placed is not None \
        else place_configs(cfgs, sharding)
    fn = _jitted_map_exec(calib, max_ops, fidelity)
    out = fn(tile, chip, xs, jnp.asarray(float(ws["total_macs"]), _F))
    res = {k: np.asarray(v) for k, v in out.items()}
    res["area_mm2"] = cfgs["chip"]["chip_area"]
    res["peak_tops"] = cfgs["chip"]["peak_tops"]
    res["mode"] = mode
    return res


# =============================================================================
# the search kernel: single-scan fused map+execute, class-specialized
# =============================================================================

def _build_search(calib: CalibrationTable, n_state: int,
                  enable_split: bool = True, fidelity: str = "aggregate"):
    """ONE loop over the op axis that maps *and* executes each op
    in the same step, with the cost model specialized per operator class.

    The class predicates (``op_cls``, ``splittable``, ``macs > 0``) come
    from the shared workload op table (``vmap in_axes=None``), so every
    ``lax.cond`` below keeps real branch semantics under vmap: only the
    taken class's sub-models are evaluated at runtime.  The taken-path
    arithmetic is the exact restriction of the full model
    (``CostModel.execute_static_*`` / ``roofline_cycles_*`` /
    ``supports_*``), so latency/energy/TOPS (both §3.2 schedule-mode
    surfaces) are bitwise equal to ``map_and_simulate`` — pinned by
    tests/test_ga_device.py and the exact-search parity property.

    The scan axis is the *compacted* op table (``_search_xs_cached``):
    fused children and padding rows — which the full executors cost and
    then gate out with ``active``, 30-90 % of the rows on real graphs —
    are dropped host-side.  ``n_steps`` is the compacted (bucketed)
    scan length; ``n_state`` the ORIGINAL op count, which still sizes
    the per-op state arrays so ``preds`` gathers use original indices
    (an inactive row never writes state, so dropping it is bitwise
    inert; compaction padding carries ``index == n_state`` and its
    state writes fall out via scatter ``mode="drop"``).

    The op loop runs ``n_steps`` iterations, a traced scalar, over a
    table padded to any longer length: one compiled body serves every
    workload whose op count fits ``n_state``, so a program scoring W
    workloads holds this kernel once, not W times (each copy costs tens
    of seconds of TPU compile time).
    """
    cm = cost_model(calib, jnp)
    c = calib
    link = fidelity == "link"

    def run(tile, chip, per_op, n_steps, total_macs):
        T = tile
        num_macs = T["num_macs"]
        n_tiles = jnp.sum(T["exists"])
        # static per-tile bandwidth share of the estimate domain (§3.2)
        bw_share_est = chip["dram_gbps"] / n_tiles
        tidx_f = jnp.arange(MAX_TILES, dtype=_F)
        ch_oh = dram_channel_one_hot(jnp, tidx_f, chip["dram_channels"])

        def noc_s(nbytes):
            return noc_transfer_seconds(jnp, nbytes, chip["noc_bpc"],
                                        chip["hops"],
                                        chip["noc_base_cycles"],
                                        chip["ref_clock_hz"])

        def noc_e(nbytes):
            return noc_transfer_energy_pj(jnp, nbytes,
                                          c.e_noc_pj_per_byte_hop,
                                          chip["hops"])

        def link_seconds(nbytes):
            return noc_transfer_seconds(jnp, nbytes, chip["noc_bpc"], 1.0,
                                        chip["noc_base_cycles"],
                                        chip["ref_clock_hz"])

        def step(carry, op):
            (m_tile_finish, m_op_finish, m_op_tile, ok,
             tile_finish, op_finish, cached_at, fifo_ops, fifo_bytes,
             tile_ops, tile_active, e_mod, res_occ) = carry[:13]
            if link:
                link_occ, chan_occ = carry[13], carry[14]
            idx = jnp.asarray(op["index"], jnp.int32)
            active = (op["valid"] > 0) & (op["fused"] == 0)

            # workload-static class predicates (shared across candidates)
            is_spec_u = op["op_cls"] == int(OpClass.SPECIAL)
            is_mac_u = op["op_cls"] == int(OpClass.MAC)
            can_split_u = jnp.asarray(enable_split) & is_mac_u \
                & (op["splittable"] > 0) & (op["macs"] > 0)

            # ---- mapping: compat + SPECIAL->SFU routing + Eq. 2 roofline
            def map_spec(o):
                compat0 = cm.supports_special(T, o)
                native = cm.sfu_native(T, o) & compat0
                compat1 = jnp.where(jnp.any(native), native, compat0)
                return compat1, cm.roofline_cycles_special(T, o, bw_share_est)

            def map_mac(o):
                return (cm.supports_mac(T, o),
                        cm.roofline_cycles_mac(T, o, bw_share_est))

            def map_dsp(o):
                return (cm.supports_dsp(T, o),
                        cm.roofline_cycles_dsp(T, o, bw_share_est))

            compat, c_hat = jax.lax.cond(
                is_spec_u, map_spec,
                lambda o: jax.lax.cond(is_mac_u, map_mac, map_dsp, o), op)
            any_compat = jnp.any(compat)

            # ---- Eq. 1 earliest start per tile ---------------------------
            preds = jnp.asarray(op["preds"], jnp.int32)
            pred_ok = preds >= 0
            pidx = jnp.maximum(preds, 0)
            per_pred = op["per_pred_bytes"]
            noc_pred_s = noc_s(per_pred)
            pf = jnp.where(pred_ok, m_op_finish[pidx], 0.0)
            ptile = jnp.where(pred_ok, m_op_tile[pidx], -1)
            cross = (ptile[:, None] >= 0) \
                & (ptile[:, None] != jnp.arange(MAX_TILES)[None, :])
            dep = jnp.max(jnp.where(
                pred_ok[:, None],
                pf[:, None] + jnp.where(cross, noc_pred_s, 0.0),
                0.0), axis=0)
            t_start = jnp.maximum(m_tile_finish, dep)
            fins = t_start + c_hat / T["clock_hz"]

            # map_graph's sequential tie-break fold (see _build_mapper)
            best_t = jnp.asarray(-1, jnp.int32)
            best_fin = jnp.asarray(jnp.inf, _F)
            best_nm = jnp.asarray(0.0, _F)
            for t in range(MAX_TILES):
                fin, nm = fins[t], num_macs[t]
                better = fin < best_fin - _TIE
                tie = (jnp.abs(fin - best_fin) <= _TIE) & (best_t >= 0) \
                    & (nm < best_nm)
                upd = compat[t] & (better | tie)
                best_t = jnp.where(upd, t, best_t).astype(jnp.int32)
                best_fin = jnp.where(upd, fin, best_fin)
                best_nm = jnp.where(upd, nm, best_nm)

            # ---- Eq. 3 split probe: statically splittable MAC ops only ---
            mac_mask = compat & (num_macs > 0)

            def probe_split(o):
                ksplit = jnp.sum(mac_mask)
                kf = jnp.maximum(ksplit.astype(_F), 1.0)

                def axis_fin(axis):
                    sub = split_op_fields(jnp, o, axis, kf)
                    ch_s = cm.roofline_cycles_mac(T, sub, bw_share_est / kf) \
                        / T["clock_hz"]
                    fins_s = jnp.where(mac_mask, t_start + ch_s, -jnp.inf)
                    return jnp.max(fins_s) + noc_s(o["bytes_out"] / kf)

                fins3 = jnp.stack([axis_fin(0), axis_fin(1), axis_fin(2)])
                best_axis = jnp.argmin(fins3).astype(jnp.int32)
                do_split = (ksplit > 1) & (fins3[best_axis] < best_fin)
                return ksplit, best_axis, do_split, fins3[best_axis]

            def no_split(o):
                return (jnp.asarray(0, jnp.sum(mac_mask).dtype),
                        jnp.asarray(-1, jnp.int32),
                        jnp.asarray(False), jnp.asarray(jnp.inf, _F))

            ksplit, best_axis, do_split, split_fin = jax.lax.cond(
                can_split_u, probe_split, no_split, op)

            first_mac = jnp.argmax(mac_mask).astype(jnp.int32)
            owner = jnp.where(do_split, first_mac, best_t)
            choice_fin = jnp.where(do_split, split_fin, best_fin)

            # ---- mapping-state update (map_graph's finish bookkeeping) ---
            placed = active & any_compat
            onehot = jnp.arange(MAX_TILES) == owner
            mtf_single = jnp.where(onehot, choice_fin, m_tile_finish)
            mtf_split = jnp.where(mac_mask,
                                  jnp.maximum(m_tile_finish, choice_fin),
                                  m_tile_finish)
            m_tile_finish = jnp.where(
                placed, jnp.where(do_split, mtf_split, mtf_single),
                m_tile_finish)
            # compaction-padding rows carry index == n_state: drop their
            # state writes instead of clipping onto a real op's slot
            m_op_finish = m_op_finish.at[idx].set(
                jnp.where(placed, choice_fin, 0.0), mode="drop")
            m_op_tile = m_op_tile.at[idx].set(
                jnp.where(placed, owner, -1).astype(jnp.int32), mode="drop")
            ok = ok & (any_compat | ~active)

            # ---- execution of this op (batched.exec_plan semantics) ------
            k_ex = jnp.where(placed & do_split, ksplit, 1).astype(_F)
            mask = jnp.where(do_split, mac_mask, onehot) & placed
            is_split = k_ex > 1.0

            t_dep_e = jnp.max(jnp.where(pred_ok, op_finish[pidx], 0.0))
            src = jnp.where(pred_ok, cached_at[pidx], -1)
            via_noc = pred_ok & (src >= 0) & (src != owner)
            miss = pred_ok & (src < 0)
            dram_rd = op["bytes_w"] \
                + jnp.sum(jnp.where(miss, per_pred, 0.0)) \
                + jnp.where(op["num_preds"] == 0, op["bytes_in"], 0.0)
            extra_noc_s = jnp.sum(jnp.where(via_noc, noc_pred_s, 0.0))
            e_noc_in = jnp.sum(jnp.where(via_noc, noc_e(per_pred), 0.0))
            dram_wr = jnp.where(op["bytes_out"] > T["cache_cap"][owner],
                                op["bytes_out"], 0.0)

            t_start0 = jnp.maximum(tile_finish[owner], t_dep_e)
            n_active = jnp.maximum(jnp.sum(
                jnp.where(T["exists"] > 0, tile_finish > t_start0, False)),
                1.0)
            bw = chip["dram_gbps"] / n_active

            def ex_spec(o):
                return cm.execute_static_special(T, o)

            def ex_mac(o):
                return cm.execute_static_mac(T, o)

            def ex_dsp(o):
                return cm.execute_static_dsp(T, o)

            st = jax.lax.cond(
                is_spec_u, ex_spec,
                lambda o: jax.lax.cond(is_mac_u, ex_mac, ex_dsp, o), op)
            ex = cm.execute_dynamic(st, T, bw, dram_rd, dram_wr)
            fin_single = t_start0 + extra_noc_s + ex["seconds"][owner]

            def exec_split(o):
                kf = jnp.maximum(k_ex, 1.0)
                sub = split_op_fields(jnp, o, best_axis, kf)
                st_s = cm.execute_static_mac(T, sub)  # splits are MAC ops
                ex_s = cm.execute_dynamic(st_s, T, bw, dram_rd / kf,
                                          dram_wr / kf)
                starts_sub = jnp.maximum(tile_finish, t_dep_e) + extra_noc_s
                fins_sub = jnp.where(mask, starts_sub + ex_s["seconds"],
                                     -jnp.inf)
                slice_out = o["bytes_out"] / kf
                reduce_s = noc_s(slice_out)
                e_split = {m: ex_s[m] for m in
                           ("e_compute", "e_dram", "e_sram", "e_irf",
                            "e_orf", "e_dsp", "e_special")}
                return (ex_s["seconds"], fins_sub,
                        jnp.max(fins_sub) + reduce_s,
                        (kf - 1.0) * noc_e(slice_out), reduce_s,
                        e_split, ex_s["dram_bytes"])

            def exec_no_split(o):
                z = jnp.zeros(MAX_TILES, _F)
                zs = jnp.asarray(0.0, _F)
                e_split = {m: z for m in ("e_compute", "e_sram", "e_irf",
                                          "e_orf", "e_dsp", "e_special")}
                e_split["e_dram"] = zs   # e_dram is op-scalar, not per-tile
                return (z, z - jnp.inf, zs, zs, zs, e_split, zs)

            (sec_sub, fins_sub, fin_split, e_noc_split, reduce_s, e_sub,
             dram_b_sub) = jax.lax.cond(can_split_u, exec_split,
                                        exec_no_split, op)

            fin_op = jnp.where(is_split, fin_split, fin_single)

            tf_single = jnp.where(onehot, fin_single, tile_finish)
            tf_split = jnp.where(mask, fins_sub, tile_finish)
            tf_split = jnp.where(onehot,
                                 jnp.maximum(tf_split, fin_split), tf_split)
            new_tf = jnp.where(is_split, tf_split, tf_single)
            tile_finish = jnp.where(placed, new_tf, tile_finish)

            exec_mask = jnp.where(is_split, mask, onehot)
            tile_ops = tile_ops + jnp.where(placed & exec_mask, 1.0, 0.0)
            sec_each = jnp.where(is_split, sec_sub, ex["seconds"])
            tile_active = tile_active + jnp.where(placed & exec_mask,
                                                  sec_each, 0.0)

            new_e = dict(e_mod)
            for mod, key in (("compute", "e_compute"), ("dram", "e_dram"),
                             ("sram", "e_sram"), ("irf", "e_irf"),
                             ("orf", "e_orf"), ("dsp", "e_dsp"),
                             ("special", "e_special")):
                single_v = jnp.broadcast_to(ex[key], (MAX_TILES,))[owner]
                contrib = jnp.where(
                    is_split,
                    jnp.sum(jnp.where(
                        mask, jnp.broadcast_to(e_sub[key], (MAX_TILES,)),
                        0.0)),
                    single_v)
                new_e[mod] = e_mod[mod] + jnp.where(placed, contrib, 0.0)
            e_noc_op = e_noc_in + jnp.where(is_split, e_noc_split, 0.0)
            new_e["noc"] = e_mod["noc"] + jnp.where(placed, e_noc_op, 0.0)
            new_e["dsp"] = new_e["dsp"] + jnp.where(
                placed, op["fused_lane_ops"] * c.e_dsp_pj_per_lane_op, 0.0)
            new_e["fuse_savings"] = e_mod["fuse_savings"] + jnp.where(
                placed,
                op["fused_refund_bytes"] * c.e_sram_pj_per_byte, 0.0)
            e_mod = new_e

            dram_b_op = jnp.where(
                is_split,
                jnp.sum(jnp.where(
                    mask, jnp.broadcast_to(dram_b_sub, (MAX_TILES,)), 0.0)),
                jnp.broadcast_to(ex["dram_bytes"], (MAX_TILES,))[owner])
            noc_s_op = extra_noc_s + jnp.where(is_split, reduce_s, 0.0)
            occ = jnp.stack([dram_b_op, noc_s_op])
            res_occ = res_occ + jnp.where(placed, occ, jnp.zeros(2, _F))

            if link:
                # per-link XY routes + per-DRAM-channel bytes, identical
                # accumulation to batched._build_plan_exec (parity holds
                # because every ok row adds the same float contributions
                # in the same op order; empty routes add exact 0.0)
                owner_f = jnp.asarray(owner, _F)
                acq_rt = xy_route_link_mask(jnp, jnp.asarray(src, _F),
                                            owner_f, chip["grid_w"],
                                            chip["grid_h"], chip["torus"])
                acq_t = link_seconds(per_pred)
                for p in range(MAX_PREDS):
                    link_occ = link_occ + jnp.where(placed,
                                                    acq_rt[p] * acq_t, 0.0)
                red_rt = xy_route_link_mask(jnp, tidx_f, owner_f,
                                            chip["grid_w"], chip["grid_h"],
                                            chip["torus"])
                red_t = link_seconds(op["bytes_out"]
                                     / jnp.maximum(k_ex, 1.0))
                for t in range(MAX_TILES):
                    link_occ = link_occ + jnp.where(
                        placed & is_split & mask[t], red_rt[t] * red_t, 0.0)
                dram_each = jnp.where(
                    is_split,
                    jnp.where(mask, jnp.broadcast_to(dram_b_sub,
                                                     (MAX_TILES,)), 0.0),
                    jnp.where(onehot, jnp.broadcast_to(ex["dram_bytes"],
                                                       (MAX_TILES,)), 0.0))
                for t in range(MAX_TILES):
                    chan_occ = chan_occ + jnp.where(placed,
                                                    dram_each[t] * ch_oh[t],
                                                    0.0)

            op_finish = op_finish.at[idx].set(
                jnp.where(placed, fin_op, 0.0), mode="drop")
            fifo_ops, fifo_bytes, cached_at = fifo_insert(
                fifo_ops, fifo_bytes, cached_at, owner, idx,
                op["bytes_out"], T["cache_cap"][owner], placed)
            out_c = (m_tile_finish, m_op_finish, m_op_tile, ok,
                     tile_finish, op_finish, cached_at, fifo_ops, fifo_bytes,
                     tile_ops, tile_active, e_mod, res_occ)
            if link:
                out_c = out_c + (link_occ, chan_occ)
            return out_c, None

        e0 = {m: jnp.asarray(0.0, _F)
              for m in ("compute", "dram", "sram", "irf", "orf", "dsp",
                        "special", "noc", "fuse_savings")}
        init = (jnp.zeros(MAX_TILES, _F), jnp.zeros(n_state, _F),
                jnp.full(n_state, -1, jnp.int32), jnp.asarray(True),
                jnp.zeros(MAX_TILES, _F), jnp.zeros(n_state, _F),
                jnp.full(n_state, -1, jnp.int32),
                jnp.full((MAX_TILES, ACT_CACHE_SLOTS), -1, jnp.int32),
                jnp.zeros((MAX_TILES, ACT_CACHE_SLOTS), _F),
                jnp.zeros(MAX_TILES, _F), jnp.zeros(MAX_TILES, _F),
                e0, jnp.zeros(2, _F))
        if link:
            init = init + (jnp.zeros(MAX_LINKS, _F),
                           jnp.zeros(MAX_DRAM_CHANNELS, _F))
        def body(i, carry):
            op = {k: jax.lax.dynamic_index_in_dim(v, i, keepdims=False)
                  for k, v in per_op.items()}
            return step(carry, op)[0]

        final = jax.lax.fori_loop(0, n_steps, body, init)
        (_, _, _, ok, tile_finish, _, _, _, _, tile_ops, tile_active,
         e_mod, res_occ) = final[:13]
        link_occ, chan_occ = (final[13], final[14]) if link else (None, None)

        # final surface: batched.exec_plan's reductions, verbatim
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
               "achieved_tops": achieved, "ok": ok}
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

    return run


def _search_xs_cached(ws_list):
    """Compacted device staging of a workload list for the search kernel
    (same identity-pinned cache as ``_device_xs_cached``): (per_op dict
    of (W, S, ...) arrays, (W,) int32 step counts, n_state, (W,)
    total MACs)."""
    return _staged(_SEARCH_XS_CACHE, tuple(ws_list), _search_xs)


def _search_xs(ws_list):
    """Stack the W workloads' compacted op tables (active, unfused rows
    only) into one (W, S, ...) table.  Workload w runs its first
    ``steps[w]`` rows; the rest is padding it never reads.  ``n_state``
    is the largest ORIGINAL op count, so each workload's ``preds`` and
    ``index`` keep their original values; padding rows carry
    ``index == n_state`` (scatter-dropped) and no predecessors."""
    sels = [np.flatnonzero((np.asarray(ws["valid"]) > 0)
                           & (np.asarray(ws["fused"]) == 0))
            for ws in ws_list]
    n_state = max(len(ws["op_type"]) for ws in ws_list)
    # bucket the table length (multiples of 16): it is a data shape, so
    # near-size workload sets share one compiled program
    S = max(-(-max(len(sel) for sel in sels) // 16) * 16, 16)

    def pad(a, fill):
        out = np.full((S,) + a.shape[1:], fill, a.dtype)
        out[:len(a)] = a
        return out

    per_op = {}
    for k in _WS_KEYS:
        per_op[k] = jnp.asarray(np.stack(
            [pad(np.asarray(ws[k], np.float64)[sel], 0.0)
             for ws, sel in zip(ws_list, sels)]))
    per_op["preds"] = jnp.asarray(np.stack(
        [pad(np.asarray(ws["preds"], np.int32)[sel], -1)
         for ws, sel in zip(ws_list, sels)]))
    per_op["index"] = jnp.asarray(np.stack(
        [pad(sel.astype(np.int32), n_state) for sel in sels]))
    steps = jnp.asarray([len(sel) for sel in sels], jnp.int32)
    tms = jnp.asarray([float(ws["total_macs"]) for ws in ws_list], _F)
    return per_op, steps, n_state, tms


@functools.lru_cache(maxsize=64)
def _jitted_search_population(calib: CalibrationTable, n_state: int,
                              enable_split: bool = True,
                              fidelity: str = "aggregate"):
    """One jitted dispatch evaluating a candidate batch on EVERY workload
    of a generation: the single-scan search kernel runs once per
    workload (``lax.map`` over the stacked op tables of
    ``_search_xs``), so a GA generation costs one evaluation dispatch
    instead of W, and the program holds one copy of the kernel whatever
    W is.  Returns a dict of (B, W) arrays."""
    fn = _build_search(calib, n_state, enable_split, fidelity)

    def run_all(tile, chip, per_op, steps, tms):
        return jax.lax.map(lambda a: fn(tile, chip, *a),
                           (per_op, steps, tms))

    batched = jax.vmap(run_all, in_axes=({k: 0 for k in TILE_KEYS},
                                         {k: 0 for k in CHIP_KEYS},
                                         None, None, None))
    return jax.jit(batched)


def search_population(ws_list, cfgs, calib: CalibrationTable = DEFAULT_CALIB,
                      sharding=None, placed=None, mode: str = "latency",
                      out_keys: Optional[Tuple[str, ...]] = None,
                      fidelity: str = "aggregate"):
    """Exact search scoring of one candidate batch on a list of prepared
    workloads, as ONE device dispatch (see ``_jitted_search_population``).
    Returns one result dict per workload — the ``search_and_simulate``
    surface (restricted to ``out_keys`` + ``ok`` when given: the engine
    fetches only the mode's three metric columns).  This is what
    ``EvalEngine(backend="exact")`` dispatches per miss batch, and hence
    what the device GA loop costs per generation."""
    if mode not in SCHEDULE_MODES:
        raise ValueError(
            f"exact search kernel cannot model schedule mode {mode!r}; "
            f"supported modes: {SCHEDULE_MODES}")
    if fidelity not in FIDELITIES:
        raise ValueError(f"fidelity must be one of {FIDELITIES}, got {fidelity!r}")
    per_op, steps, n_state, tms = _search_xs_cached(ws_list)
    tile, chip = placed if placed is not None \
        else place_configs(cfgs, sharding)
    fn = _jitted_search_population(calib, n_state, True, fidelity)
    out = fn(tile, chip, per_op, steps, tms)
    keys = out.keys() if out_keys is None else tuple(out_keys) + ("ok",)
    host = {k: np.asarray(out[k]) for k in keys}
    results = []
    for j in range(len(ws_list)):
        res = {k: np.ascontiguousarray(v[:, j]) for k, v in host.items()}
        res["area_mm2"] = cfgs["chip"]["chip_area"]
        res["peak_tops"] = cfgs["chip"]["peak_tops"]
        res["mode"] = mode
        results.append(res)
    return results


def search_and_simulate(ws: Dict[str, np.ndarray],
                        cfgs: Dict[str, Dict[str, np.ndarray]],
                        calib: CalibrationTable = DEFAULT_CALIB,
                        sharding=None, placed=None,
                        mode: str = "latency",
                        fidelity: str = "aggregate") -> Dict[str, np.ndarray]:
    """The exact *search* dispatch: one class-specialized scan that maps
    and executes every (active) op, returning only the (B,) scoring
    surface.

    Metrics are bitwise equal to ``map_and_simulate`` (same formulas —
    only the untaken operator-class branches and the inert fused/padding
    rows are skipped), for a fraction of its wall-clock: no second scan
    pass, no per-op placement materialization, no untaken-class
    arithmetic, no dead scan steps.  Both §3.2 schedule surfaces ride in
    the one scan; ``mode`` validates and tags the result.  Rows with
    ``ok == False`` carry garbage metrics and must be discarded by the
    caller.  For scoring several workloads per batch, prefer
    ``search_population`` (one dispatch for all of them).
    """
    return search_population([ws], cfgs, calib, sharding=sharding,
                             placed=placed, mode=mode, fidelity=fidelity)[0]
