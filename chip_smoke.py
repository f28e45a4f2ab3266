#!/usr/bin/env python3
"""Drive the DSE main path once on a TPU and check what comes out.

    python3 chip_smoke.py             # one chip: the three phases below
    python3 chip_smoke.py --chips 4   # four chips: the island-sharded study
                                      # against the same study on one device

Everything runs in this one process (a chip belongs to one process), the
service included; its TCP front is a thread of this process.

Phase 1, the paper's section 4 study at its published width: all 20 suite
workloads, ``run_pipeline`` with the GA at population 200 (generations cut
to 3), one seed, one area bracket, a stratified sweep of 4 samples per
stratum, on ``EngineConfig(backend="exact")``.
Phase 2, the kernel's other surfaces: 200 genomes in throughput mode at
link fidelity on a MAC-heavy (llama7b_int4) and a non-MAC (kan) workload.
Phase 3, the evaluation service on the phase-1 engine: two tenants send
evaluate requests (one in process, one over TCP) and one streams a GA of 2
generations; every answer must equal the same call on a local engine with
the same memo, bitwise (same device, same process).

Correctness: a sample of the final Pareto genomes and of the phase-2 batch
is rescored with the host oracle (``map_graph`` + numpy ``ChipSim``).
Placements (owner, n_split, split_axis) from ``map_and_simulate`` on the
chip must equal the host mapper's, and float metrics must agree within
``REL_TOL``, but for a few near-tie flips (``FLIP_TOL``).  Any failure
exits non-zero, and only a full pass prints the last line
``{"ok": true, "device": {...}}``.  Without a TPU, or without
the repository's ``src/`` next to this file, it exits non-zero at once.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

# TPU float64 is emulated in float32 pairs (about 48 bits of mantissa),
# with division and the transcendentals not correctly rounded; the exact
# path chains ~1000 sequential scan steps of such ops per workload.  On
# XLA:CPU the device and the oracle agree to ~1e-15.  On the TPU a
# (genome, workload) pair is held to 1e-9 relative: far above what ~1000
# emulated-float64 steps accumulate (~1e-14), far below what the same
# path in float32 gives (a float32 run of it on the CPU is off by ~1e-7
# and more), so a silent fall to float32 fails here.
REL_TOL = 1e-9
# The mapper's argmin can meet a near-tie: two placements whose estimated
# finish times agree to ~1e-14.  Rounding that small picks either, and
# the pair's metrics then differ by what the two placements differ (on
# the CPU, scaling the tile clocks by 1 - 1e-14 moves llama7b_int4 by
# 1.05e-4 on one genome in 16; the TPU showed the same 1.05e-4).  Such
# flips are allowed on a few pairs -- at most FLIP_SHARE of them, never
# fewer than 2 -- and each within FLIP_TOL; a fault in the arithmetic
# (a floor off by one moved metrics by 29% to 810% on the chip) or a fall
# to float32 moves far more pairs, or by more.  The bitwise contract on
# the chip is left open (ROADMAP A2 / C9).
FLIP_TOL = 1e-2
FLIP_SHARE = 0.1
BRACKET = 200.0
PLACEMENT_WORKLOADS = ("kan", "llama7b_int4")   # non-MAC, MAC-heavy
SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds JAX spent lowering and compiling since the last ``take()``
    (JAX's own monitoring events; tracing nests and is left in run
    time)."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self._secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            with self._lock:
                self._secs += duration

    def take(self) -> float:
        with self._lock:
            s, self._secs = self._secs, 0.0
        return s


class Stages:
    """Wall seconds per stage, split into compile and run seconds."""

    def __init__(self, clock: CompileClock, phase: str):
        self.clock, self.phase = clock, phase
        self.t = time.perf_counter()
        clock.take()

    def mark(self, stage: str) -> None:
        now = time.perf_counter()
        wall, comp = now - self.t, self.clock.take()
        self.t = now
        log(f"[{self.phase}] stage={stage} compile_s={comp:.3f} "
            f"run_s={max(wall - comp, 0.0):.3f} wall_s={wall:.3f}")


def rel_err(dev, ref):
    """Elementwise relative error over the finite entries (0 elsewhere);
    the inf pattern (unmappable pairs) must match exactly."""
    import numpy as np
    dev, ref = np.asarray(dev, np.float64), np.asarray(ref, np.float64)
    if not np.array_equal(np.isfinite(dev), np.isfinite(ref)):
        raise AssertionError("finite/inf pattern differs from the oracle")
    fin = np.isfinite(ref)
    err = np.zeros(ref.shape)
    err[fin] = np.abs(dev[fin] - ref[fin]) / np.maximum(np.abs(ref[fin]),
                                                        1e-300)
    return err


def flip_allowance(n: int) -> int:
    return max(2, int(FLIP_SHARE * n))


def check_oracle(engine, genomes, chip, label: str) -> float:
    """Metrics the chip produced for ``genomes`` (and the chip areas the
    device computes for the GA's Eq. 8 band) vs the host oracle.  Every
    (genome, workload) pair must agree within REL_TOL, except near-tie
    flips (see FLIP_TOL).  Logs the errors, then raises on a violation.
    Returns the largest error among the pairs within REL_TOL."""
    import numpy as np
    import jax
    from repro.core.dse.ga_device import _chip_area_device
    ref = engine.rescore(genomes, oracle=True)
    area = jax.jit(lambda g: _chip_area_device(g, engine.calib))(
        np.asarray(genomes, np.int32))
    area_err = float(rel_err(np.asarray(area), ref["area"]).max())
    for k in ("latency", "energy", "tops_w"):
        if not np.isfinite(chip[k]).any():
            raise AssertionError(f"{label}: no finite {k}")
    pair = np.maximum.reduce([rel_err(chip[k], ref[k])
                              for k in ("latency", "energy", "tops_w")])
    n = int(np.isfinite(ref["latency"]).sum())
    off = np.argwhere(pair > REL_TOL)
    worst = float(pair[pair <= REL_TOL].max(initial=0.0))
    log(f"[oracle] {label}: {len(genomes)} genomes x "
        f"{len(engine.workloads)} workloads ({n} mappable pairs), max rel "
        f"err {worst:.3e} (tol {REL_TOL:g}), device area {area_err:.3e}; "
        f"{len(off)} near-tie flips (allowed {flip_allowance(n)}, each "
        f"within {FLIP_TOL:g})"
        + "".join(f"; {engine.workloads[j]}#{i} {pair[i, j]:.3e}"
                  for i, j in off))
    if area_err > REL_TOL:
        raise AssertionError(f"{label}: device area rel err {area_err:.3e}")
    if len(off) > flip_allowance(n) or pair.max() > FLIP_TOL:
        raise AssertionError(f"{label}: {len(off)} pairs beyond {REL_TOL:g},"
                             f" largest {pair.max():.3e}")
    return worst


def check_placements(genomes, label: str) -> int:
    """``map_and_simulate`` placements on the chip == ``map_graph`` on the
    host, for the PLACEMENT_WORKLOADS; returns the rows compared."""
    import numpy as np
    from repro.core.compiler.batched_mapper import map_and_simulate
    from repro.core.compiler.mapper import UnmappableError, map_graph
    from repro.core.compiler.pipeline import lower_plan
    from repro.core.compiler.schedule import emit_schedule
    from repro.core.dse.encoding import decode
    from repro.core.dse.engine import (_prepared_graph, genomes_to_configs,
                                       prepared_workload)
    rows, differ = 0, []
    cfgs = genomes_to_configs(genomes)
    for w in PLACEMENT_WORKLOADS:
        ws = prepared_workload(w)
        out = map_and_simulate(ws, cfgs)
        g2 = _prepared_graph(w)
        for b, genome in enumerate(genomes):
            chip = decode(genome)
            try:
                pl = map_graph(g2, chip)
            except UnmappableError:
                if out["ok"][b]:
                    raise AssertionError(f"{label}/{w}/{b}: host "
                                         "unmappable, chip mapped")
                continue
            if not out["ok"][b]:
                raise AssertionError(f"{label}/{w}/{b}: chip unmappable")
            tbl = lower_plan(emit_schedule(g2, pl), chip.num_tiles,
                             max_ops=len(ws["op_type"]))
            if not all(np.array_equal(np.asarray(out[k][b]),
                                      getattr(tbl, k))
                       for k in ("owner", "n_split", "split_axis")):
                differ.append(f"{w}#{b}")
            rows += 1
    log(f"[placements] {label}: {rows - len(differ)} of {rows} (genome, "
        f"workload) plans equal map_graph on "
        f"{', '.join(PLACEMENT_WORKLOADS)}; near-tie flips: {differ} "
        f"(allowed {flip_allowance(rows)})")
    if len(differ) > flip_allowance(rows):
        raise AssertionError(f"{label}: {len(differ)} plans differ from "
                             "map_graph")
    return rows


def study(wls, engine, islands=None, on_stage=None):
    """The phase-1 study: one seed, one bracket, 4 samples per stratum,
    GA population 200 for 3 generations."""
    from repro.core.dse.ga import GAConfig
    from repro.core.dse.pipeline import run_pipeline
    return run_pipeline(wls, seeds=(0,), brackets=(BRACKET,),
                        samples_per_stratum=4,
                        cfg=GAConfig(population=200, generations=3),
                        engine=engine, islands=islands, on_stage=on_stage)


def phase_study(clock):
    import numpy as np
    from repro.core.dse.api import EngineConfig
    from repro.core.dse.engine import EvalEngine
    from repro.core.workloads.suite import workload_names
    wls = workload_names()
    log(f"[phase1] run_pipeline: {len(wls)} workloads, population 200, "
        f"3 generations, seed 0, bracket {BRACKET:g} mm2, 4 samples per "
        "stratum, backend=exact latency aggregate")
    eng = EvalEngine(wls, config=EngineConfig(
        backend="exact", mode="latency", fidelity="aggregate"))
    st = Stages(clock, "phase1")
    res = study(wls, eng, on_stage=lambda ev: st.mark(ev["stage"]))
    fr = res.results[0].get(BRACKET)
    if fr is None:
        raise AssertionError("phase1: no refinement at the bracket")
    if not np.isfinite(fr.best_fitness):
        raise AssertionError("phase1: best fitness is not finite")
    pts = res.front_points
    if pts.ndim != 2 or pts.shape[1] != 3 or len(pts) == 0 \
            or not np.isfinite(pts).all():
        raise AssertionError(f"phase1: bad Pareto front {pts.shape}")
    log(f"[phase1] front {len(pts)} points, best fitness "
        f"{fr.best_fitness!r}, {res.evaluated} GA evaluations")
    sample = res.front_genomes[:3]
    t0 = time.perf_counter()
    # memo hits: the values the fused refinement computed on the chip
    err = check_oracle(eng, sample, eng.evaluate(sample),
                       "phase1 Pareto sample")
    check_placements(sample, "phase1 Pareto sample")
    st.mark("oracle")
    log(f"[phase1] oracle checks took {time.perf_counter() - t0:.1f}s")
    return eng, res, err


def phase_surfaces(clock):
    import numpy as np
    from repro.core.dse.api import EngineConfig
    from repro.core.dse.encoding import random_genomes
    from repro.core.dse.engine import EvalEngine
    wls = ["llama7b_int4", "kan"]
    eng = EvalEngine(wls, config=EngineConfig(
        backend="exact", mode="throughput", fidelity="link"))
    g = random_genomes(np.random.default_rng(2), 200)
    st = Stages(clock, "phase2")
    m = eng.evaluate(g)
    st.mark("score200")
    if m["latency"].shape != (200, 2) \
            or not np.isfinite(m["latency"]).any(axis=0).all():
        raise AssertionError("phase2: no finite II on some workload")
    if np.isnan(m["latency"]).any() or np.isnan(m["energy"]).any():
        raise AssertionError("phase2: NaN metrics")
    idx = np.flatnonzero(np.isfinite(m["latency"]).all(axis=1))[:6]
    err = check_oracle(eng, g[idx], {k: m[k][idx] for k in m if k != "meta"},
                       "phase2 throughput/link sample")
    st.mark("oracle")
    return err


def phase_service(clock, eng, res):
    import numpy as np
    from repro.core.dse.api import EngineConfig
    from repro.core.dse.encoding import random_genomes
    from repro.core.dse.engine import EvalEngine
    from repro.core.dse.ga import GAConfig, run_ga
    from repro.serve.dse_service import DSEClient, DSEService
    # the local reference: same config, same memo contents
    local = EvalEngine(eng.workloads, config=EngineConfig(
        backend="exact", mode="latency", fidelity="aggregate"))
    local.import_memo(*eng.export_memo())
    svc = DSEService(eng).start()
    st = Stages(clock, "phase3")
    try:
        host, port = svc.listen()
        tenants = {"inproc": DSEClient(service=svc),
                   "tcp": DSEClient(address=(host, port))}
        reqs = {name: [random_genomes(np.random.default_rng(100 + 10 * i + r),
                                      8) for r in range(3)]
                for i, name in enumerate(tenants)}
        got, errs = {}, []

        def tenant(name):
            try:
                got[name] = [tenants[name].evaluate(g) for g in reqs[name]]
            except Exception as exc:  # noqa: BLE001 - re-raised below
                errs.append(exc)

        threads = [threading.Thread(target=tenant, args=(n,))
                   for n in tenants]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        if errs or len(got) != len(tenants):
            raise AssertionError(f"phase3: tenant failed: {errs!r}")
        st.mark("evaluate")

        sw = res.sweeps[0]
        fit = sw.fitness(GAConfig().alpha)
        in_b = np.flatnonzero((sw.bracket == BRACKET) & np.isfinite(fit))
        seeds = sw.genomes[in_b[np.argsort(-fit[in_b])]]
        e_homo = sw.homo_baseline()[BRACKET]
        cfg = {"population": 200, "generations": 2}
        events = list(tenants["inproc"].search(seeds, BRACKET, e_homo,
                                               cfg=cfg, seed=1))
        done = events[-1]
        if done.get("event") != "done" or done.get("result") is None:
            raise AssertionError(f"phase3: GA stream ended with {done!r}")
        n_gen = sum(ev.get("event") == "generation" for ev in events)
        st.mark("ga_stream")

        for name in tenants:
            for g, out in zip(reqs[name], got[name]):
                ref = local.evaluate(g)
                for k in ("latency", "energy", "tops_w", "area"):
                    if np.asarray(out[k]).tobytes() != ref[k].tobytes():
                        raise AssertionError(f"phase3: {name} {k} differs "
                                             "from the local engine")
        ga = run_ga(sw, BRACKET, GAConfig(population=200, generations=2),
                    seed=1, engine=local)
        r = done["result"]
        same = (np.array_equal(np.asarray(r["best_genome"]), ga.best_genome)
                and r["best_fitness"] == ga.best_fitness
                and r["history"] == list(ga.history)
                and all(np.asarray(r["best_metrics"][k], np.float64).tobytes()
                        == np.asarray(ga.best_metrics[k],
                                      np.float64).tobytes()
                        for k in ("latency", "energy", "tops_w", "area")))
        if not same:
            raise AssertionError("phase3: streamed GA differs from the "
                                 "local run")
        st.mark("local_reference")
        log(f"[phase3] 2 tenants x 3 evaluate requests + a {n_gen}-event GA "
            "stream: bitwise equal to the local engine; service "
            f"stats {svc.stats.snapshot(svc.max_batch)}")
        for c in tenants.values():
            c.close()
    finally:
        svc.stop()


def run_one_chip(clock) -> float:
    eng, res, e1 = phase_study(clock)
    e2 = phase_surfaces(clock)
    phase_service(clock, eng, res)
    return max(e1, e2)


def run_four_chips(clock) -> float:
    """The island GA sharded over four chips (ring migration as a
    collective permute) against the same islands=4 study on one device."""
    from unittest import mock

    import numpy as np
    import jax
    import repro.launch.mesh as mesh
    from repro.core.compiler.batched_mapper import place_configs
    from repro.core.dse.api import EngineConfig
    from repro.core.dse.encoding import GENOME_LEN, random_genomes
    from repro.core.dse.engine import EvalEngine, genomes_to_configs
    from repro.core.workloads.suite import workload_names
    if len(jax.devices()) != 4:
        raise AssertionError(f"--chips 4 needs 4 devices, found "
                             f"{len(jax.devices())}")
    wls = workload_names()
    sharded = EvalEngine(wls, config=EngineConfig(backend="exact",
                                                  shard=True))
    isl = mesh.island_sharding(4)
    if isl is None or sharded._sharding is None:
        raise AssertionError("no 4-device sharding: the study would run "
                             "on one device")
    pop = jax.device_put(np.zeros((200, GENOME_LEN), np.int32), isl)
    tile, _ = place_configs(genomes_to_configs(
        random_genomes(np.random.default_rng(0), 16)), sharded._sharding)
    for name, arr in (("population", pop), ("candidates", tile["num_macs"])):
        devs = {s.device for s in arr.addressable_shards}
        if len(devs) != 4:
            raise AssertionError(f"{name} array spans {len(devs)} devices")
        log(f"[4chip] {name} {arr.shape} split over {len(devs)} devices: "
            f"shard {arr.addressable_shards[0].data.shape}")
    st = Stages(clock, "4chip")
    a = study(wls, sharded, islands=4)
    st.mark("sharded_study")
    single = EvalEngine(wls, config=EngineConfig(backend="exact"))
    with mock.patch.object(mesh, "island_sharding", lambda n: None):
        b = study(wls, single, islands=4)
    st.mark("one_device_study")
    if a.front_points.shape != b.front_points.shape:
        raise AssertionError(f"fronts differ in size: {a.front_points.shape}"
                             f" vs {b.front_points.shape}")
    ra, rb = a.results[0][BRACKET], b.results[0][BRACKET]
    err = float(max(rel_err(a.front_points, b.front_points).max(),
                    rel_err(ra.history, rb.history).max()))
    if err > REL_TOL:
        raise AssertionError(f"sharded vs one-device rel err {err:.3e}")
    log(f"[4chip] sharded front == one-device front ({len(a.front_points)} "
        f"points), genomes equal: "
        f"{np.array_equal(a.front_genomes, b.front_genomes)}, "
        f"max rel err {err:.3e} (tol {REL_TOL:g})")
    return err


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform})",
              file=sys.stderr)
        return 2
    from importlib.metadata import version
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    log(f"[device] platform={devs[0].platform} kind={devs[0].device_kind!r} "
        f"count={len(devs)} jax={jax.__version__} "
        f"jaxlib={version('jaxlib')} libtpu={version('libtpu')} "
        f"compile_cache={cache}")
    clock = CompileClock()
    t0 = time.perf_counter()
    err = run_four_chips(clock) if args.chips == 4 else run_one_chip(clock)
    log(f"[done] largest relative error against the reference "
        f"{err:.3e} (tol {REL_TOL:g}); total {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
