"""Design-space exploration (paper §3.5 flow): stratified sweep over the
12-knob space -> per-area-budget GA refinement -> Pareto front.

  PYTHONPATH=src python examples/dse_search.py [--samples 24] [--budget 200]

``--pipeline`` runs the §4 multi-seed study instead: per-seed stratified
sweeps feeding fused (device-memo, single-dispatch) island-GA
refinements across every area bracket, merged into one cumulative
Pareto front on device:

  PYTHONPATH=src python examples/dse_search.py --pipeline --seeds 0 1

``--checkpoint DIR`` (with ``--pipeline``) makes every completed stage
durable in DIR: kill the run at any point — SIGKILL included — and
rerunning the same command resumes where it left off, bitwise equal to
an uninterrupted run.  The directory also hosts the study's persistent
result store (``results.sqlite``).
"""
import argparse
import warnings

import numpy as np

from repro.core.dse.encoding import decode
from repro.core.dse.api import EngineConfig
from repro.core.dse.engine import EvalEngine
from repro.core.dse.ga import GAConfig, run_ga
from repro.core.dse.pareto import pareto_front
from repro.core.dse.sweep import run_sweep
from repro.compile_cache import enable_compile_cache


def run_pipeline_demo(args):
    from repro.core.dse.pipeline import run_pipeline

    def stage(e):
        if e["stage"] == "sweep":
            print(f"   seed {e['seed']}: swept {e['configs']} configs "
                  f"({e['seconds']:.1f}s)")
        elif e["stage"] == "refine":
            print(f"   seed {e['seed']} @ {e['bracket']:5.0f} mm^2: "
                  f"fitness {e['best_fitness']:+.3f} "
                  f"({e['generations']} gens, {e['seconds']:.1f}s, "
                  f"front {len(e['front']['points'])})"
                  if not e.get("skipped") else
                  f"   seed {e['seed']} @ {e['bracket']:5.0f} mm^2: skipped "
                  f"(no homogeneous baseline)")
        elif e["stage"] == "seed_done":
            print(f"   seed {e['seed']}: drained {e['drained']} "
                  f"device-scored rows to the store")
        if e.get("resumed"):
            print("      ^ resumed from checkpoint (not recomputed)")

    print(f"pipeline: seeds {args.seeds}, "
          f"{args.samples}/stratum sweeps, population {args.population}"
          + (f", checkpoint {args.checkpoint}" if args.checkpoint else ""))
    res = run_pipeline(args.workloads, seeds=tuple(args.seeds),
                       samples_per_stratum=args.samples,
                       cfg=GAConfig(population=args.population,
                                    generations=8, early_stop=4),
                       checkpoint=args.checkpoint, on_stage=stage)
    print(f"\ncumulative Pareto front: {len(res.front_points)} points "
          f"({res.evaluated} genomes evaluated)")
    for pt, g in list(zip(res.front_points, res.front_genomes))[:8]:
        chip = decode(np.asarray(g))
        print(f"   E={pt[0]*1e-6:9.1f}uJ  A={pt[1]:6.1f}mm2  "
              f"L={pt[2]*1e3:8.2f}ms  ({len(chip.tiles)} tile types)")
    for b in res.brackets:
        best = res.best(b)
        if best is not None:
            print(f"   best @ {b:5.0f} mm^2: fitness "
                  f"{best.best_fitness:+.3f}")


def main():
    warnings.filterwarnings("ignore")
    ap = argparse.ArgumentParser()
    ap.add_argument("--samples", type=int, default=24)
    ap.add_argument("--budget", type=float, default=200.0)
    ap.add_argument("--workloads", nargs="*", default=[
        "resnet50_int8", "vit_b16_int8", "llama7b_int8", "hyena_1_3b",
        "kan", "spec_decode"])
    ap.add_argument("--exact", action="store_true",
                    help="search on the exact fused-mapper backend: the "
                         "sweep AND the GA score with bitwise-rescore-grade "
                         "metrics (no approximate/rescore gap)")
    ap.add_argument("--pipeline", action="store_true",
                    help="run the §4 multi-seed fused pipeline (implies "
                         "the exact backend) and print the cumulative "
                         "cross-seed Pareto front")
    ap.add_argument("--seeds", type=int, nargs="*", default=[0, 1],
                    help="pipeline sweep seeds (with --pipeline)")
    ap.add_argument("--population", type=int, default=64,
                    help="pipeline GA population (with --pipeline)")
    ap.add_argument("--checkpoint", default=None, metavar="DIR",
                    help="with --pipeline: durable per-stage checkpoints "
                         "in DIR — an interrupted run (even kill -9) "
                         "resumes bitwise-identically on rerun")
    args = ap.parse_args()
    if args.pipeline:
        run_pipeline_demo(args)
        return

    # one cache-aware engine end to end: the GA re-scores sweep genomes
    # (its seed population) and its own elites for free
    engine = EvalEngine(args.workloads, config=EngineConfig(
        backend="exact" if args.exact else "scan"))

    print(f"[1/3] stratified sweep ({args.samples}/stratum x 15 strata)...")
    sw = run_sweep(args.workloads, samples_per_stratum=args.samples, seed=0,
                   verbose=True, engine=engine)
    sav = sw.savings()
    best = np.nanmax(np.where((sw.family > 0)[:, None], sav, np.nan), axis=0)
    for w, s in zip(args.workloads, best):
        print(f"   best iso-area savings {w:16s}: {100*s:+6.1f} %")

    print(f"\n[2/3] GA refinement at {args.budget:.0f} mm^2 ...")
    ga = run_ga(sw, args.budget, GAConfig(population=24, generations=8,
                                          seed_top_k=16, early_stop=4),
                verbose=True, engine=engine)
    chip = decode(ga.best_genome)
    print(f"   winner: {len(chip.tiles)} tile types, "
          f"fitness {ga.best_fitness:+.3f}")
    for t, c in chip.tiles:
        kind = "SFU" if t.sfu_mask else f"{t.rows}x{t.cols}"
        print(f"     {c}x {kind:8s} {sorted(p.name for p in t.precisions)} "
              f"sram={t.sram_kb}KB {t.sparsity.name} @{t.clock_mhz}MHz")

    print("\n[3/3] Pareto front (energy, area, latency) over the sweep ...")
    valid = sw.valid_mask()
    pts = np.stack([sw.energy[valid].mean(1), sw.area[valid],
                    sw.latency[valid].mean(1)], axis=1)
    front = pareto_front(pts)
    print(f"   {len(front)} Pareto-optimal designs of {valid.sum()} valid")
    for i in front[:5]:
        print(f"     E={pts[i,0]*1e-6:9.1f}uJ  A={pts[i,1]:6.1f}mm2  "
              f"L={pts[i,2]*1e3:8.2f}ms")

    st = engine.stats
    print(f"\nengine: {st.misses} simulated / {st.hits} cache hits / "
          f"{st.skips} skipped ({st.hit_rate():.0%} hit rate, "
          f"{st.throughput():,.0f} cfg*wl/s)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
