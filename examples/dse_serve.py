"""DSE-as-a-service demo: one shared evaluation service, many tenants.

Runs the §3.5 flow against ``repro.serve.dse_service`` instead of a
private engine: a stratified sweep seeds a persistent content-addressed
result store, two GA tenants then refine *concurrently* through the
service's coalescing queue (their per-generation populations fuse into
shared micro-batches, duplicates served from the store), and a third
search streams live Pareto-front updates as its generations complete.
Results are bitwise identical to a local ``EvalEngine(backend="exact")``
run — the fused metrics are batch-composition independent, so the
coalescing is fidelity-free.

  PYTHONPATH=src python examples/dse_serve.py [--samples 8] [--budget 200]
      [--store results.sqlite] [--tcp] [--workers 3] [--kill-after 10]

Rerun with ``--store`` pointing at the same file to watch the warm
persistent store answer most of the work without touching the engine.

With ``--workers N`` (N > 1) the GA tenants refine through a sharded
``repro.serve.cluster.DSECluster`` over N worker services instead of
one; add ``--kill-after K`` to stop one worker for real while the Kth
shard forms and watch the survivors absorb its load — the results are
bitwise identical either way (that invariant is pinned by the ``-m
chaos`` suite, ``tests/test_cluster.py``).
"""
import argparse
import threading
import warnings

import numpy as np

from repro.core.dse.encoding import decode
from repro.core.dse.api import EngineConfig
from repro.core.dse.engine import EvalEngine
from repro.core.dse.ga import GAConfig, run_ga
from repro.core.dse.store import MemoryLRUStore, SqliteStore, TieredStore
from repro.core.dse.sweep import run_sweep
from repro.serve.dse_service import DSEClient, DSEService
from repro.compile_cache import enable_compile_cache


def main():
    warnings.filterwarnings("ignore")
    ap = argparse.ArgumentParser()
    ap.add_argument("--samples", type=int, default=8)
    ap.add_argument("--budget", type=float, default=200.0)
    ap.add_argument("--workloads", nargs="*",
                    default=["resnet50_int8", "kan", "hyena_1_3b"])
    ap.add_argument("--store", default=None, metavar="PATH",
                    help="persist results to this sqlite file (memory-LRU "
                         "front stays on regardless); rerun to start warm")
    ap.add_argument("--tcp", action="store_true",
                    help="tenants connect over the JSON-lines TCP front "
                         "instead of in-process (same bytes either way)")
    ap.add_argument("--max-wait-ms", type=float, default=50.0)
    ap.add_argument("--workers", type=int, default=1, metavar="N",
                    help="refine through a DSECluster over N worker "
                         "services (default: one plain service)")
    ap.add_argument("--kill-after", type=int, default=None, metavar="K",
                    help="chaos demo: kill one worker while the Kth "
                         "cluster shard forms (requires --workers > 1)")
    args = ap.parse_args()
    if args.kill_after is not None and args.workers < 2:
        ap.error("--kill-after needs --workers > 1")

    store = (TieredStore(MemoryLRUStore(), SqliteStore(args.store))
             if args.store else None)
    engine = EvalEngine(args.workloads, config=EngineConfig(
        backend="exact", store=store))

    print(f"[1/4] stratified sweep ({args.samples}/stratum, warms the "
          f"store)...")
    sw = run_sweep(args.workloads, samples_per_stratum=args.samples, seed=0,
                   brackets=(100.0, args.budget), engine=engine)

    service = DSEService(engine, max_batch=256, max_wait_ms=args.max_wait_ms)
    service.start()
    try:
        if args.tcp:
            host, port = service.listen()
            print(f"      service on tcp://{host}:{port}")
            client = lambda: DSEClient(address=(host, port))  # noqa: E731
        else:
            client = lambda: DSEClient(service=service)      # noqa: E731

        cluster, workers = None, []
        if args.workers > 1:
            from repro.core.dse.faults import FaultInjector
            from repro.serve.cluster import DSECluster
            inj = None
            if args.kill_after is not None:
                inj = FaultInjector(seed=0,
                                    at={"worker_kill": (args.kill_after,)})
            workers = [DSEService(
                EvalEngine(args.workloads,
                           config=EngineConfig(backend="exact")),
                max_batch=256, max_wait_ms=args.max_wait_ms,
                worker_id=f"demo-w{i}").start()
                for i in range(args.workers)]
            cluster = DSECluster(workers, fault_injector=inj)
            kill = (f", killing one worker at shard {args.kill_after}"
                    if inj is not None else "")
            print(f"\n[2/4] two GA tenants refine {args.budget:.0f} mm^2 "
                  f"through a {args.workers}-worker cluster{kill} ...")
        else:
            print(f"\n[2/4] two GA tenants refine {args.budget:.0f} mm^2 "
                  f"concurrently through the service ...")
        cfg = GAConfig(population=24, generations=8, seed_top_k=16,
                       early_stop=10_000)
        results = {}

        def tenant(seed):
            if cluster is not None:
                results[seed] = run_ga(sw, args.budget, cfg, seed=seed,
                                       engine=cluster)
                return
            cl = client()
            results[seed] = run_ga(sw, args.budget, cfg, seed=seed,
                                   engine=cl)
            cl.close()

        threads = [threading.Thread(target=tenant, args=(s,)) for s in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for seed, ga in sorted(results.items()):
            chip = decode(ga.best_genome)
            print(f"      tenant seed={seed}: fitness {ga.best_fitness:+.3f}"
                  f" ({len(chip.tiles)} tile types)")
        if cluster is not None:
            cs = cluster.cluster_stats
            print(f"      cluster: {cs.shards} shards / {cs.dispatches} "
                  f"dispatches, {cs.retried_shards} retried, "
                  f"{cs.worker_failures} worker failures")
            for m in cluster.membership():
                print(f"        {m['name']}: {m['status']} "
                      f"(failures={m['failures']})")
            cluster.close()
            for w in workers:
                w.stop(drain=False)

        print("\n[3/4] streamed server-side search (live Pareto front) ...")
        fit = sw.fitness(cfg.alpha)
        in_b = np.nonzero((sw.bracket == args.budget) & np.isfinite(fit))[0]
        seeds = sw.genomes[in_b[np.argsort(-fit[in_b])][:cfg.seed_top_k]]
        e_homo = sw.homo_baseline()[args.budget]
        cl = client()
        for ev in cl.search(seeds, args.budget, e_homo,
                            cfg={"population": 24, "generations": 6,
                                 "seed_top_k": 16, "early_stop": 10_000},
                            seed=2):
            if ev["event"] == "generation":
                print(f"      gen {ev['gen']:2d}: best "
                      f"{ev['best_fitness']:+.3f}, Pareto front "
                      f"{ev['front_size']} designs")
            elif ev["event"] == "done":
                r = ev["result"]
                print(f"      done: fitness {r['best_fitness']:+.3f} after "
                      f"{r['evaluated']} evaluations")
            else:
                raise RuntimeError(ev.get("error"))
        cl.close()

        print("\n[4/4] service counters ...")
        st = service.stats
        hit = st.store_hits / max(st.request_genomes, 1)
        print(f"      {st.requests} requests / {st.request_genomes} genomes "
              f"-> {st.batches} micro-batches "
              f"({st.coalesced_batches} coalesced across tenants)")
        print(f"      store served {hit:.0%} at admission, "
              f"{st.inflight_merged} merged in flight, "
              f"{st.engine_dispatches} fused engine dispatches")
        print(f"      mean queue {st.mean_queue_ms():.1f} ms, occupancy "
              f"{st.occupancy(service.max_batch):.1%}")
        if args.store:
            print(f"      persistent store: {len(engine.store)} rows in "
                  f"{args.store} (rerun --store to start warm)")
    finally:
        service.stop()


if __name__ == "__main__":
    enable_compile_cache()
    main()
