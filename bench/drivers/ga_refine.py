"""Back-to-back fused GA refinements (traffic kind ``ga_refine``).

Each ``run_ga_fused`` call refines one area bracket and is a seed
boundary: the device memo is preloaded from the engine's store before
the call and drained back after it.  Set-up runs the seeding sweep and
one whole call, which loads or compiles every program the window runs.

``stale_share``, compared for ``correct``: the share of the genomes a
call could score anew that the search did not, read from the drain
(the device memo's entries inserted by the call).  A call could score
its seed population and, in each generation, every child but the
elites, which pass through unchanged: P + generations x (P - elites).
A GA whose breeding hands back its parents, or whose calls ignore their
seed, scores nothing after the first generation or call, and its later
children are all memo hits.
"""
from __future__ import annotations

import time
import traceback
from typing import List

import numpy as np

from harness.compare import Sample
from harness.driver import Window, engine, log, span
from harness.seeds import rng, sub_seed


class Driver:

    def __init__(self, config: dict, mix: dict, seed: int):
        self.config, self.mix, self.seed = config, mix, seed
        self.results: List = []
        self.in_call_s = 0.0

    def setup(self) -> None:
        from repro.core.dse.ga import GAConfig
        from repro.core.dse.sweep import run_sweep
        ga = self.config["ga"]
        self.engine = engine(self.config)
        self.bracket = float(self.mix["bracket"])
        self.gacfg = GAConfig(population=int(ga["population"]),
                              generations=int(ga["generations"]),
                              alpha=float(ga["alpha"]))
        with span("seeding_sweep"):
            self.sweep = run_sweep(
                self.engine.workloads,
                int(self.mix["seeding_samples_per_stratum"]),
                seed=sub_seed(self.seed, 1), brackets=(self.bracket,),
                engine=self.engine)
        if self.bracket not in self.sweep.homo_baseline():
            raise RuntimeError("the seeding sweep found no homogeneous "
                               f"baseline at {self.bracket:g} mm2")
        self._call(sub_seed(self.seed, 2))        # compiles every program
        self.results.clear()
        self._warm_preloads()

    def _warm_preloads(self) -> None:
        """The memo preload pads the store's export to a power of two
        (at least 256): warm each such shape up to the memo's capacity,
        as the store grows through the window."""
        from repro.core.dse.device_memo import memo_from_store
        from repro.core.dse.encoding import GENOME_LEN
        cap = int(self.mix["memo_capacity"])
        W = len(self.engine.workloads)

        class Export:
            workloads = self.engine.workloads

            def __init__(self, n):
                self.n = n

            def export_memo(self, mode=None):
                return (np.zeros((self.n, GENOME_LEN), np.int64),
                        np.zeros((self.n, 3, W), np.float64))

        n = 256
        while n <= max(cap, 256):
            memo_from_store(Export(n), cap).keys.block_until_ready()
            n *= 2

    def _call(self, ga_seed: int):
        """One refinement call; returns (result, rows it drained)."""
        from repro.core.dse.device_memo import drain_to_store, memo_from_store
        from repro.core.dse.ga_device import run_ga_fused
        with span("memo_preload"):
            memo = memo_from_store(self.engine, int(self.mix["memo_capacity"]))
        t0 = time.perf_counter()
        with span("refine_call"):
            fused = run_ga_fused(self.sweep, self.bracket, self.gacfg,
                                 seed=ga_seed, engine=self.engine,
                                 islands=int(self.config["ga"]["islands"]),
                                 memo=memo, store_sync=False)
        self.in_call_s += time.perf_counter() - t0
        with span("memo_drain"):
            fresh = drain_to_store(fused.memo, self.engine)
        self.results.append(fused)
        return fused, fresh

    def window(self, seconds: float) -> Window:
        gens = calls = failed = slots = fresh = 0
        self.in_call_s = 0.0
        P = self.gacfg.population
        elites = max(int(self.gacfg.elitism * P), 1)     # as run_ga_fused
        t0 = time.perf_counter()
        while True:
            try:
                fused, n = self._call(sub_seed(self.seed, 3, calls))
                gens += fused.generations_run
                slots += P + fused.generations_run * (P - elites)
                fresh += n
            except Exception:       # noqa: BLE001 - counted, run goes on
                traceback.print_exc()
                failed += 1
            calls += 1
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        log(f"[refine] {calls} calls, {gens} generations, {fresh} of "
            f"{slots} genomes that could be new scored anew, {self.in_call_s:.3f} of "
            f"{elapsed:.3f} s inside the refinement calls")
        e2e = {"refine_s_per_gen": elapsed / gens} if gens else {}
        return Window(e2e, calls, failed,
                      {"generations": gens, "calls": calls,
                       "seconds": elapsed, "in_call_s": self.in_call_s},
                      {"stale_share": 1.0 - fresh / slots if slots else 1.0})

    def sample(self) -> Sample:
        pops = np.concatenate([r.population for r in self.results])
        m = {k: np.concatenate([r.pop_metrics[k] for r in self.results])
             for k in ("latency", "energy", "tops_w", "area")}
        idx = np.sort(rng(self.seed, 4).choice(
            len(pops), min(int(self.mix["sample_genomes"]), len(pops)),
            replace=False))
        rows = np.stack([m["latency"][idx], m["energy"][idx],
                         m["tops_w"][idx]], axis=1)
        return Sample(pops[idx], list(self.engine.workloads),
                      self.engine.mode, self.engine.fidelity, rows,
                      m["area"][idx])

    def close(self) -> None:
        self.results.clear()
