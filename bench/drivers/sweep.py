"""Back-to-back stratified sweeps (traffic kind ``sweep``), a fresh
sampling seed each call.

``repeat_share``, compared for ``correct``: the share of the genomes
the window's sweeps sent to the engine that its store answered.  Each
call samples anew, so almost none repeat; a sampler that ignores the
call's seed sends the same genomes again, and the store answers them.
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
        self.hits = self.misses = 0

    def setup(self) -> None:
        from repro.core.dse.encoding import FAMILIES
        self.engine = engine(self.config)
        evaluate = self.engine.evaluate

        def counted(*a, **k):
            with span("engine.evaluate"):
                out = evaluate(*a, **k)
            self.hits += out["meta"]["hits"]
            self.misses += out["meta"]["misses"]
            return out

        self.engine.evaluate = counted
        # one batch shape for every call: the full sweep's genome count.
        # The engine pads a batch to the smallest shape it has made within
        # 1.5x, so duplicates or store hits that cut a call's misses below
        # it still run this program.
        n = (len(FAMILIES) * len(self.mix["brackets"])
             * int(self.mix["samples_per_stratum"]))
        self.engine.warmup(buckets=(n,))

    def _call(self, s: int):
        from repro.core.dse.sweep import run_sweep
        with span("run_sweep"):
            sw = run_sweep(self.engine.workloads,
                           int(self.mix["samples_per_stratum"]), seed=s,
                           brackets=tuple(self.mix["brackets"]),
                           engine=self.engine)
        self.results.append(sw)
        return sw

    def window(self, seconds: float) -> Window:
        pairs = calls = failed = 0
        self.hits = self.misses = 0
        t0 = time.perf_counter()
        while True:
            try:
                sw = self._call(sub_seed(self.seed, 3, calls))
                pairs += sw.latency.size
            except Exception:       # noqa: BLE001 - counted, run goes on
                traceback.print_exc()
                failed += 1
            calls += 1
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        asked = self.hits + self.misses
        log(f"[sweep] {calls} calls, {pairs} pairs; the store answered "
            f"{self.hits} of {asked} genomes")
        return Window({"sweep_evals_per_s": pairs / elapsed}, calls, failed,
                      {"calls": calls, "pairs": pairs, "seconds": elapsed},
                      {"repeat_share": self.hits / asked if asked else 1.0})

    def sample(self) -> Sample:
        g = np.concatenate([s.genomes for s in self.results])
        rows = np.concatenate([np.stack([s.latency, s.energy, s.tops_w],
                                        axis=1) for s in self.results])
        idx = np.sort(rng(self.seed, 4).choice(
            len(g), min(int(self.mix["sample_genomes"]), len(g)),
            replace=False))
        return Sample(g[idx], list(self.engine.workloads), self.engine.mode,
                      self.engine.fidelity, rows[idx])

    def close(self) -> None:
        self.results.clear()
