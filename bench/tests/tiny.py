"""Drive one cell end to end, skipping the look for a chip.

    python3 bench/tests/tiny.py <cell> [--seed N ...] [--seconds S]
        [--full] [--control float32] [--fault KIND]

Runs everything else a run does: set-up, the window, the reference and
the checks; prints one result line per seed.  By default the cell keeps
its kind, engine settings and limits and only its sizes shrink (2
workloads for the study, P=64 for 2 generations, 2 samples per
stratum), so the tests run it on the CPU.  ``--full`` keeps the cell's
own sizes: on a chip it reads the compared numbers of sound runs,
controls and faults over many seeds in one process.  ``--fault`` breaks
the timed path underneath the harness:

``alter``   every answer the program produces has its latency scaled by
            1 + 1e-6 where it is produced
``half``    only the first half of each batch is scored; the second half
            repeats its rows
``frozen``  the state is left unchanged: the GA's breeding hands back its
            parents (``ga_refine``), the sweep's sampler ignores the
            call's seed (``sweep``)
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def shrink(cell):
    if cell.config.get("workloads") == "suite":
        cell.config["workloads"] = ["kan", "resnet50_int8"]
    if "ga" in cell.config:
        cell.config["ga"].update(population=64, generations=2)
    if cell.traffic["kind"] == "sweep":
        cell.traffic["samples_per_stratum"] = 2
    return cell


def _freeze(kind: str) -> None:
    if kind == "ga_refine":
        from repro.core.dse import ga_device

        def parents(pop, fit, key):
            return pop, ga_device._canonical_device(pop)

        ga_device._genetics_kernel = lambda *a, **k: parents
    else:
        from repro.core.dse import sweep
        run_sweep = sweep.run_sweep

        @functools.wraps(run_sweep)
        def same_sample(*a, **k):
            k["seed"] = 0
            return run_sweep(*a, **k)

        sweep.run_sweep = same_sample


def plant(fault: str, kind: str) -> None:
    import numpy as np
    from repro.core.dse import ga_device
    from repro.core.dse.engine import EvalEngine
    if fault == "frozen":
        return _freeze(kind)

    def alter_rows(out):
        out = dict(out)
        out["latency"] = np.asarray(out["latency"]) * (1 + 1e-6)
        return out

    def half_rows(out):
        out = dict(out)
        for k in ("latency", "energy", "tops_w"):
            v = np.array(out[k])
            h = (len(v) + 1) // 2
            v[h:] = v[:len(v) - h]
            out[k] = v
        return out

    change = {"alter": alter_rows, "half": half_rows}[fault]
    evaluate = EvalEngine.evaluate

    @functools.wraps(evaluate)
    def broken_evaluate(self, *a, **k):
        return change(evaluate(self, *a, **k))

    EvalEngine.evaluate = broken_evaluate
    fused = ga_device.run_ga_fused

    @functools.wraps(fused)
    def broken_fused(*a, **k):
        out = fused(*a, **k)
        out.pop_metrics = change(out.pop_metrics)
        return out

    ga_device.run_ga_fused = broken_fused


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seed", type=int, nargs="+", default=[2 ** 33 + 5])
    ap.add_argument("--seconds", type=float, default=0.5)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--control", default=None)
    ap.add_argument("--fault", choices=("alter", "half", "frozen"),
                    default=None)
    a = ap.parse_args(argv)
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    import jax
    import run
    from harness import spec
    cell = spec.load_cell(a.cell)
    if not a.full:
        cell = shrink(cell)
    if a.fault:
        plant(a.fault, cell.traffic["kind"])
    for seed in a.seed:
        args = run.parse(["--workload", a.cell, "--seed", str(seed),
                          "--seconds", str(a.seconds), "--trace", "0"]
                         + (["--control", a.control] if a.control else []))
        line = run.run(args, cell, jax.devices()[:1],
                       os.path.join(ROOT, ".bench_out", "tiny"))
        line["seed"] = seed
        print(json.dumps(run._finite(line)), flush=True)
        for k, c in line["checks"].items():
            print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
                  file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
