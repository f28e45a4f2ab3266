#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: set up (build, make inputs from ``--seed``, warm every
program shape from the persistent compile cache), measure for
``--seconds``, then check what the timed path produced against the
frozen numpy oracle, and print one JSON line as the last line of
standard output.  ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` traces the window and reports its per-layer metrics.
Without a TPU, or with fewer chips than the cell asks for, it exits 3
and prints no result.

``--control float32`` runs the program with 64-bit floats switched off
after import: the precision control, which has to come out not correct.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

from harness import compare, spec  # noqa: E402


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("float32",), default=None)
    ap.add_argument("--dump", default=None,
                    help="also write a sample of the trace's events and the "
                    "compared arrays into this directory (how the tests' "
                    "recorded trace was made)")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    return args


def devices_or_exit(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        _log(f"bench: needs {chips} TPU chip(s); JAX found {len(devs)} "
             f"{devs[0].platform} device(s)")
        sys.exit(3)
    return devs[:chips]


def timed_window(driver, seconds: float):
    """``driver.window(seconds)``, and what the program's own spans and
    counters (``repro.core.telemetry``) recorded inside it: the delta of
    the registry across the window, so nothing of set-up."""
    from repro.core import telemetry
    before = telemetry.snapshot()
    win = driver.window(seconds)
    return win, telemetry.diff(telemetry.snapshot(), before)


def run(args, cell, devices, out_dir: str) -> dict:
    """Set up, measure, check; returns the result line as a dict."""
    import jax
    from harness.clock import CompileClock
    from harness.driver import make_driver
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    # the benchmark's cache is its own: no size bound, so no eviction
    # (JAX's eviction reads an access-time file for every entry)
    jax.config.update("jax_compilation_cache_max_size", -1)
    clock = CompileClock()
    # the program's modules switch 64-bit floats on as they are imported
    import repro.core.compiler.batched_mapper  # noqa: F401
    import repro.core.dse.pipeline  # noqa: F401
    import repro.core.simulator.batched  # noqa: F401
    if args.control == "float32":
        jax.config.update("jax_enable_x64", False)
    driver = make_driver(cell.config, cell.traffic, args.seed)
    try:
        driver.setup()
        setup_s = time.perf_counter() - T0
        setup_compile_s, compiles0 = clock.read()
        _log(f"[setup] {setup_s:.3f} s, of which compile "
             f"{setup_compile_s:.3f} s")
        trace = None
        if args.trace:
            seconds = min(args.seconds, float(cell.traffic["trace_seconds"]))
            log_dir = os.path.join(out_dir, "trace")
            shutil.rmtree(log_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation("bench:window"):
                    win, program = timed_window(driver, seconds)
            finally:
                jax.profiler.stop_trace()
        else:
            win, program = timed_window(driver, args.seconds)
        window_compiles = clock.read()[1] - compiles0
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices)
        if args.trace:
            from harness import trace as tr
            t0 = time.perf_counter()
            raw = tr.load_xplane(log_dir)
            trace = tr.reduce(raw) if raw is not None else None
            if args.dump and raw is not None:
                os.makedirs(args.dump, exist_ok=True)
                with open(os.path.join(args.dump, f"{cell.name}.{args.seed}"
                                       ".trace.json"), "w") as f:
                    json.dump({"reduced": trace,
                               "raw": tr.head(raw, 5000).to_json()}, f)
            shutil.rmtree(log_dir, ignore_errors=True)
            _log(f"[trace] read in {time.perf_counter() - t0:.3f} s: "
                 + json.dumps({k: v for k, v in (trace or {}).items()
                               if k != "breakdown"}))
        sample = driver.sample()
    finally:
        driver.close()

    t0 = time.perf_counter()
    ref_rows, ref_area = compare.reference(sample)
    values = dict(compare.numbers(sample, ref_rows, ref_area), **win.checked)
    checks = compare.judge(values, cell.checks)
    _log(f"[reference] {len(sample.genomes)} genomes x "
         f"{len(sample.workloads)} workloads in "
         f"{time.perf_counter() - t0:.3f} s; largest pair error "
         f"{float(compare.pair_errors(sample.rows, ref_rows).max()):.6g}; "
         f"pairs beyond "
         f"{compare.PAIR_TOL:g} (workload, row, error, metrics l/e/t): "
         f"{compare.off_pairs(sample, ref_rows)}")
    if args.dump:
        import numpy as np
        os.makedirs(args.dump, exist_ok=True)
        np.savez_compressed(
            os.path.join(args.dump, f"{cell.name}.{args.seed}.check.npz"),
            genomes=sample.genomes, rows=sample.rows, ref=ref_rows,
            area=sample.area if sample.area is not None else [],
            ref_area=ref_area, workloads=sample.workloads)

    metrics = {}
    if args.trace:
        ctx = {"trace": trace, "counters": win.counters,
               "window_compiles": window_compiles, "program": program}
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        got = dict(win.end_to_end, setup_s=setup_s)
        for m in cell.end_to_end:
            if m["name"] in got:
                metrics[m["name"]] = {"value": got[m["name"]],
                                      "unit": m["unit"]}
    wanted = cell.per_layer if args.trace else cell.end_to_end
    missing = [m["name"] for m in wanted if m["name"] not in metrics]
    if missing:
        _log(f"[metrics] nothing to read for {missing}")
    correct = (win.failed == 0 and (args.trace or not missing)
               and all(c["ok"] for c in checks.values()))
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": peak}
    line = {"correct": correct, "attempted": win.attempted,
            "failed": win.failed, "metrics": metrics, "device": dev}
    if args.trace and trace is not None:
        dev["busy_s"] = trace["busy_s"]
        dev["window_s"] = trace["window_s"]
        line["breakdown"] = trace["breakdown"]
    line["setup_compile_s"] = setup_compile_s
    line["window_compiles"] = window_compiles
    line["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                      for k, c in checks.items()}
    return line


def _finite(x):
    """JSON has no inf or nan: such a number prints as +-1e308."""
    if isinstance(x, float) and not -1e308 <= x <= 1e308:
        return -1e308 if x < 0 else 1e308
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [_finite(v) for v in x]
    return x


def main(argv=None) -> int:
    args = parse(argv)
    cell = spec.load_cell(args.workload)
    out_dir = os.path.join(spec.ROOT, ".bench_out", args.workload)
    os.makedirs(out_dir, exist_ok=True)
    # the compile cache lives at a fixed path inside the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(spec.ROOT,
                                                           ".jax_cache")
    src = os.path.join(spec.ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        _log(f"bench: no program under {src}")
        return 2
    if src not in sys.path:
        sys.path.insert(0, src)
    devices = devices_or_exit(cell.chips)
    line = run(args, cell, devices, out_dir)
    sys.stdout.flush()
    print(json.dumps(_finite(line)), flush=True)
    # the numbers compared, beside their limits, last on standard error
    for k, c in line["checks"].items():
        _log(f"check {k}: {c['value']!r} (limit {c['limit']!r})"
             + ("" if c["value"] <= c["limit"] else " FAILED"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
