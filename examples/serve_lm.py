"""Batched serving demo: continuous-batching engine over prefill/decode
steps with a KV cache.

  PYTHONPATH=src python examples/serve_lm.py [--arch starcoder2-15b]

Serving-mode DSE (--dse)
------------------------
Serving is a *throughput* deployment: successive inference batches
pipeline through the NPU, so the right hardware target is the
steady-state initiation interval (II) and the energy per inference at
that rate — not the one-batch latency the default DSE optimizes.  With
``--dse`` this example searches NPU designs for exactly that regime:

* an ``EvalEngine(..., mode="throughput")`` scores candidates on the
  pipelined steady state (the ``latency`` column is II seconds, the
  ``energy`` column per-inference pJ with leakage charged over II);
* ``objective.serving_fitness`` picks the lowest energy-per-inference
  design whose II meets ``--ii-target-us`` on every serving workload
  (designs that cannot sustain the request rate are infeasible);
* finalists are re-scored through the exact compile-free backend
  (``rescore(mode="throughput")``), so the reported II / energy are the
  ChipSim-parity numbers, not the in-scan search approximation.

  PYTHONPATH=src python examples/serve_lm.py --dse --ii-target-us 2e6

Engine knobs (see ROADMAP "backend x mode" matrix): ``backend`` selects
scan/batched/oracle, ``mode`` selects latency/throughput, and both
compose — every backend models both modes.
"""
import argparse

import numpy as np
from repro.compile_cache import enable_compile_cache


def run_serving_demo(args):
    import jax

    from repro.models import get_config, init_params
    from repro.serve.engine import Request, ServeEngine

    cfg = get_config(args.arch).reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    engine = ServeEngine(cfg, params, max_batch=4, max_len=128)

    rng = np.random.default_rng(0)
    for rid in range(args.requests):
        prompt = rng.integers(0, cfg.vocab, size=rng.integers(4, 12),
                              dtype=np.int32)
        engine.submit(Request(rid, prompt, max_new_tokens=12))
    print(f"serving {args.requests} requests on {cfg.name} "
          f"(max_batch=4, greedy) ...")
    results = engine.run()
    for rid, toks in sorted(results.items()):
        print(f"  req {rid}: generated {toks}")


def run_serving_dse(args):
    """Throughput-mode NPU search for the serving deployment (see module
    docstring): sweep candidates at an II target, exact-rescore the best."""
    from repro.core.dse.encoding import random_genomes
    from repro.core.dse.api import EngineConfig
    from repro.core.dse.engine import EvalEngine
    from repro.core.dse.objective import serving_fitness

    workloads = ["llama7b_int4", "vit_b16_int8"]
    ii_target_s = args.ii_target_us * 1e-6
    engine = EvalEngine(workloads,
                        config=EngineConfig(mode="throughput"))
    rng = np.random.default_rng(args.seed)
    genomes = random_genomes(rng, args.samples)
    m = engine.evaluate(genomes)
    score = serving_fitness(m["energy"], m["latency"], ii_target_s)
    print(f"serving-mode DSE: {args.samples} candidates on {workloads}, "
          f"II target {args.ii_target_us:.0f} us "
          f"(mode={m['meta']['mode']}, backend={m['meta']['backend']})")
    feasible = np.isfinite(score)
    if not feasible.any():
        print("  no design sustains the II target; relax --ii-target-us")
        return
    order = np.argsort(-score)
    top = order[np.isfinite(score[order])][:4]
    exact = engine.rescore(genomes[top], mode="throughput")
    print(f"  {feasible.sum()}/{args.samples} designs meet the target; "
          f"top finalists exact-rescored "
          f"(mapper={exact['meta']['mapper']}):")
    for r in range(len(top)):
        ii_us = exact["latency"][r] * 1e6
        e_uj = exact["energy"][r] * 1e-6
        print(f"  #{r} (candidate {top[r]}): "
              f"area {exact['area'][r]:7.1f} mm^2  "
              f"II {np.max(ii_us):8.1f} us  "
              f"energy/inf {np.sum(e_uj):8.1f} uJ")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-15b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--dse", action="store_true",
                    help="run the serving-mode (throughput) NPU design "
                         "search instead of the token-serving demo")
    ap.add_argument("--ii-target-us", type=float, default=2e6,
                    help="steady-state initiation-interval target per "
                         "workload (microseconds)")
    ap.add_argument("--samples", type=int, default=48,
                    help="candidate designs to sweep in --dse mode")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.dse:
        run_serving_dse(args)
    else:
        run_serving_demo(args)


if __name__ == "__main__":
    enable_compile_cache()
    main()
