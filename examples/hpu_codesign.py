"""Beyond-paper loop closure: hardware/software co-design for the assigned
architectures.

1. Extract a JAX model (the same code that trains under pjit) into a
   MOSAIC operator DAG.
2. Search heterogeneous NPU compositions for it (the paper's DSE).
3. Search TPU mesh/sharding knobs for its training run with the same
   roofline methodology (repro.core.tpu_dse).

  PYTHONPATH=src python examples/hpu_codesign.py [--arch mamba2-780m]
"""
import argparse
import warnings

import numpy as np

from repro.core import compile_workload, hetero_bls, homogeneous_baseline, simulate
from repro.core.tpu_dse import search_mesh
from repro.core.workloads.extract import extract_model
from repro.models import get_config
from repro.compile_cache import enable_compile_cache


def main():
    warnings.filterwarnings("ignore")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-780m")
    args = ap.parse_args()
    cfg = get_config(args.arch)

    print(f"[1] extracting {cfg.name} into a MOSAIC DAG ...")
    g = extract_model(cfg, seq_len=256)
    print(f"    {len(g.nodes)} ops, {g.total_macs/1e9:.1f} GMACs, "
          f"AI={g.arithmetic_intensity():.1f}")

    print("[2] NPU composition comparison (single-batch inference):")
    for chip in (homogeneous_baseline(6), hetero_bls()):
        r = simulate(chip, compile_workload(g, chip))
        print(f"    {chip.name:22s} lat={r.latency_s*1e3:9.2f}ms "
              f"E={r.energy_pj*1e-6:9.1f}uJ TOPS/W={r.tops_per_w:.2f}")

    print("[3] TPU mesh DSE for training (256 chips, batch 256 x 4096):")
    ranked = search_mesh(cfg, chips=256, global_batch=256, seq_len=4096)
    for c in ranked[:5]:
        k = c.knobs
        print(f"    dp={k.dp:3d} tp={k.tp:2d} mb={k.microbatches} "
              f"remat={int(k.remat)}  step={c.step_s*1e3:7.1f}ms "
              f"(cmp {c.compute_s*1e3:.1f} / mem {c.memory_s*1e3:.1f} / "
              f"coll {c.collective_s*1e3:.1f})  hbm={c.hbm_gib:.1f}GiB "
              f"{'fits' if c.fits else 'OOM'}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
