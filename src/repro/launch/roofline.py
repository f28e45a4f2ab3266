"""Roofline analysis (deliverable g): derive the three roofline terms per
(arch x shape x mesh) cell from the dry-run's compiled artifacts.

  compute term    = HLO_FLOPs / (chips x peak_FLOP/s)
  memory term     = HLO_bytes / (chips x HBM_bw)
  collective term = collective_bytes / (chips x link_bw)

cost_analysis() reports the per-device SPMD program, so per-device values
divide by per-chip rates directly (equivalently: global = per-device x
chips).  Hardware: TPU v5e-class — 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI.

  PYTHONPATH=src python -m repro.launch.roofline [--dir results/dryrun]
"""
from __future__ import annotations

import argparse
import glob
import json
import os
from typing import Dict, List, Optional

from repro.models import get_config
from repro.launch.shapes import SHAPES
from repro.compile_cache import enable_compile_cache

PEAK_FLOPS = 197e12      # bf16 / chip
HBM_BW = 819e9           # B/s / chip
LINK_BW = 50e9           # B/s / link (ICI)

__all__ = ["analyze", "load_cells", "model_flops"]


def model_flops(arch: str, shape: str) -> float:
    """MODEL_FLOPS = 6*N*D (dense) or 6*N_active*D (MoE) for training;
    2*N*D (fwd only) for prefill; 2*N_active per token for decode."""
    cfg = get_config(arch)
    case = SHAPES[shape]
    n_total = cfg.param_count()
    if cfg.n_experts:
        # active params: replace full expert banks by top_k (+shared)
        f = cfg.moe_d_ff or cfg.d_ff
        moe_layers = sum(cfg.is_moe_layer(i) for i in range(cfg.n_layers))
        inactive = moe_layers * (cfg.n_experts - cfg.top_k) * 3 * cfg.d_model * f
        n_active = n_total - inactive
    else:
        n_active = n_total
    tokens = case.global_batch * (1 if case.mode == "decode" else case.seq_len)
    mult = 6.0 if case.mode == "train" else 2.0
    return mult * n_active * tokens


def load_cells(dirname: str) -> List[dict]:
    cells = []
    for path in sorted(glob.glob(os.path.join(dirname, "*.json"))):
        cells.append(json.load(open(path)))
    return cells


def analyze(rec: dict) -> Optional[dict]:
    if "skipped" in rec or "error" in rec:
        return None
    chips = rec["n_devices"]
    fl = rec["flops_per_device"]
    by = rec["bytes_per_device"]
    coll = sum(v["bytes"] for v in rec.get("collectives", {}).values())
    t_c = fl / PEAK_FLOPS
    t_m = by / HBM_BW
    t_l = coll / LINK_BW
    dominant = max(("compute", t_c), ("memory", t_m), ("collective", t_l),
                   key=lambda kv: kv[1])[0]
    mf = model_flops(rec["arch"], rec["shape"])
    hlo_global = fl * chips
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "compute_s": t_c, "memory_s": t_m, "collective_s": t_l,
        "dominant": dominant,
        "model_flops": mf,
        "hlo_flops_global": hlo_global,
        "useful_ratio": mf / hlo_global if hlo_global else 0.0,
        "bound_s": max(t_c, t_m, t_l),
        # roofline fraction: how much of the bound is useful compute
        "roofline_frac": (mf / chips / PEAK_FLOPS) / max(t_c, t_m, t_l)
        if max(t_c, t_m, t_l) > 0 else 0.0,
        "peak_gib": rec["memory"]["peak_bytes"] / 2**30,
        "collectives": rec.get("collectives", {}),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="results/dryrun")
    ap.add_argument("--mesh", default="16x16")
    ap.add_argument("--md", action="store_true", help="markdown table")
    args = ap.parse_args()

    rows = []
    for rec in load_cells(args.dir):
        if rec.get("mesh") != args.mesh:
            continue
        if "skipped" in rec:
            rows.append({"arch": rec["arch"], "shape": rec["shape"],
                         "skipped": rec["skipped"]})
            continue
        if "error" in rec:
            rows.append({"arch": rec["arch"], "shape": rec["shape"],
                         "error": rec["error"][:80]})
            continue
        rows.append(analyze(rec))

    hdr = (f"{'arch':28s} {'shape':12s} {'cmp_ms':>8s} {'mem_ms':>8s} "
           f"{'coll_ms':>8s} {'dom':>10s} {'useful':>7s} {'roofl%':>7s} {'GiB':>6s}")
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        if r is None:
            continue
        if "skipped" in r:
            print(f"{r['arch']:28s} {r['shape']:12s} -- skipped: full attention")
            continue
        if "error" in r:
            print(f"{r['arch']:28s} {r['shape']:12s} !! {r['error']}")
            continue
        print(f"{r['arch']:28s} {r['shape']:12s} {r['compute_s']*1e3:8.2f} "
              f"{r['memory_s']*1e3:8.2f} {r['collective_s']*1e3:8.2f} "
              f"{r['dominant']:>10s} {r['useful_ratio']:7.3f} "
              f"{r['roofline_frac']*100:6.1f}% {r['peak_gib']:6.2f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
