"""Quickstart: MOSAIC in a dozen lines.

Builds a workload, compiles it onto a homogeneous NPU and a
Big+Little+Special-Function HPU, and prints the PEA triple (paper §4.2).

  PYTHONPATH=src python examples/quickstart.py [workload]
"""
import sys

from repro.core import (compile_workload, hetero_bls, homogeneous_baseline,
                        simulate)
from repro.core.workloads import build, workload_names
from repro.compile_cache import enable_compile_cache


def main():
    name = sys.argv[1] if len(sys.argv) > 1 else "resnet50_int8"
    g = build(name)
    print(f"workload: {name}  ops={len(g.nodes)}  "
          f"macs={g.total_macs/1e9:.2f}G  AI={g.arithmetic_intensity():.1f}")
    for chip in (homogeneous_baseline(6), hetero_bls(n_big=2, n_little=3,
                                                     n_special=1)):
        plan = compile_workload(g, chip)
        r = simulate(chip, plan)
        print(f"\n{chip.name}")
        print(f"  latency : {r.latency_s*1e3:9.3f} ms")
        print(f"  energy  : {r.energy_pj*1e-6:9.3f} uJ")
        print(f"  area    : {r.area_mm2:9.2f} mm^2")
        print(f"  TOPS/W  : {r.tops_per_w:9.3f}   power {r.avg_power_w:.2f} W")
        print(f"  util    : "
              + " ".join(f"{b.template}:{b.utilization(r.latency_s):.2f}"
                         for b in r.tiles))


if __name__ == "__main__":
    enable_compile_cache()
    main()
