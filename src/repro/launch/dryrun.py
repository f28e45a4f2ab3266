"""Multi-pod dry-run (deliverable e).

Lowers + compiles every (architecture x input-shape x mesh) cell on the
production meshes — (16,16) single-pod and (2,16,16) multi-pod — with
ShapeDtypeStruct inputs (no allocation), and records memory_analysis,
cost_analysis, and the HLO collective schedule for the roofline table.

  PYTHONPATH=src python -m repro.launch.dryrun --all
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-34b --shape train_4k
"""
# The VERY FIRST lines, before ANY other import: jax locks the device
# count on first init.
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import functools
import gc
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed.sharding import (AXIS_MAP_MULTI, AXIS_MAP_SINGLE,
                                        resolve_specs, set_axis_map)
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import (SHAPES, cache_specs_physical, cache_structs,
                                 input_specs, runnable, skip_reason)
from repro.models import get_config, init_params, list_archs, param_specs
from repro.models.model import set_activation_spec, set_scan_unroll
from repro.optim.adamw import AdamWConfig
from repro.serve.step import make_decode_step, make_prefill_step
from repro.train.step import init_train_state, make_train_step
from repro.compile_cache import enable_compile_cache

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

# optimized HLO prints untyped operands; parse the RESULT type of each
# collective and derive operand bytes from the op kind + replica-group size
_SHAPE_RE = re.compile(
    r"\b(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64|f64|c64|c128)"
    r"\[([0-9,]*)\]")


def _shape_bytes(dt: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def _group_size(spec: str) -> int:
    if not spec:
        return 1
    if spec.startswith("[{"):
        spec = spec[1:]
    if spec.startswith("{{"):
        first = spec[2:].split("}")[0]
        return first.count(",") + 1
    m = re.match(r"\[(\d+),(\d+)\]", spec)
    return int(m.group(2)) if m else 1


def collective_stats(hlo: str):
    """Per-device operand bytes of every collective in the HLO module.

    Result->operand conversion: all-gather R/g, all-reduce R,
    reduce-scatter R*g, all-to-all R, collective-permute R."""
    stats = {}
    for line in hlo.splitlines():
        m = re.search(
            r"=\s*(.*?)\s(all-gather|all-reduce|reduce-scatter|all-to-all|"
            r"collective-permute)(?:-start)?\(", line)
        if not m:
            continue
        restype, kind = m.group(1), m.group(2)
        rbytes = sum(_shape_bytes(dt, dims)
                     for dt, dims in _SHAPE_RE.findall(restype))
        gm = re.search(r"replica_groups=(\{\{[^}]*\}|\[\d+,\d+\])", line)
        g = _group_size(gm.group(1)) if gm else 1
        if kind == "all-gather":
            nbytes = rbytes / max(g, 1)
        elif kind == "reduce-scatter":
            nbytes = rbytes * g
        else:
            nbytes = rbytes
        e = stats.setdefault(kind, {"count": 0, "bytes": 0})
        e["count"] += 1
        e["bytes"] += int(nbytes)
    return stats


def _moments_dtype(cfg) -> str:
    # int8 moments above 100B params (DESIGN.md §6 / optim.adamw)
    return "int8" if cfg.param_count() > 100e9 else "fp32"


def _opt_specs(pspecs, moments_dtype: str):
    def one(s):
        scale_spec = P(*(tuple(s)[:-1] + (None,))) if len(tuple(s)) else P()
        if moments_dtype == "int8":
            q = {"q": s, "scale": scale_spec}
            return {"m": q, "v": q}
        return {"m": s, "v": s}

    return jax.tree.map(one, pspecs, is_leaf=lambda x: isinstance(x, P))


def build_lowerable(arch: str, shape: str, multi_pod: bool):
    """Returns (jitted_fn, example_args_structs) for the cell."""
    cfg = get_config(arch)
    case = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    axis_map = AXIS_MAP_MULTI if multi_pod else AXIS_MAP_SINGLE
    pspecs = resolve_specs(param_specs(cfg), axis_map)
    b_axes = ("pod", "data") if multi_pod else ("data",)
    n_dp = 32 if multi_pod else 16
    # residual-stream constraint between periods (tuning.act_mode):
    # "seq" = sequence parallelism (baseline), "dmodel", "batch"
    from repro.launch.tuning import KNOBS
    act_batch = b_axes if case.global_batch % n_dp == 0 else None
    act_spec = {"seq": P(act_batch, "model", None),
                "dmodel": P(act_batch, None, "model"),
                "batch": P(act_batch, None, None)}[KNOBS.act_mode]
    set_activation_spec(NamedSharding(mesh, act_spec))
    set_axis_map({"b": act_batch, "m": "model", "d": "data"})

    structs, in_pspecs = input_specs(cfg, shape, multi_pod=multi_pod)
    params_struct = jax.eval_shape(
        functools.partial(init_params, cfg),
        jax.ShapeDtypeStruct((2,), jnp.uint32))

    def shard(tree, specs):
        return jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))

    if case.mode == "train":
        opt_cfg = AdamWConfig(moments_dtype=_moments_dtype(cfg))
        state_struct = jax.eval_shape(
            lambda ps: init_train_state(cfg, ps, opt_cfg), params_struct)
        state_specs = {"params": pspecs,
                       "opt": {"mu": _opt_specs(pspecs, opt_cfg.moments_dtype),
                               "step": P()},
                       "step": P()}
        step = make_train_step(cfg, opt_cfg, microbatches=KNOBS.microbatches)
        fn = jax.jit(step,
                     in_shardings=(shard(None, state_specs), shard(None, in_pspecs)),
                     out_shardings=(shard(None, state_specs), None),
                     donate_argnums=(0,))
        args = (state_struct, structs)
    elif case.mode == "prefill":
        pf = make_prefill_step(cfg, case.seq_len)
        c_specs = cache_specs_physical(cfg, case.global_batch,
                                       multi_pod=multi_pod)
        ctx_keys = [k for k in structs if k != "tokens"]
        tok_sh = NamedSharding(mesh, in_pspecs["tokens"])
        ctx_sh = (NamedSharding(mesh, in_pspecs[ctx_keys[0]]),) if ctx_keys else ()
        fn = jax.jit(pf,
                     in_shardings=(shard(None, pspecs), tok_sh) + ctx_sh,
                     out_shardings=(None, shard(None, c_specs)))
        args = (params_struct, structs["tokens"]) + tuple(
            structs[k] for k in ctx_keys)
    else:  # decode
        dec = make_decode_step(cfg)
        c_struct = cache_structs(cfg, case.global_batch, case.seq_len)
        c_specs = cache_specs_physical(cfg, case.global_batch,
                                       multi_pod=multi_pod)
        ctx_keys = [k for k in structs if k not in ("tokens", "pos")]
        shardings = [shard(None, pspecs),
                     NamedSharding(mesh, in_pspecs["tokens"]),
                     NamedSharding(mesh, in_pspecs["pos"]),
                     shard(None, c_specs)]
        args = [params_struct, structs["tokens"], structs["pos"], c_struct]
        if ctx_keys:
            shardings.append(NamedSharding(mesh, in_pspecs[ctx_keys[0]]))
            args.append(structs[ctx_keys[0]])
        fn = jax.jit(dec, in_shardings=tuple(shardings),
                     out_shardings=(None, shard(None, c_specs)),
                     donate_argnums=(3,))
        args = tuple(args)
    return fn, args, mesh


def run_cell(arch: str, shape: str, multi_pod: bool) -> dict:
    cfg = get_config(arch)
    rec = {"arch": arch, "shape": shape,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "n_devices": 512 if multi_pod else 256}
    reason = skip_reason(cfg, shape)
    if reason:
        rec["skipped"] = reason
        return rec
    t0 = time.time()
    try:
        fn, args, mesh = build_lowerable(arch, shape, multi_pod)
        with mesh:
            lowered = fn.lower(*args)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
            ma = compiled.memory_analysis()
            ca = compiled.cost_analysis() or {}
            hlo = compiled.as_text()
        rec.update({
            "lower_s": round(t1 - t0, 2),
            "compile_s": round(t2 - t1, 2),
            "flops_per_device": ca.get("flops", 0.0),
            "bytes_per_device": ca.get("bytes accessed", 0.0),
            "memory": {
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "peak_bytes": ma.argument_size_in_bytes
                + ma.output_size_in_bytes + ma.temp_size_in_bytes
                - ma.alias_size_in_bytes,
            },
            "collectives": collective_stats(hlo),
            "hlo_chars": len(hlo),
        })
        del fn, lowered, compiled, hlo
        gc.collect()

        # ---- cost pass: XLA's cost analysis counts while-loop bodies once,
        # so the scanned block stack under-reports by n_repeats.  Cost-mode
        # lowering unrolls the small scans fully and the blocks scan by u;
        # cost is affine in u, so two lowerings (u=1, u=2) extrapolate the
        # true totals exactly: total = f1 + (R-1) * (f2 - f1). --------------
        try:
            R = cfg.n_repeats

            def cost_lower(u: int):
                set_scan_unroll(True, blocks_unroll=u)
                fnc, argsc, meshc = build_lowerable(arch, shape, multi_pod)
                with meshc:
                    comp = fnc.lower(*argsc).compile()
                    cac = comp.cost_analysis() or {}
                    stats = collective_stats(comp.as_text())
                out = {"flops": cac.get("flops", 0.0),
                       "bytes": cac.get("bytes accessed", 0.0),
                       "coll": stats}
                del fnc, comp
                gc.collect()
                return out

            c1 = cost_lower(1)
            if R > 1:
                c2 = cost_lower(2)

                def extrap(a, b):
                    return a + (R - 1) * (b - a)

                rec["flops_per_device"] = extrap(c1["flops"], c2["flops"])
                rec["bytes_per_device"] = extrap(c1["bytes"], c2["bytes"])
                coll = {}
                kinds = set(c1["coll"]) | set(c2["coll"])
                for k in kinds:
                    b1 = c1["coll"].get(k, {"count": 0, "bytes": 0})
                    b2 = c2["coll"].get(k, {"count": 0, "bytes": 0})
                    coll[k] = {"count": int(extrap(b1["count"], b2["count"])),
                               "bytes": int(extrap(b1["bytes"], b2["bytes"]))}
                rec["collectives"] = coll
            else:
                rec["flops_per_device"] = c1["flops"]
                rec["bytes_per_device"] = c1["bytes"]
                rec["collectives"] = c1["coll"]
            rec["cost_unrolled"] = True
        except Exception as e:
            rec["cost_unrolled"] = False
            rec["cost_pass_error"] = f"{type(e).__name__}: {e}"[:200]
        finally:
            set_scan_unroll(False)
    except Exception as e:  # record the failure — these are bugs to fix
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    finally:
        set_activation_spec(None)
        set_axis_map(None)
        gc.collect()
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    ok = skip = fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{'multi' if mp else 'single'}_{arch}_{shape}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    rec = json.load(open(path))
                    if "error" not in rec:
                        print(f"[cached] {tag}")
                        ok += 0 if "skipped" in rec else 1
                        skip += 1 if "skipped" in rec else 0
                        continue
                rec = run_cell(arch, shape, mp)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                if "skipped" in rec:
                    skip += 1
                    print(f"[skip]   {tag}: {rec['skipped'][:60]}")
                elif "error" in rec:
                    fail += 1
                    print(f"[FAIL]   {tag}: {rec['error'][:200]}")
                else:
                    ok += 1
                    peak_gb = rec["memory"]["peak_bytes"] / 2**30
                    print(f"[ok]     {tag}: compile={rec['compile_s']}s "
                          f"peak={peak_gb:.2f}GiB/dev "
                          f"flops/dev={rec['flops_per_device']:.3g}")
    print(f"\ndry-run: {ok} ok, {skip} skipped, {fail} FAILED")
    raise SystemExit(1 if fail else 0)


if __name__ == "__main__":
    enable_compile_cache()
    main()
