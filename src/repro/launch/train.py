"""Training driver: ``python -m repro.launch.train --arch <id> [--smoke]``.

--smoke trains the reduced config for a few hundred steps on CPU (the
end-to-end example); full configs are exercised via the dry-run.
"""
from __future__ import annotations

import argparse
import json
import time

from repro.models import get_config
from repro.optim.adamw import AdamWConfig
from repro.train.loop import TrainLoopConfig, train_loop
from repro.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-20b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--lr", type=float, default=1e-3)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    loop = TrainLoopConfig(steps=args.steps, global_batch=args.batch,
                           seq_len=args.seq, ckpt_dir=args.ckpt_dir)
    t0 = time.time()
    out = train_loop(cfg, loop, AdamWConfig(lr=args.lr))
    dt = time.time() - t0
    print(json.dumps({
        "arch": cfg.name, "steps": out["steps_run"],
        "first_loss": out["losses"][0], "final_loss": out["final_loss"],
        "restarts": out["restarts"], "wall_s": round(dt, 1),
        "steps_per_s": round(out["steps_run"] / dt, 2),
    }, indent=1))


if __name__ == "__main__":
    enable_compile_cache()
    main()
