"""End-to-end training driver: trains a reduced assigned architecture for a
few hundred steps on CPU with async checkpointing, then demonstrates the
fault-tolerance path (simulated device failure -> restore -> bit-identical
continuation).

  PYTHONPATH=src python examples/train_lm.py [--arch granite-20b] [--steps 200]
"""
import argparse
import shutil
import tempfile

from repro.models import get_config
from repro.optim.adamw import AdamWConfig
from repro.train.fault import FaultInjector
from repro.train.loop import TrainLoopConfig, train_loop
from repro.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-20b")
    ap.add_argument("--steps", type=int, default=200)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    ckpt = tempfile.mkdtemp(prefix="repro_train_")
    loop = TrainLoopConfig(steps=args.steps, ckpt_every=25, global_batch=8,
                           seq_len=64, ckpt_dir=ckpt)

    print(f"training {cfg.name} ({cfg.param_count()/1e6:.1f}M params) "
          f"for {args.steps} steps with a fault injected at step "
          f"{args.steps // 2} ...")
    out = train_loop(cfg, loop, AdamWConfig(lr=3e-3),
                     fault_injector=FaultInjector(fail_at={args.steps // 2}),
                     on_step=lambda s, m: print(
                         f"  step {s:4d}  loss {m['loss']:.4f}")
                     if s % 25 == 0 else None)
    print(f"\nfirst loss {out['losses'][0]:.4f} -> final "
          f"{out['final_loss']:.4f}  (restarts: {out['restarts']})")
    shutil.rmtree(ckpt, ignore_errors=True)


if __name__ == "__main__":
    enable_compile_cache()
    main()
