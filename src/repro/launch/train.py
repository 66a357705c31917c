"""Training launcher: any assigned architecture at its published widths
(--smoke: the reduced same-family variant, for CPU); the same code path
drives the production meshes on real fleets.

  PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b --steps 50 --smoke
"""
import argparse

from .. import configs as C
from ..configs.base import ShapeCell
from ..models.common import profile_names
from ..substrate import enable_compile_cache
from ..train import Trainer, TrainerConfig
from .mesh import make_test_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=C.ARCHS, default="minicpm-2b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--smoke", action="store_true",
                    help="build the reduced same-family config instead of "
                         "the published widths (CPU-sized)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest valid checkpoint before training")
    ap.add_argument("--profile", default="opt1", choices=profile_names(),
                    help="sharding profile, scoped to this trainer")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = C.get(args.arch, smoke=args.smoke)
    cell = ShapeCell("cli", seq_len=args.seq, global_batch=args.batch, kind="train")
    tcfg = TrainerConfig(steps=args.steps, ckpt_every=args.ckpt_every,
                         ckpt_dir=args.ckpt_dir, log_every=max(1, args.steps // 20),
                         profile=args.profile)
    tr = Trainer(cfg, cell, tcfg, make_test_mesh)
    for m in tr.run():
        print(m, flush=True)


if __name__ == "__main__":
    main()
