"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch granite_3_8b \
        --smoke --steps 50 --ckpt-dir /tmp/ckpt

On a real TPU fleet each host runs this same entry point (jax.distributed
initializes from the TPU environment); ``--smoke`` selects the reduced
config that runs on a CPU.  Demonstrates the full substrate: sharded params,
W-DBB schedule, DAP training, checkpoint/restart, straggler monitor.
"""

from __future__ import annotations

import argparse
import dataclasses

import jax

from repro import configs
from repro.core import dbb
from repro.core.schedule import WDBBSchedule
from repro.data.pipeline import MarkovLM, Prefetcher
from repro.train.optimizer import OptimizerConfig
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite_3_8b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (default: published widths)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--sparsity", default=None, help="dense|wdbb|awdbb")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--wdbb-end", type=int, default=None,
                    help="enable progressive W-DBB pruning ending this step")
    args = ap.parse_args()

    cfg = configs.get_config(args.arch, smoke=args.smoke,
                             sparsity_mode=args.sparsity)
    cfg = dataclasses.replace(cfg, vocab=min(cfg.vocab, 2048))
    print(f"arch={cfg.name} family={cfg.family} sparsity={cfg.sparsity.mode} "
          f"params~{cfg.param_count()/1e6:.1f}M devices={len(jax.devices())}")

    data = Prefetcher(MarkovLM(cfg.vocab, args.batch, args.seq, seed=0))
    wdbb = None
    if args.wdbb_end:
        wdbb = WDBBSchedule(target=dbb.DBBConfig(cfg.sparsity.w_nnz, cfg.sparsity.bz),
                            begin_step=0, end_step=args.wdbb_end, update_every=10)
    trainer = Trainer(
        cfg,
        OptimizerConfig(lr=args.lr, warmup_steps=max(10, args.steps // 10),
                        total_steps=args.steps),
        TrainerConfig(total_steps=args.steps, log_every=10,
                      ckpt_every=args.ckpt_every, ckpt_dir=args.ckpt_dir,
                      wdbb=wdbb),
        data,
    )
    hist = trainer.run(args.steps)
    print(f"final loss {hist[-1]['loss']:.4f} acc {hist[-1]['acc']:.3f}")


if __name__ == "__main__":
    main()
