"""Training CLI: ``python -m repro.launch.train --arch <id> [...]``.

On real hardware this launches the pjit-sharded loop on the production
mesh; on CPU it runs the reduced config end-to-end (smoke-scale) with the
same code path — checkpointing, straggler monitor, resumption.
"""
import argparse

import jax

from ..config import TrainConfig
from ..paths import enable_compile_cache
from ..registry import get_config
from ..train import train


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--full-config", action="store_true",
                    help="use the full (pod-scale) config instead of the "
                         "reduced CPU config")
    args = ap.parse_args()

    cfg = get_config(args.arch, reduced=not args.full_config)
    print(f"training {cfg.name}: {cfg.num_layers}L d={cfg.d_model} "
          f"on {jax.device_count()} device(s)")
    tcfg = TrainConfig(total_steps=args.steps, lr=args.lr,
                       warmup_steps=max(args.steps // 10, 1),
                       checkpoint_every=max(args.steps // 4, 1),
                       loss_chunk=0)
    res = train(cfg, tcfg, checkpoint_dir=args.ckpt, log_every=10,
                batch_shape=(args.batch, args.seq))
    print(f"done; final loss {res.history[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
