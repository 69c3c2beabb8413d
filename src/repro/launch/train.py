"""Training driver.

Smoke mode (default, CPU): reduced config, real optimization on the
synthetic stream. Production mode (--mesh single|multi) builds the
sharded train step exactly as the dry-run does and executes it if the
host actually has the devices (on this CPU container use
launch.dryrun for the compile-only path).

  PYTHONPATH=src python -m repro.launch.train --arch glm4-9b --steps 50
"""

from __future__ import annotations

import argparse
import dataclasses

import jax

from ..configs import get_config
from ..models import RuntimeFlags, build_model
from ..training import AdamWConfig, DataConfig, train_loop
from .compile_cache import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama2-7b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--full-size", action="store_true",
                    help="use the full config (needs a real cluster)")
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch, smoke=not args.full_size)
    if not args.full_size:
        cfg = dataclasses.replace(cfg, dtype="float32")
    model = build_model(cfg, RuntimeFlags(remat=True))
    print(f"[train] {args.arch} ({cfg.family}) L={cfg.n_layers} d={cfg.d_model} "
          f"on {jax.default_backend()}")
    _, hist = train_loop(
        model,
        DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                   batch_size=args.batch),
        AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                    total_steps=args.steps),
        n_steps=args.steps,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
    )
    print(f"[train] done: loss {hist[0]['loss']:.4f} -> {hist[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
