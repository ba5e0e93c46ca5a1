"""Training launcher CLI.

  PYTHONPATH=src python -m repro.launch.train --arch falcon3-1b --smoke \
      --steps 100 --batch 8 --seq 64 [--lora-only] [--opt-8bit] \
      [--ckpt-dir DIR]

Full (non-smoke) configs expect accelerator hardware; the smoke variants
run on CPU. Checkpoint/resume, straggler monitoring and 8-bit optimizer
states are wired through repro.training.loop.
"""

from __future__ import annotations

import argparse

from repro.configs import get_config, get_smoke_config
from repro.launch.compile_cache import use_compile_cache
from repro.training import loop as train_loop
from repro.training.optimizer import AdamWConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--micro", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--lora-only", action="store_true")
    ap.add_argument("--opt-8bit", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    opt = AdamWConfig(
        lr=args.lr,
        total_steps=args.steps,
        warmup_steps=max(args.steps // 20, 1),
        quantized_state=args.opt_8bit,
    )
    r = train_loop.train(
        cfg,
        steps=args.steps,
        global_batch=args.batch,
        seq_len=args.seq,
        opt_cfg=opt,
        n_micro=args.micro,
        lora_only=args.lora_only,
        ckpt_dir=args.ckpt_dir,
    )
    print(f"done: {r['step']} steps, final loss {r['losses'][-1]:.4f}")


if __name__ == "__main__":
    main()
