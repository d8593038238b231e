"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --smoke \
      --steps 50 --batch 4 --seq 128 [--retries 2] [--ckpt-dir DIR]

Full (non-smoke) configs are meant for real accelerator fleets; on this
CPU host use --smoke. Fault tolerance: any crash restarts from the latest
atomic checkpoint (see repro.runtime.train_loop).
"""
from __future__ import annotations

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--retries", type=int, default=2)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--remat", default="none", choices=["none", "dots", "full"])
    ap.add_argument("--data-bin", default=None)
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    from repro.configs import get_config, get_smoke_config
    from repro.data import DataConfig
    from repro.optim import OptConfig
    from repro.runtime import TrainConfig, train_with_retries

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    dc = DataConfig(seq_len=args.seq, global_batch=args.batch,
                    bin_path=args.data_bin)
    tc = TrainConfig(steps=args.steps, ckpt_every=args.ckpt_every,
                     ckpt_dir=args.ckpt_dir, remat=args.remat)
    oc = OptConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                   total_steps=args.steps)
    out = train_with_retries(cfg, dc, tc, oc, retries=args.retries)
    print(f"[launch] done: final loss {out['final_loss']:.4f}")


if __name__ == "__main__":
    main()
