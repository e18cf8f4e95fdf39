"""Training launcher: runs the DSE-resilient training loop on one device,
with optional failure injection. The default is the architecture's reduced
smoke config (CPU-sized); ``--full-config`` uses the published widths,
which at mamba2-370m fit one TPU v5e (``chip_smoke.py`` drives that path).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --steps 20 \
      --kill-at 10 --out /tmp/run
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--global-batch", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=16)
    ap.add_argument("--kill-at", type=int, default=None)
    ap.add_argument("--kill-data-at", type=int, default=None)
    ap.add_argument("--group-commit-ms", type=float, default=20.0)
    ap.add_argument("--delta-codec", action="store_true")
    ap.add_argument("--full-config", action="store_true",
                    help="use the exact published dims (TPU-scale; default "
                    "is the reduced smoke config for CPU)")
    ap.add_argument("--out", default="/tmp/repro_train")
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.train import run_resilient_training

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=not args.full_config)
    res = run_resilient_training(
        Path(args.out),
        cfg,
        steps=args.steps,
        global_batch=args.global_batch,
        seq_len=args.seq_len,
        kill_trainer_at=args.kill_at,
        kill_data_at=args.kill_data_at,
        group_commit_interval=args.group_commit_ms / 1e3,
        use_delta_codec=args.delta_codec,
    )
    print(json.dumps({
        "arch": cfg.name,
        "final_step": res.final_step,
        "params_digest": res.params_digest,
        "rollbacks": res.rollbacks,
        "checkpoint_bytes": res.checkpoint_bytes,
        "first_loss": res.external_metrics[0][1] if res.external_metrics else None,
        "last_loss": res.external_metrics[-1][1] if res.external_metrics else None,
    }, indent=2))


if __name__ == "__main__":
    main()
