"""End-to-end training driver: ~100M-class model, a few hundred steps,
with checkpointing and a simulated failure/restart (fault tolerance).

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 200]
"""

import argparse
import os
import shutil
import tempfile

from repro.launch import train as train_launch
from repro.runtime import enable_compilation_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--arch", default="qwen3-0.6b")
    args = ap.parse_args()

    ckpt_dir = os.path.join(tempfile.gettempdir(), "repro_train_lm_ckpt")
    shutil.rmtree(ckpt_dir, ignore_errors=True)

    # ~100M-class config: reduce qwen3-0.6b by 2 (=> ~0.15B with the
    # trimmed vocab; adjust --reduce for bigger/smaller).
    half = max(50, args.steps // 2)
    common = [
        "--arch", args.arch, "--reduce", "4", "--batch", "8", "--seq", "256",
        "--ckpt-dir", ckpt_dir, "--ckpt-every", "25", "--data-mode", "markov",
    ]

    print(f"=== phase 1: train to step {half}, then 'crash' ===")
    train_launch.main(common + ["--steps", str(half)])

    print("=== phase 2: restart from the latest checkpoint (elastic) ===")
    losses = train_launch.main(common + ["--steps", str(args.steps), "--resume"])

    import numpy as np

    first, last = np.mean(losses[:10]), np.mean(losses[-10:])
    print(f"=== done: loss {first:.3f} -> {last:.3f} "
          f"({'LEARNED' if last < first else 'no improvement?'}) ===")


if __name__ == "__main__":
    enable_compilation_cache()
    main()
