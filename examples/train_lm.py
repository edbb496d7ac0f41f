"""End-to-end LM training example: a few hundred steps of the mamba2-130m
family (reduced width on CPU; pass --full on a real cluster for the exact
130M config), with checkpoint/restart demonstrated mid-run.

  PYTHONPATH=src python examples/train_lm.py [--steps 200]
"""
import argparse
import shutil
import tempfile

from repro.compile_cache import use_compile_cache
from repro.launch import train


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-130m")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--full", action="store_true",
                    help="exact published config (cluster-scale)")
    args = ap.parse_args()

    ckpt_dir = tempfile.mkdtemp(prefix="repro_ck_")
    common = ["--arch", args.arch, "--batch", "16", "--seq", "128",
              "--micro", "2", "--ckpt-dir", ckpt_dir,
              "--ckpt-every", "50", "--log-every", "20"]
    if not args.full:
        common.append("--smoke")

    half = max(args.steps // 2, 1)
    print(f"=== phase 1: train to step {half}, checkpointing ===")
    train.main(common + ["--steps", str(half)])

    print(f"=== phase 2: restart from checkpoint -> step {args.steps} ===")
    train.main(common + ["--steps", str(args.steps), "--resume"])

    shutil.rmtree(ckpt_dir, ignore_errors=True)
    print("=== done: loss continued falling across the restart ===")


if __name__ == "__main__":
    main()
