"""Train an LM from the assigned-architecture pool with the full substrate:
synthetic data pipeline, AdamW, async checkpointing, failure-injection
restart.  On CPU the default is a reduced config; on real hardware drop
--smoke to train the full architecture (mamba2-130m is the ~130M-param
pool member the task's "train ~100M model" clause points at).

    PYTHONPATH=src python examples/train_lm.py                 # CPU quick
    PYTHONPATH=src python examples/train_lm.py --full --steps 300
"""
import argparse
import sys

from repro.launch.train import main as train_main
from repro.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-130m")
    ap.add_argument("--full", action="store_true",
                    help="train the full config (needs accelerator)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--simulate-failure", type=int, default=90,
                    help="inject a node failure at this step (0 = off)")
    args = ap.parse_args()

    argv = [
        "--arch", args.arch,
        "--steps", str(args.steps),
        "--batch", str(args.batch),
        "--seq", str(args.seq),
        "--ckpt-dir", "/tmp/repro_train_lm",
        "--ckpt-every", "50",
        "--log-every", "10",
    ]
    if not args.full:
        argv.append("--smoke")
    if args.simulate_failure:
        argv += ["--simulate-failure", str(args.simulate_failure)]
    out = train_main(argv)
    improved = out["last_loss"] < out["first_loss"]
    print(f"loss improved: {improved} "
          f"({out['first_loss']:.3f} -> {out['last_loss']:.3f})")
    return 0 if improved else 1


if __name__ == "__main__":
    sys.exit(main())
