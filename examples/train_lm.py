"""End-to-end driver: train a ~100M-parameter LM with segment checkpointing.

    # real ~100M model, on a TPU (one v5e chip holds it; the mesh spans
    # every local chip):
    PYTHONPATH=src python examples/train_lm.py --steps 300

    # CPU-sized demo of the same code path (~15M params):
    PYTHONPATH=src python examples/train_lm.py --small --steps 200

Interrupt it and re-run with --resume: training continues from the last
materialized segment on the exact same data stream.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "src"))

from repro.launch import train as train_mod  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true",
                    help="CPU-sized model instead of the full ~100M")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--resume", action="store_true")
    args, rest = ap.parse_known_args()

    argv = ["--arch", "helix100m", "--steps", str(args.steps),
            "--workdir", "results/train_lm", "--segment-steps", "25",
            "--batch", "8", "--seq", "128", "--lr", "3e-3"]
    if args.small:
        argv += ["--reduced", "--batch", "16"]
    if args.resume:
        argv += ["--resume"]
    sys.argv = ["train"] + argv + rest
    train_mod.main()


if __name__ == "__main__":
    main()
