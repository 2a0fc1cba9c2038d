"""End-to-end driver: train the ~135M-param smollm config for a few hundred
steps on the synthetic pipeline with checkpointing + restart.

NOTE: full-size 135M on 1 CPU core is slow; the default runs the REDUCED
config for 300 steps (same code path as production).  Pass --full for the
real 135M config with a small batch.

    PYTHONPATH=src python examples/train_lm.py [--full] [--steps 300]
"""
import argparse
import os
import subprocess
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full 135M config (slow on CPU)")
    ap.add_argument("--steps", type=int, default=300)
    args = ap.parse_args()

    cmd = [sys.executable, "-m", "repro.launch.train",
           "--arch", "smollm-135m",
           "--steps", str(args.steps),
           "--batch", "8" if not args.full else "2",
           "--seq-len", "128",
           "--ckpt-dir", os.path.join(tempfile.gettempdir(), "repro_train_lm"),
           "--ckpt-every", "100",
           "--log-every", "20"]
    if not args.full:
        cmd.append("--smoke")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(HERE, "..", "src")
    raise SystemExit(subprocess.call(cmd, env=env))


if __name__ == "__main__":
    main()
