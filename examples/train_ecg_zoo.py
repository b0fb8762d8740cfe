"""Train the paper's model zoo (§4.1.1) end-to-end: per-lead 1-D-stripe
ResNeXt classifiers across the width x depth grid, plus the vitals random
forest and labs logistic regression.  A few hundred optimizer steps per
model on the synthetic cohort (~100M-scale training overall).

    PYTHONPATH=src:. python examples/train_ecg_zoo.py [--steps 200]
"""
import argparse
import sys
import os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--patients", type=int, default=16)
    args = ap.parse_args()

    from benchmarks.zoo_setup import build_zoo
    zoo, extras = build_zoo(n_patients=args.patients, clips=8,
                            steps=args.steps)
    print("\nmodel zoo profiles (Table 3):")
    print(f"{'name':16s} {'depth':>5s} {'width':>5s} {'MACs':>10s} "
          f"{'mem(KB)':>8s} {'val AUC':>8s}")
    for p in zoo.profiles:
        print(f"{p.name:16s} {p.depth:5d} {p.width:5d} {p.macs:10.2e} "
              f"{p.memory_bytes / 1024:8.1f} {p.val_auc:8.4f}")
    aucs = [p.val_auc for p in zoo.profiles]
    print(f"\nzoo AUC range: {min(aucs):.3f} .. {max(aucs):.3f} "
          f"(spread is what the composer exploits)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
