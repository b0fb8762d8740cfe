"""Ensemble composition deep-dive: HOLMES vs all baselines (Table 2) with
the search trajectory (Fig. 6) and the accuracy-constrained dual (A.6).

    PYTHONPATH=src:. python examples/compose_ensemble.py
"""
import sys
import os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

from benchmarks.composition import bench_fig6, bench_table2
from benchmarks.zoo_setup import (binding_budget, build_zoo,
                                  make_profilers, single_model_stats)
from repro.core.composer import ComposerParams, compose
from repro.core.objective import AccuracyConstrainedObjective
from repro.core.profiles import SystemConfig


def accuracy_constrained_demo(zoo, extras):
    """A.6: min latency s.t. accuracy >= floor, same search machinery."""
    sysconf = SystemConfig(n_devices=2, n_patients=64)
    f_a, f_l = make_profilers(zoo, sysconf, extras)
    acc1, _ = single_model_stats(zoo, f_a, f_l)
    floor = float(np.quantile(acc1, 0.75))
    obj = AccuracyConstrainedObjective(floor)

    # reuse compose() by flipping the roles: maximize -latency with a
    # pseudo-"budget" on negative accuracy
    res = compose(len(zoo),
                  f_a=lambda b: -f_l(b),          # maximize -> min latency
                  f_l=lambda b: -f_a(b),          # constraint -> acc floor
                  latency_budget=-floor,
                  params=ComposerParams(N=8, K=6, seed=0))
    print(f"\nA.6 dual: accuracy floor {floor:.4f} -> "
          f"latency {-res.accuracy * 1000:.1f} ms at "
          f"accuracy {-res.latency:.4f} "
          f"(objective value {obj(-res.latency, -res.accuracy):.4f})")


def main():
    zoo, extras = build_zoo(n_patients=16, clips=8, steps=120)
    bench_table2(seeds=(0, 1), zoo=zoo, extras=extras)
    bench_fig6(zoo=zoo, extras=extras)
    accuracy_constrained_demo(zoo, extras)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
