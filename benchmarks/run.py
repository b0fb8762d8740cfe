"""Benchmark harness: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]

Prints a ``name,us_per_call,derived`` CSV summary at the end (us_per_call
is the benchmark's own wall time; derived is its headline metric).
"""
from __future__ import annotations

import argparse
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller zoo / fewer seeds")
    ap.add_argument("--only", default="",
                    help="comma-separated benchmark names")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import composition, serving_bench
    from benchmarks.roofline_table import bench_roofline
    from benchmarks.zoo_setup import build_zoo

    seeds = (0,) if args.quick else (0, 1, 2)
    print("[run] building/loading model zoo ...", flush=True)
    zoo, extras = build_zoo(
        n_patients=16 if args.quick else 32,
        clips=8 if args.quick else 12,
        steps=120 if args.quick else 160)

    rows = []

    def bench(name, fn, derive):
        if args.only and name not in args.only.split(","):
            return
        t0 = time.time()
        out = fn()
        dt = time.time() - t0
        rows.append((name, dt * 1e6, derive(out)))

    bench("table2_composition",
          lambda: composition.bench_table2(seeds=seeds, zoo=zoo,
                                           extras=extras),
          lambda t: f"HOLMES_auc={t['HOLMES']['roc_auc'][0]:.4f}")
    bench("fig6_trajectory",
          lambda: composition.bench_fig6(zoo=zoo, extras=extras),
          lambda t: f"holmes_iters={len(t['HOLMES'])}")
    bench("fig7_budget_sweep",
          lambda: composition.bench_fig7(seeds=seeds, zoo=zoo,
                                         extras=extras),
          lambda t: "holmes_wins="
          + str(sum(v["HOLMES"][0] >= v["NPO"][0] - 1e-6
                    for v in t.values())) + f"/{len(t)}")
    bench("fig8_surrogate_r2",
          lambda: composition.bench_fig8(zoo=zoo, extras=extras),
          lambda t: f"final_r2_lat={t[-1]['r2_lat']:.3f}")
    bench("fig9_online_vs_offline",
          serving_bench.bench_fig9,
          lambda t: f"staleness_ratio={t['staleness_ratio']:.0f}x")
    bench("fig10_scalability",
          serving_bench.bench_fig10,
          lambda t: "p95_64pat="
          + f"{t['vs_patients'][64]['p95_s'] * 1000:.1f}ms")
    bench("fig13_window_effects",
          serving_bench.bench_fig13,
          lambda t: f"ts_30s={t[30]['ts_s'] * 1000:.1f}ms")
    bench("measured_member_costs",
          serving_bench.bench_measured_costs,
          lambda t: f"n_members={len(t)}")
    # quick mode: fewer reps, and don't clobber the tracked
    # BENCH_serving.json trajectory with the noisy numbers
    bench("fused_serving",
          lambda: serving_bench.bench_fused_serving(
              reps=3 if args.quick else 10,
              write_json=not args.quick),
          lambda t: "microbatch_ms_per_query="
          + f"{t['modes']['fused_microbatch']['per_query_ms']:.2f}")
    # multi-device placement sweep: degrades to whatever device count
    # this process was launched with (run serving_bench.py standalone,
    # or under the CI lane's XLA_FLAGS, for the full 8-device sweep)
    bench("placement_sweep",
          lambda: serving_bench.bench_placement_sweep(
              reps=3 if args.quick else 5,
              write_json=not args.quick),
          lambda t: "makespan_min="
          + f"{min(v['makespan_s'] for v in t['sweep'].values()) * 1e3:.1f}ms")
    # adaptive control plane: static-vs-adaptive under a census spike
    # (quick mode keeps the noisy numbers out of the tracked JSON)
    from benchmarks.adaptive_bench import bench_adaptive
    bench("adaptive_serving",
          lambda: bench_adaptive(write_json=not args.quick,
                                 wallclock=not args.quick),
          lambda t: "viol_static/adaptive="
          + f"{t['static']['violation_rate']:.2f}/"
          + f"{t['adaptive']['violation_rate']:.2f}")
    bench("roofline_table",
          bench_roofline,
          lambda t: f"n_records={len(t)}")

    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.0f},{derived}")


if __name__ == "__main__":
    main()
