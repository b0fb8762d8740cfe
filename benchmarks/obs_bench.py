"""Observability bench: the tracing plane must be CHEAP, HONEST and
EXPORTABLE — three lanes, one ``BENCH_obs.json``.

1. **overhead** — real jitted serving (reduced ECG zoo, batch-aware
   ``EnsembleServer``) over a 64-patient trace, run in interleaved
   repetitions with span tracing OFF and ON.  Gates:

   * spans-enabled median per-query latency is within
     ``overhead_budget_pct`` (5%) of spans-disabled — observing the
     plane must not move the plane;
   * stage attribution explains the measured end-to-end latency:
     ``coverage`` = (queue + coalesce + marshal + dispatch + gather)
     / e2e within [0.9, 1.1] — attribution is checked against the
     clock, not assumed.

2. **sketch_fidelity** — the windowed-sketch telemetry vs the exact
   deque oracle: identical event counts and violation rate on a
   shared randomized trace, p50/p99 within the histogram's relative
   error bound, T_q bound within one sub-window bucket, and — the
   end-to-end criterion — the seeded DES controller runs
   (adaptive + tiered) take IDENTICAL action logs under either
   engine.

3. **export** — Prometheus text rendering (series count / bytes), a
   live ``/metrics`` scrape over HTTP (stdlib server), and the JSONL
   span dump all round-trip non-trivially.

``--smoke`` is the CI tier1-obs entry: tiny trace, relaxed overhead
gate (wall-clock medians on a shared CI box are noisy; the committed
BENCH_obs.json carries the strict 5% number), writes nothing.
"""
from __future__ import annotations

import json
import os
import statistics
import tempfile
import time
import urllib.request
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH_JSON = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_obs.json")

OBS_KEYS = (
    "n_patients", "windows_per_patient", "reps", "seed",
    "spans_off_ms", "spans_on_ms", "overhead_pct",
    "overhead_budget_pct", "overhead_ok",
    "coverage", "coverage_ok", "attribution",
    "sketch_fidelity", "export",
)
SKETCH_KEYS = (
    "counts_equal", "violation_rate_equal", "p50_rel_err", "p99_rel_err",
    "rel_err_bound", "tq_abs_err", "bucket_width",
    "adaptive_decisions_equal", "tiered_decisions_equal", "n_actions",
)
EXPORT_KEYS = (
    "prometheus_bytes", "prometheus_series", "http_status", "http_bytes",
    "jsonl_spans",
)


# -------------------------------------------------------- overhead lane
def _build_service(input_len: int = 250):
    import jax

    from repro.configs.ecg_zoo import zoo_specs
    from repro.serving.pipeline import EnsembleService, ZooMember
    from repro.models.ecg_resnext import init_ecg

    specs = zoo_specs(reduced=True, input_len=input_len)
    pool = [ZooMember(s, init_ecg(jax.random.PRNGKey(i), s))
            for i, s in enumerate(specs)]
    return EnsembleService(pool)


def _serve_once(svc, n_patients: int, windows_per_patient: int,
                input_len: int, seed: int, tracer=None):
    """One serving rep: every patient submits ``windows_per_patient``
    queries through the batch-aware server; returns (stats, tracer)."""
    from repro.serving.server import EnsembleServer

    srv = EnsembleServer(batch_handler=svc.predict_batch, n_workers=2,
                         max_batch=8, max_wait_ms=2.0,
                         tracer=tracer).start()
    rng = np.random.default_rng(seed)
    for _ in range(windows_per_patient):
        for p in range(n_patients):
            srv.submit(p, {"ecg": rng.standard_normal(
                (3, input_len)).astype(np.float32)})
    stats = srv.stop()
    return stats


def run_overhead(n_patients: int = 64, windows_per_patient: int = 4,
                 reps: int = 5, input_len: int = 250, seed: int = 0,
                 overhead_budget_pct: float = 5.0,
                 verbose: bool = True) -> Dict:
    """Interleaved OFF/ON reps (so drift hits both modes alike); the
    comparison is median-of-rep-means per-query latency."""
    from repro.obs.spans import SpanRecorder

    svc = _build_service(input_len)
    # warmup rep (jit compiles; discarded)
    _serve_once(svc, n_patients, 1, input_len, seed)

    off_ms: List[float] = []
    on_ms: List[float] = []
    tracer = SpanRecorder(keep=4 * n_patients * windows_per_patient * reps)
    for r in range(reps):
        st = _serve_once(svc, n_patients, windows_per_patient,
                         input_len, seed + r)
        off_ms.append(1e3 * st.mean_latency)
        st = _serve_once(svc, n_patients, windows_per_patient,
                         input_len, seed + r, tracer=tracer)
        on_ms.append(1e3 * st.mean_latency)

    med_off = statistics.median(off_ms)
    med_on = statistics.median(on_ms)
    overhead_pct = 100.0 * (med_on - med_off) / med_off
    att = tracer.attribution()
    coverage = att["coverage"]
    out = {
        "n_patients": n_patients,
        "windows_per_patient": windows_per_patient,
        "reps": reps, "seed": seed,
        "spans_off_ms": med_off, "spans_on_ms": med_on,
        "overhead_pct": overhead_pct,
        "overhead_budget_pct": overhead_budget_pct,
        "overhead_ok": bool(overhead_pct <= overhead_budget_pct),
        "coverage": coverage,
        "coverage_ok": bool(0.9 <= coverage <= 1.1),
        "attribution": {
            "n_spans": att["n_spans"],
            "by_status": att["by_status"],
            "stage_ms": {k: 1e3 * v / max(att["n_spans"], 1)
                         for k, v in att["stage_seconds"].items()},
            "mean_e2e_ms": 1e3 * att["mean_e2e_s"],
        },
    }
    if verbose:
        print(f"  overhead: off {med_off:.2f} ms  on {med_on:.2f} ms  "
              f"(+{overhead_pct:.2f}%, budget "
              f"{overhead_budget_pct:.0f}%)  coverage {coverage:.3f}")
        stage_ms = out["attribution"]["stage_ms"]
        print("  per-query stage ms: "
              + "  ".join(f"{k} {v:.2f}" for k, v in stage_ms.items()))
    return out, tracer, svc


# ------------------------------------------------- sketch-fidelity lane
def run_sketch_fidelity(seed: int = 0, verbose: bool = True) -> Dict:
    from benchmarks.adaptive_bench import (run_adaptive_sim,
                                           run_tiered_sim,
                                           synthetic_testbed)
    from repro.control.telemetry import SloTelemetry
    from repro.obs.sketch import REL_ERR_BOUND

    # shared randomized trace through both engines
    rng = np.random.default_rng(seed)
    mk = lambda exact: SloTelemetry(slo_seconds=0.3, window_seconds=20.0,
                                    clock=lambda: t, exact=exact)
    t = 0.0
    sk, ex = mk(False), mk(True)
    for _ in range(4000):
        t += float(rng.exponential(0.004))
        lat = float(rng.lognormal(-2.0, 0.8))
        for eng in (sk, ex):
            eng.record_arrival(t)
            eng.record_served(lat, t)
    s_sk, s_ex = sk.snapshot(), ex.snapshot()
    bw = sk.window / sk.n_buckets
    mu = 1.0 / 0.05
    tq_err = abs(sk.queueing_bound(mu, 0.01)
                 - ex.queueing_bound(mu, 0.01))
    fid = {
        "counts_equal": bool(
            s_sk.n_arrivals == s_ex.n_arrivals
            and s_sk.n_served == s_ex.n_served
            and s_sk.n_shed == s_ex.n_shed),
        "violation_rate_equal": bool(
            abs(s_sk.violation_rate - s_ex.violation_rate) < 1e-12),
        "p50_rel_err": abs(s_sk.p50 - s_ex.p50) / max(s_ex.p50, 1e-12),
        "p99_rel_err": abs(s_sk.p99 - s_ex.p99) / max(s_ex.p99, 1e-12),
        "rel_err_bound": REL_ERR_BOUND,
        "tq_abs_err": tq_err,
        "bucket_width": bw,
    }

    # end-to-end: seeded DES controller decisions identical per engine
    zoo, costs, f_a = synthetic_testbed(seed=0)
    sched = [(3, 24), (4, 72), (3, 24)]
    a_sk = run_adaptive_sim(zoo, costs, f_a, 1.0, sched, adaptive=True,
                            seed=seed, telemetry_exact=False)
    a_ex = run_adaptive_sim(zoo, costs, f_a, 1.0, sched, adaptive=True,
                            seed=seed, telemetry_exact=True)
    t_sk = run_tiered_sim(zoo, costs, f_a, 1.0, sched, seed=seed,
                          telemetry_exact=False)
    t_ex = run_tiered_sim(zoo, costs, f_a, 1.0, sched, seed=seed,
                          telemetry_exact=True)
    fid["adaptive_decisions_equal"] = bool(
        a_sk["actions"] == a_ex["actions"])
    fid["tiered_decisions_equal"] = bool(
        t_sk["actions"] == t_ex["actions"])
    fid["n_actions"] = len(a_sk["actions"]) + len(t_sk["actions"])
    if verbose:
        print(f"  sketch fidelity: counts_equal {fid['counts_equal']}  "
              f"p50 err {fid['p50_rel_err']:.4f}  "
              f"p99 err {fid['p99_rel_err']:.4f} "
              f"(bound {REL_ERR_BOUND:.4f})  tq err "
              f"{tq_err:.4f} (bucket {bw:.4f})")
        print(f"  decisions: adaptive "
              f"{fid['adaptive_decisions_equal']}  tiered "
              f"{fid['tiered_decisions_equal']}  "
              f"({fid['n_actions']} actions compared)")
    return fid


# ------------------------------------------------------------ export lane
def run_export(tracer, svc, verbose: bool = True) -> Dict:
    """Render/scrape/dump the export plane around a live traced run."""
    from repro.control.telemetry import SloTelemetry
    from repro.obs.export import (MetricsExporter, start_metrics_server,
                                  write_spans_jsonl)
    from repro.serving.server import EnsembleServer

    telemetry = SloTelemetry(slo_seconds=1.0, window_seconds=10.0)
    srv = EnsembleServer(batch_handler=svc.predict_batch, n_workers=2,
                         telemetry=telemetry, tracer=tracer).start()
    rng = np.random.default_rng(0)
    for p in range(8):
        srv.submit(p, {"ecg": rng.standard_normal(
            (3, 250)).astype(np.float32)})
    srv.drain(timeout=30.0)

    exporter = MetricsExporter(server=srv, telemetry=telemetry,
                               tracer=tracer, service=svc)
    text = exporter.render()
    series = sum(1 for ln in text.splitlines()
                 if ln and not ln.startswith("#"))

    httpd = start_metrics_server(exporter, port=0)
    try:
        url = f"http://127.0.0.1:{httpd.server_port}/metrics"
        with urllib.request.urlopen(url, timeout=10) as resp:
            status = resp.status
            body = resp.read()
    finally:
        httpd.shutdown()
    srv.stop()

    with tempfile.NamedTemporaryFile(suffix=".jsonl", delete=False) as f:
        jsonl_path = f.name
    try:
        n_spans = write_spans_jsonl(tracer, jsonl_path)
        with open(jsonl_path) as f:
            for line in f:
                json.loads(line)            # every line parses
    finally:
        os.unlink(jsonl_path)

    out = {
        "prometheus_bytes": len(text.encode()),
        "prometheus_series": series,
        "http_status": status,
        "http_bytes": len(body),
        "jsonl_spans": n_spans,
    }
    if verbose:
        print(f"  export: {series} series / "
              f"{out['prometheus_bytes']} B text, HTTP {status} "
              f"({out['http_bytes']} B), {n_spans} spans JSONL")
    return out


# --------------------------------------------------------------- schema
def check_obs_schema(data: Dict) -> None:
    for k in OBS_KEYS:
        assert k in data, f"missing obs key: {k}"
    assert data["overhead_ok"] is True, \
        (f"tracing overhead {data['overhead_pct']:.2f}% over budget "
         f"{data['overhead_budget_pct']}%")
    assert data["coverage_ok"] is True, \
        f"stage attribution coverage {data['coverage']:.3f} not in [0.9, 1.1]"
    assert data["attribution"]["n_spans"] > 0, "no spans recorded"
    fid = data["sketch_fidelity"]
    for k in SKETCH_KEYS:
        assert k in fid, f"missing sketch_fidelity key: {k}"
    assert fid["counts_equal"] is True
    assert fid["violation_rate_equal"] is True
    assert fid["p50_rel_err"] <= fid["rel_err_bound"], "p50 outside bound"
    assert fid["p99_rel_err"] <= fid["rel_err_bound"], "p99 outside bound"
    assert fid["tq_abs_err"] <= fid["bucket_width"] + 1e-9, \
        "T_q bound off by more than one bucket"
    assert fid["adaptive_decisions_equal"] is True, \
        "sketch flipped an adaptive-controller decision"
    assert fid["tiered_decisions_equal"] is True, \
        "sketch flipped a tiered-controller decision"
    assert fid["n_actions"] > 0, "DES runs took no actions to compare"
    exp = data["export"]
    for k in EXPORT_KEYS:
        assert k in exp, f"missing export key: {k}"
    assert exp["http_status"] == 200
    assert exp["prometheus_series"] >= 20, "suspiciously few series"
    assert exp["jsonl_spans"] > 0


def check_obs_file(path: str = BENCH_JSON) -> None:
    """CI gate on the committed BENCH_obs.json."""
    with open(path) as f:
        data = json.load(f)
    check_obs_schema(data)
    print(f"obs schema OK ({path})")


# ------------------------------------------------------------------ main
def bench_obs(n_patients: int = 64, windows_per_patient: int = 4,
              reps: int = 5, seed: int = 0,
              overhead_budget_pct: float = 5.0,
              write_json: bool = True, verbose: bool = True) -> Dict:
    if verbose:
        print(f"\nobservability bench ({n_patients} patients x "
              f"{windows_per_patient} windows x {reps} interleaved reps):")
    over, tracer, svc = run_overhead(
        n_patients, windows_per_patient, reps, seed=seed,
        overhead_budget_pct=overhead_budget_pct, verbose=verbose)
    over["sketch_fidelity"] = run_sketch_fidelity(seed=seed,
                                                  verbose=verbose)
    over["export"] = run_export(tracer, svc, verbose=verbose)
    check_obs_schema(over)
    if write_json:
        with open(BENCH_JSON, "w") as f:
            json.dump(over, f, indent=2)
        check_obs_file()
    return over


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-trace CI invocation: relaxed overhead "
                         "gate, writes nothing")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.smoke:
        bench_obs(n_patients=8, windows_per_patient=2, reps=2,
                  seed=args.seed, overhead_budget_pct=50.0,
                  write_json=False)
        print("obs smoke OK (overhead + fidelity + export lanes)")
    else:
        bench_obs(seed=args.seed)
