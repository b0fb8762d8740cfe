"""One run of one cell: set up the served path, drive it open-loop for
the measured window, check what it served against the reference, and
return the result line.

The system under test is the program's slot-engine server:
``EnsembleServer(engine="slots")`` over a ``SlotEngine`` over a
``DeviceIngest``, with the configuration's members (and side models) in
an ``EnsembleService``, LPT-placed over the cell's chips when it has
more than one.  Everything else here is the benchmark's own: the
traffic (``traffic.py``), the client clock, the reduction of the trace,
and what the configuration names: its member family
(``families/<family>.py``: members, weights, work counts, the groups
and program names it reports) and its plain reference (``reference``).
What is generic stays here: the rings, Eq. 5, the side models and
their copies (``side_reference.py``), the limits and the control.

Clock: ``time.monotonic`` throughout, the same clock the server stamps
its spans with.  A query's latency runs from its close's *scheduled*
time to the moment the client thread holds its score.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import glob
import importlib.util
import json
import logging
import os
import shutil
import sys
import threading
import time
from typing import Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import side_reference as _side           # noqa: E402
import trace_reduce as _tr                # noqa: E402
import traffic as _traffic                # noqa: E402

COMPARE = 128            # served scores compared with the reference per run
DRAIN_S = 20.0           # how long past the window a due score is awaited
FAIL_MS = 60_000.0       # latency given to a query that failed or never came
CLIENT_POLL_S = 0.001    # the client's poll of the server's results
TRACE_S = 3.0            # seconds traced in a --trace 1 run
SLO_S = 1.0              # the paper's score deadline, the server's slo
FAMILIES = os.path.join(HERE, "families")
# what a member family module supplies (each family's docstring says
# what each is)
FAMILY_ROLES = ("members", "init", "program", "input_len", "step_flops",
                "kernel_flops", "cost", "gap_groups", "PROGRAMS")


class BenchError(RuntimeError):
    """The run cannot be made here (no chip, bad cell); no result."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------- files
def load_cell(workload: str):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    cfgs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(ROOT, cfgs[cell["config"]]["file"])) as f:
        cfg = json.load(f)
    mix = _traffic.load_mix(cell["traffic"])
    return bench, cell, cfg, mix


def load_limits(workload: str) -> Dict:
    """The limits of ``correct`` for one cell, set from chip readings
    (``limits/<workload>.json``; the readings are in PERF.md)."""
    with open(os.path.join(HERE, "limits", f"{workload}.json")) as f:
        return json.load(f)


def _load_module(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod          # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str):
    mdir = os.path.join(HERE, "metrics")
    if mdir not in sys.path:
        sys.path.insert(0, mdir)
    return _load_module(f"bench_metric_{name}",
                        os.path.join(mdir, f"{name}.py")).read


def load_family(cfg: Dict):
    """The member family the configuration names, from
    ``FAMILIES/<family>.py``, with every role of ``FAMILY_ROLES``."""
    name = cfg.get("family")
    path = os.path.join(FAMILIES, f"{name}.py")
    if not isinstance(name, str) or not os.path.isfile(path):
        raise BenchError(f"no member family {name!r} in {FAMILIES}")
    mod = _load_module(f"bench_family_{name}", path)
    missing = [r for r in FAMILY_ROLES if not hasattr(mod, r)]
    if missing:
        raise BenchError(f"member family {name!r} lacks {missing}")
    return mod


def load_reference(cfg: Dict):
    """The plain reference the configuration names (``reference``, a
    file relative to ``bench/``): ``member_scores(params, members,
    windows, *, dtype, precision, devices)``."""
    path = os.path.join(HERE, str(cfg.get("reference")))
    if not os.path.isfile(path):
        raise BenchError(f"no reference {cfg.get('reference')!r} for "
                         f"configuration {cfg.get('name')!r}")
    mod = _load_module("bench_reference_" + os.path.splitext(
        os.path.basename(path))[0], path)
    if not hasattr(mod, "member_scores"):
        raise BenchError(f"reference {path} has no member_scores")
    return mod


def peak_for(kind: str) -> Dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    if kind not in peaks:
        raise BenchError(f"no peak for device kind {kind!r} in peaks.json")
    return peaks[kind]


def compile_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` when set, else a fixed directory in
    the checkout, so that every run of a cell after the first reads its
    programs back instead of compiling them."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(HERE, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


# ------------------------------------------------------- compile counting
class CompileLog(logging.Handler):
    """Counts the programs JAX compiles (``jax_log_compiles`` lines)
    while ``armed``; the names tell a bucket program (``fn``) apart."""

    def __init__(self):
        super().__init__(logging.WARNING)
        self.armed = False
        self.names: List[str] = []      # compiled inside the window

    def emit(self, record):
        msg = record.getMessage()
        if self.armed and msg.startswith("Compiling "):
            self.names.append(msg.split()[1])


# --------------------------------------------------------- instruments
class TimedModel:
    """Delegates ``predict_proba`` and adds up the host seconds spent in
    it: how the traced run reads the side models' share of a tick."""

    def __init__(self, model, sink: List):
        self.model = model
        self.sink = sink

    def predict_proba(self, X):
        t = time.monotonic()
        y = self.model.predict_proba(X)
        self.sink.append((t, time.monotonic() - t))
        return y


@dataclasses.dataclass
class Query:
    qid: int
    bed: int
    close: int
    due: float                    # scheduled close, monotonic seconds
    in_window: bool
    shed: bool = False
    score: float = float("nan")
    got: Optional[float] = None   # when the client held the score


class Client(threading.Thread):
    """The bedside consumer: polls the server's retired queries and
    stamps each score as it arrives."""

    def __init__(self, server, queries: Dict[int, Query], trace: bool):
        super().__init__(name="bench-client", daemon=True)
        self.server, self.queries, self.trace = server, queries, trace
        self.stop = threading.Event()

    def run(self):
        from jax.profiler import TraceAnnotation
        while not self.stop.is_set():
            got = self.server.results()
            if got:
                now = time.monotonic()
                with TraceAnnotation("client.read") if self.trace \
                        else contextlib.nullcontext():
                    for _patient, score, _lat, ref in got:
                        q = self.queries.get(ref.extra.get("qid"))
                        if q is not None and q.got is None:
                            q.score, q.got = float(score), now
            else:
                time.sleep(CLIENT_POLL_S)


# ------------------------------------------------------------------ run
def run(workload: str, seed: int, seconds: float, trace: bool, *,
        platform: str = "tpu", t_start: Optional[float] = None,
        cfg: Optional[Dict] = None, mix: Optional[Dict] = None,
        chips: Optional[int] = None, control: bool = False,
        compare: int = COMPARE, peak_flops: Optional[float] = None) -> Dict:
    """One run; returns the result line as a dict.  ``cfg``, ``mix``,
    ``chips``, ``platform`` and ``peak_flops`` let a test drive the
    same path at a tiny size on the CPU; ``control`` also puts the
    lower-precision control in the program's place on the compared
    sample and returns its ``correct`` and checks under ``control``."""
    t_start = time.monotonic() if t_start is None else t_start
    bench, cell, cfg0, mix0 = load_cell(workload)
    cfg = cfg if cfg is not None else cfg0
    mix = mix if mix is not None else mix0
    chips = chips if chips is not None else int(cell["chips"])
    fam = load_family(cfg)
    ref = load_reference(cfg)

    import jax
    devices = jax.devices()
    d0 = devices[0]
    log(f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devices)}")
    if d0.platform != platform:
        raise BenchError(f"JAX found {d0.platform!r}; this benchmark "
                         f"runs on {platform!r}")
    if len(devices) < chips:
        raise BenchError(f"cell {workload} needs {chips} chips, JAX found "
                         f"{len(devices)}")
    devs = devices[:chips]
    log(f"compile cache: {compile_cache_dir()}")
    clog = CompileLog()
    logging.getLogger("jax").addHandler(clog)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.models.tabular import LogisticRegression, VitalsForest
    from repro.obs.spans import SpanRecorder
    from repro.serving.aggregator import (DeviceIngest, DeviceWindowRef,
                                          ModalitySpec)
    from repro.serving.pipeline import EnsembleService
    from repro.serving.placement import grouped_lpt_placement
    from repro.serving.server import EnsembleServer
    from repro.serving.slots import SlotEngine

    # ---- the members: weights made on the device from the seed
    members = fam.members(cfg)
    params = fam.init(members, seed, device=devs[0])
    jax.block_until_ready(params)
    log(f"set-up: weights made at {time.monotonic() - t_start:.1f} s")
    served, stacks = fam.program(members, params)

    # ---- side models, fitted from the seed on the copied generators
    side = cfg.get("side_models") or {}
    side_s: List = []
    vit_model = lab_model = None
    cohort = None
    if side:
        cohort = _traffic.side_cohort(seed, side["cohort"],
                                      int(cfg["window_s"]))
        vf = side["vitals_forest"]
        vit_model = VitalsForest(_traffic.N_VITALS, n_trees=vf["n_trees"],
                                 seed=seed).fit(cohort["vitals"],
                                                cohort["label"])
        lab_model = LogisticRegression(seed=seed).fit(cohort["labs"],
                                                      cohort["label"])
        if trace:
            vit_model = TimedModel(vit_model, side_s)
            lab_model = TimedModel(lab_model, side_s)

    placement = None
    if chips > 1:
        costs = [fam.cost(members[g[0]]) * len(g) for g in stacks]
        placement = grouped_lpt_placement(stacks, costs, chips)
        log(f"placement over {chips} chips (LPT on the family's costs): "
            f"members per chip {[len(s) for s in placement.assignment]}")
    service = EnsembleService(served, vitals_model=vit_model,
                              labs_model=lab_model, placement=placement,
                              devices=devs if chips > 1 else None)

    # ---- traffic and the rings, filled with one window of history
    win_s = int(cfg["window_s"])
    tr = _traffic.build_traffic(mix, seed, seconds, win_s,
                                vitals=bool(side), labs=bool(side))
    mods = [ModalitySpec("ecg", _traffic.ECG_HZ, _traffic.ECG_LEADS)]
    if side:
        mods.append(ModalitySpec("vitals", _traffic.VITALS_HZ,
                                 _traffic.N_VITALS))
    ingest = DeviceIngest(mods, tr.beds, window_seconds=float(win_s))
    for b in range(tr.beds):
        ingest.ingest(0.0, b, "ecg", tr.ecg[b, :, :tr.history])
        if side:
            ingest.ingest(0.0, b, "vitals",
                          tr.vitals[b, :, :tr.vitals_history])
    want = {m: ingest.want[m] for m in ingest.want}

    log(f"set-up: rings filled at {time.monotonic() - t_start:.1f} s")
    engine = SlotEngine(service, ingest)
    engine.warm()
    log(f"set-up: engine warm at {time.monotonic() - t_start:.1f} s")
    ticks: List = []
    # the member scores of every tick that stamped a close, keyed by
    # (slot, close version): the tick has already read its states to
    # the host, so np.asarray here returns that copy and adds no device
    # work
    cols: Dict = {}

    def on_tick(r):
        ticks.append((time.monotonic(), r.seconds, r.n_scored, r.spad))
        if len(r.stamped):
            mat = np.empty((len(members), r.spad))
            for g in engine.groups:
                mat[g.rows] = np.asarray(g.state)
            for s, v in zip(r.stamped, r.versions):
                cols.setdefault((int(s), int(v)), []).append(
                    mat[:, s].copy())
    engine.on_tick = on_tick
    tracer = SpanRecorder(keep=1 << 16) if trace else None
    server = EnsembleServer(engine="slots", slot_engine=engine,
                            slo_seconds=SLO_S, tracer=tracer)
    queries: Dict[int, Query] = {}
    client = Client(server, queries, trace)
    server.start()
    client.start()

    def submit(b: int, j: int, due: float, in_window: bool) -> None:
        qid = len(queries)
        ends = {"ecg": int(tr.close_ends[b, j])}
        valid = {"ecg": want["ecg"]}
        extra = {"qid": qid}
        if side:
            ends["vitals"] = int(tr.close_vends[b, j])
            valid["vitals"] = want["vitals"]
            extra["labs"] = tr.labs[b, j]
        q = Query(qid, b, j, due, in_window)
        queries[qid] = q
        ref = DeviceWindowRef(ingest=ingest, patient=b, ends=ends,
                              valid=valid, extra=extra)
        if not server.submit(b, ref):
            q.shed = True

    try:
        # close 0 of every bed on the history: admits the census and
        # runs the first ticks (the fold compiles there)
        t0 = time.monotonic()
        for b in range(tr.beds):
            submit(b, 0, t0, False)
        deadline = time.monotonic() + 120.0
        while any(q.got is None and not q.shed for q in queries.values()) \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        log(f"set-up: first ticks done at {time.monotonic() - t_start:.1f} s")
        live = _drive(tr, ingest, submit, clog, trace, workload, seed)
        _drain(queries)
    finally:
        logging.getLogger("jax").removeHandler(clog)
        server.stop()
        client.stop.set()
        client.join(timeout=5.0)
    leaked = list(server.leaked) + (["bench-client"] if client.is_alive()
                                    else [])

    setup_s = live["w0"] - t_start
    _log_window(live, ticks, queries)
    peak = _peak_bytes(devs)
    ticks_all = list(ticks)
    spans = ([(s.t_submit, s.t_dequeue, s.t_retire)
              for s in tracer.spans()] if tracer is not None else [])
    spad = engine._Spad
    n_buckets = len(service._buckets)
    del server, engine, service, ingest, served, submit, client
    gc.collect()

    # ---- what the window attempted, and what came back
    in_win = [q for q in queries.values() if q.in_window]
    attempted = len(in_win)
    lat_ms = []
    failed = 0
    for q in in_win:
        ok = (not q.shed and q.got is not None and np.isfinite(q.score))
        if ok:
            lat_ms.append((q.got - q.due) * 1e3)
        else:
            failed += 1
            lat_ms.append(FAIL_MS)
    log(f"window: {attempted} closes due, {failed} failed (shed "
        f"{sum(q.shed for q in in_win)}, never retired "
        f"{sum(q.got is None and not q.shed for q in in_win)}, NaN "
        f"{sum(q.got is not None and not np.isfinite(q.score) for q in in_win)})")
    log(f"compiles inside the measured window: {len(live['compiles'])} "
        f"{sorted(set(live['compiles']))}")
    if leaked:
        log(f"threads left running after stop: {leaked}")

    # ---- correctness: a seeded sample of the finished queries
    checks = _check(workload, cfg, fam, ref, tr, params, members, in_win,
                    seed, devs, cohort, side, compare, control,
                    live["compiles"], cols)
    ctl = checks.pop("_control", None)
    readings = checks.pop("_readings", None)
    correct = all(c["ok"] for c in checks.values())

    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    metrics: Dict[str, Dict] = {}
    breakdown = None
    if not trace:
        lat = np.asarray(lat_ms)
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        vals = {"score_p50_ms": float(np.percentile(lat, 50)) if len(lat)
                else FAIL_MS,
                "score_p95_ms": float(np.percentile(lat, 95)) if len(lat)
                else FAIL_MS,
                "setup_s": setup_s}
        for m in bench["end_to_end"]:
            if "workloads" in m and workload not in m["workloads"]:
                continue
            metrics[m["name"]] = {"value": vals[m["name"]],
                                  "unit": units[m["name"]]}
    else:
        red = live["trace"]
        rec = {
            "trace": red, "t0": live["t0"], "t1": live["t1"],
            "gen_lag": live["lag"], "spans": spans, "ticks": ticks_all,
            "side": side_s, "chips": chips, "spad": spad,
            "n_buckets": n_buckets,
            "step_flops": fam.step_flops(members),
            "kernel_flops": fam.kernel_flops(members),
            "peak_flops": peak_flops if peak_flops is not None
            else peak_for(d0.device_kind)["bf16_flops_per_s"],
        }
        for m in bench["per_layer"]:
            if "workloads" in m and workload not in m["workloads"]:
                continue
            v = load_reader(m["name"])(rec)
            if v is None:
                log(f"per-layer metric {m['name']}: nothing to read")
                continue
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        busy = [d["busy_s"] for d in red["devices"].values()]
        device["busy_s"] = float(np.mean(busy)) if busy else 0.0
        device["window_s"] = red["window_s"]
        breakdown = {"device_ops": [[n, s] for n, s in red["device_ops"]],
                     "idle_gaps": red["idle_gaps"]}
        for layer in red["unmatched_layers"]:
            log(f"TRACE: layer {layer!r} matched no program in the trace "
                f"(names: {_tr.layer_names()[layer]})")

    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r} "
            f"({'ok' if c['ok'] else 'FAILED'})")
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if control:
        out["control"] = ctl
        out["readings"] = readings
    out["checks"] = {n: {"value": c["value"], "limit": c["limit"]}
                     for n, c in checks.items()}
    return out


def _log_window(live, ticks, queries) -> None:
    """One stderr line on how the window went: the ticks, how late the
    generator ran, the collector's pauses and when the slowest score
    came, so a run whose tail stands out can be read afterwards."""
    w0, w1 = live["w0"], live["w1"]
    sec = np.asarray([s for t, s, _, _ in ticks if w0 <= t <= w1])
    lag = live["lag"][(live["lag"][:, 0] >= w0) & (live["lag"][:, 0] < w1), 1]
    gcs = np.asarray(live["gc"])
    lat = [(q.got - q.due, q.due - w0) for q in queries.values()
           if q.in_window and q.got is not None]
    worst = max(lat) if lat else (float("nan"), float("nan"))
    med = float(np.median(sec)) if len(sec) else float("nan")
    log(f"window detail: {len(sec)} ticks, median {med * 1e3:.1f} ms, "
        f"max {sec.max() * 1e3 if len(sec) else float('nan'):.1f} ms, "
        f"{int((sec > 2 * med).sum())} over twice the median; generator "
        f"late p95 {np.percentile(lag, 95) * 1e3 if len(lag) else 0:.1f} "
        f"ms, max {lag.max() * 1e3 if len(lag) else 0:.1f} ms; "
        f"{len(gcs)} collector pauses, "
        f"{gcs.sum() * 1e3 if len(gcs) else 0:.1f} ms in all; slowest "
        f"score {worst[0] * 1e3:.1f} ms, due {worst[1]:.1f} s into the "
        f"window")


def _peak_bytes(devs) -> Optional[int]:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _drive(tr, ingest, submit, clog, trace, workload, seed):
    """Replay the schedule against the clock.  Returns the window's
    bounds, the generator's lag per event, the compiles inside the
    window and, for a traced run, the reduced trace."""
    import jax
    from jax.profiler import TraceAnnotation
    ev = tr.events
    lead = 0.05
    t_live = time.monotonic() + lead
    w0 = t_live + tr.window_start
    w1 = t_live + tr.window_end
    t_tr0 = w0 + max(0.0, (tr.window_end - tr.window_start - TRACE_S) / 2)
    tdir = os.path.join(HERE, ".trace", f"{workload}-{seed}")
    state = {"armed": False, "tracing": False}
    lag = np.zeros((len(ev), 2))
    vh, h, pk = tr.vitals_history, tr.history, tr.packet
    # JAX's own handler would print every compile line; it stays quiet
    # while the harness counts them
    quiet = [(hd, hd.level) for hd in logging.getLogger("jax").handlers
             if hd is not clog]
    for hd, _ in quiet:
        hd.setLevel(logging.ERROR)
    jax.config.update("jax_log_compiles", True)
    span = {0: "gen.ecg", 1: "gen.vitals", 2: "gen.close"}
    gc_pauses: List[float] = []
    gc_t = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            gc_t[0] = time.monotonic()
        elif state["armed"]:
            gc_pauses.append(time.monotonic() - gc_t[0])
    gc.callbacks.append(on_gc)

    def profile():
        # its own thread: starting and stopping the profiler each can
        # take seconds on the chip, and the generator keeps its schedule
        try:
            time.sleep(max(0.0, t_tr0 - time.monotonic()))
            shutil.rmtree(tdir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            # host spans at the lowest level that keeps the harness's
            # annotations and JAX's dispatch: the profiler holds the
            # interpreter lock while it collects them
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(tdir, profiler_options=opts)
            with TraceAnnotation(_tr.WINDOW):
                state["t0"] = time.monotonic()
                state["tracing"] = True
                time.sleep(TRACE_S)
                state["tracing"] = False
                state["t1"] = time.monotonic()
            jax.profiler.stop_trace()
        except Exception as e:              # reported after the window
            state["error"] = e

    profiler = threading.Thread(target=profile, name="bench-profiler")
    if trace:
        profiler.start()
    for i, (t, kind, b, k) in enumerate(ev):
        due = t_live + t
        now = time.monotonic()
        if due > now:
            time.sleep(due - now)
        if not state["armed"] and due >= w0:
            state["armed"] = clog.armed = True
        b, k, kind = int(b), int(k), int(kind)
        with TraceAnnotation(span[kind]) if state["tracing"] \
                else contextlib.nullcontext():
            if kind == _traffic.ECG:
                ingest.ingest(t, b, "ecg",
                              tr.ecg[b, :, h + k * pk: h + (k + 1) * pk])
            elif kind == _traffic.VITALS:
                ingest.ingest(t, b, "vitals",
                              tr.vitals[b, :, vh + k: vh + k + 1])
            else:
                submit(b, k, due, w0 <= due < w1)
        lag[i] = (due, time.monotonic() - due)
    now = time.monotonic()
    if now < w1:
        time.sleep(w1 - now)
    if trace:
        profiler.join()
    gc.callbacks.remove(on_gc)
    clog.armed = False
    jax.config.update("jax_log_compiles", False)
    for hd, level in quiet:
        hd.setLevel(level)
    out = {"w0": w0, "w1": w1, "lag": lag, "compiles": list(clog.names),
           "gc": gc_pauses}
    if trace:
        if "error" in state:
            raise BenchError(f"the profiler failed: {state['error']!r}")
        paths = sorted(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                                 recursive=True))
        if not paths:
            raise BenchError("the profiler wrote no trace")
        out["trace"] = _tr.reduce(_tr.extract(paths[-1]))
        out["t0"], out["t1"] = state["t0"], state["t1"]
        shutil.rmtree(tdir, ignore_errors=True)
    return out


def _drain(queries: Dict[int, Query]) -> None:
    """Wait, at most ``DRAIN_S``, for every in-window query to come back."""
    deadline = time.monotonic() + DRAIN_S
    while time.monotonic() < deadline and not all(
            q.got is not None or q.shed
            for q in queries.values() if q.in_window):
        time.sleep(0.01)


def _check(workload, cfg, fam, ref_mod, tr, params, members, in_win, seed,
           devs, cohort, side, compare, control, compiles,
           cols) -> Dict[str, Dict]:
    """Compare a seeded sample of the served scores with the
    configuration's reference (``ref_mod``):
    the ensemble score the client held and every member's score in the
    tick that served it (``_gaps``), each held to its limit where the
    cell's limits name it.  Returns {name: {value, limit, ok}}, with the
    readings under ``_readings`` and, when asked for, the
    lower-precision control's ``correct``, checks and readings under
    ``_control``."""
    limits = load_limits(workload)
    done = [q for q in in_win if q.got is not None and np.isfinite(q.score)]
    rng = np.random.default_rng([int(seed), 7])
    n = min(compare, len(done))
    pick = sorted(rng.choice(len(done), size=n, replace=False)) if n else []
    sample = [done[i] for i in pick]
    checks: Dict[str, Dict] = {}
    want_n = min(compare, len(in_win))
    checks["compared"] = {"value": n, "limit": want_n, "ok": n >= want_n
                          and n > 0}
    bucket_compiles = [c for c in compiles if c in fam.PROGRAMS]
    checks["bucket_compiles_in_window"] = {
        "value": len(bucket_compiles), "limit": 0,
        "ok": not bucket_compiles}
    if not sample:
        for name in limits:
            checks[name] = _gap_check(None, limits[name])
        return checks
    L = fam.input_len(members)
    groups = fam.gap_groups(members)
    side_of = _side_scores(cfg, side, cohort, seed, tr)
    # the member scores of the tick that stamped each served score, and
    # the close whose window that tick scored: the query's own, or,
    # where a later close of the bed arrived before any tick stamped
    # it, the later close whose score the slot engine served
    found = [_served_by(q, cols, side_of) for q in sample]
    closes = [f[0] if f else q.close for q, f in zip(sample, found)]
    wins = np.stack([tr.ecg[q.bed, :, tr.close_ends[q.bed, c] - L:
                            tr.close_ends[q.bed, c]]
                     for q, c in zip(sample, closes)])
    sides = [side_of(q.bed, c) for q, c in zip(sample, closes)]
    side_rows = [np.asarray(r) for r in zip(*sides)]
    t = time.monotonic()
    prec = cfg.get("matmul_precision", "default")
    mat = ref_mod.member_scores(params, members, wins, devices=devs,
                                precision=prec)
    ref = _side.ensemble_scores(mat, side_rows)
    served = np.asarray([q.score for q in sample])
    log(f"reference: {n} windows x {len(members)} members at {prec} "
        f"precision in {time.monotonic() - t:.1f} s")
    got = [(i, f[1], f[2]) for i, f in enumerate(found) if f]
    log(f"member scores found for {len(got)} of {n} compared closes, "
        f"{sum(c != q.close for q, c in zip(sample, closes))} of them "
        f"served with a later close's score")
    rows = [i for i, _, _ in got]
    served_mat = (np.stack([c for _, c, _ in got], axis=1) if got
                  else np.zeros((len(members), 0)))
    read = _gaps(served, served_mat, [g for _, _, g in got], ref,
                 mat[:, rows], groups)
    log("readings: " + " ".join(f"{k}={v:.4g}" for k, v in read.items()
                                if v is not None))
    for name in limits:
        checks[name] = _gap_check(read.get(name), limits[name])
    if any(not name.startswith("score") for name in limits):
        checks["members_compared"] = {"value": len(got), "limit": n,
                                      "ok": len(got) == n}
    checks["_readings"] = read
    if control:
        # the reference in bfloat16, put in the program's place on the
        # same windows, through the same comparison
        cmat = ref_mod.member_scores(params, members, wins, devices=devs,
                                     dtype="bfloat16")
        ctl = np.asarray([_eq5(cmat[:, i], sides[i]) for i in range(n)])
        cread = _gaps(ctl, cmat, [0.0] * n, ref, mat, groups)
        cchecks = {k: v for k, v in checks.items() if k[0] != "_"}
        for name in limits:
            cchecks[name] = _gap_check(cread.get(name), limits[name])
        # both sides again against the reference at the highest
        # precision: readings for PERF.md, not compared
        hmat = ref_mod.member_scores(params, members, wins, devices=devs,
                                     precision="highest")
        href = _side.ensemble_scores(hmat, side_rows)
        checks["_control"] = {
            "correct": all(c["ok"] for c in cchecks.values()),
            "checks": {n: {"value": c["value"], "limit": c["limit"]}
                       for n, c in cchecks.items()},
            "readings": cread,
            "highest": {"program": _gaps(served, served_mat, [], href,
                                         hmat[:, rows], groups),
                        "control": _gaps(ctl, cmat, [], href, hmat,
                                         groups)},
            # each member's mean gap, program and control
            "per_member": [np.abs(served_mat - mat[:, rows]).mean(1).tolist(),
                           np.abs(cmat - mat).mean(1).tolist()]}
    return checks


# the numbers a cell may compare, each held to its limit where the
# cell's limits file names it; ``member_mean_gap`` also comes per group
# of the family's ``gap_groups`` (``member_mean_gap.b16``)
GAPS = ("combine_gap", "score_gap", "score_mean_gap", "member_gap",
        "member_mean_gap")


def _eq5(col, side) -> float:
    """Eq. 5 as the program's host combine takes it: the float64 mean of
    the member scores, side-model scores appended, as one list."""
    return float(np.mean(list(col) + list(side)))


def _side_scores(cfg, side, cohort, seed, tr):
    """The plain side models' scores of bed b's close c, in Eq. 5's
    order, as ``side_of(b, c)``; none without side models."""
    if not side:
        return lambda b, c: ()
    vf = side["vitals_forest"]
    rvit = _side.VitalsForest(_traffic.N_VITALS, vf["n_trees"],
                              vf["max_depth"], seed).fit(
        cohort["vitals"], cohort["label"])
    lr = side["labs_logistic"]
    rlab = _side.LogisticRegression(lr["lr"], lr["steps"], lr["l2"],
                                    seed).fit(cohort["labs"],
                                              cohort["label"])
    W = int(cfg["window_s"]) * _traffic.VITALS_HZ

    @functools.lru_cache(maxsize=None)
    def side_of(b: int, c: int):
        end = tr.close_vends[b, c]
        vit = tr.vitals[b, :, end - W: end]
        return (float(rvit.predict_proba(vit[None])[0]),
                float(rlab.predict_proba(tr.labs[b, c][None])[0]))
    return side_of


def _served_by(q, cols, side_of):
    """(close, member column, gap) of the tick that stamped the score
    the client held for ``q``, or None when no tick stamped this close
    or a later one of its bed.

    The slot engine answers a close with the bed's newest stamped
    score, so a close that a later one superseded before any tick
    stamped it is answered with that later close's score.  The
    candidates are the bed's closes from ``q``'s own on, in order;
    the first whose column gives the served score exactly under Eq. 5,
    with that close's side rows, is the one; failing that, the column
    that comes nearest."""
    best = None
    for v in sorted(v for b, v in cols if b == q.bed and v > q.close):
        side = side_of(q.bed, v - 1)
        comb = [abs(q.score - _eq5(c, side)) for c in cols[(q.bed, v)]]
        k = int(np.argmin(comb))
        if best is None or comb[k] < best[2]:
            best = (v - 1, cols[(q.bed, v)][k], comb[k])
        if comb[k] == 0.0:
            break
    return best


def _gaps(score, member_mat, combine, ref_score, ref_mat,
          groups: Dict[str, List[int]]) -> Dict:
    """The widest gap of the served score from Eq. 5 over its own
    members, and the widest and the mean gap from the reference of the
    ensemble scores ([windows]) and of the member scores ([members,
    windows]), the mean also over the members of each group."""
    e = np.abs(score - ref_score)
    d = np.abs(member_mat - ref_mat)
    out = {"combine_gap": float(max(combine)) if len(combine) else None,
           "score_gap": float(np.max(e)),
           "score_mean_gap": float(np.mean(e)),
           "member_gap": float(np.max(d)) if d.size else None,
           "member_mean_gap": float(np.mean(d)) if d.size else None}
    for tag, rows in groups.items():
        out[f"member_mean_gap.{tag}"] = (float(np.mean(d[rows])) if d.size
                                         else None)
    return out


def _gap_check(gap: Optional[float], limit: float) -> Dict:
    return {"value": gap, "limit": limit,
            "ok": gap is not None and gap <= limit}
