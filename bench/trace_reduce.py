"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

``extract`` reads the ``.xplane.pb`` the profiler wrote into plain
event lists: for each TPU device plane its ``XLA Modules`` and ``XLA Ops``
lines, and every host event.  ``reduce`` works on that extract only, so
``tests/test_bench_trace_reduce.py`` can check it on a small recorded trace.

Within the traced window (the host annotation ``bench.traced``) it
gives, per device:

* busy seconds: the union of the ``XLA Ops`` intervals (async copy
  lines are left out: a DMA in flight is not the device computing);
* seconds per program (``XLA Modules``, named by the jitted function,
  e.g. ``jit_fn``) and seconds per layer, a layer being a set of program
  names read from ``trace_names/*.json`` (files are merged, so a later
  change can add names without editing one);
* the number of runs of each layer's programs that started in it;
* seconds per layer and op class (``op_class``) of every op, so that a
  kernel's roofline reader selects its own ops (``layer_op_s``; ops of
  a program in no layer go under ``other``);

and for the whole trace the top device ops and the longest idle gaps,
each gap labelled by the host events that overlap it most.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
from typing import Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
WINDOW = "bench.traced"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OP = re.compile(r"^%([A-Za-z][\w\-]*?)(?:\.\d+)? = ")
_KIND = re.compile(r"kind=(k\w+)")


def layer_names() -> Dict[str, List[str]]:
    """{layer: [program names]} merged over ``trace_names/*.json``."""
    out: Dict[str, List[str]] = {}
    for path in sorted(glob.glob(os.path.join(HERE, "trace_names",
                                              "*.json"))):
        with open(path) as f:
            for layer, names in json.load(f).items():
                out.setdefault(layer, [])
                out[layer] += [n for n in names if n not in out[layer]]
    return out


def extract(path: str) -> Dict:
    """Plain lists from one ``.xplane.pb``:
    ``{"devices": {id: {"modules": [[name, start, end]], "ops": [...]}},
    "host": [[line, name, start, end]]}``, times in ns."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out: Dict = {"devices": {}, "host": []}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key:
                    dev[key] = [[e.name, e.start_ns, e.end_ns]
                                for e in line.events]
            out["devices"][int(m.group(1))] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                out["host"] += [[line.name, e.name, e.start_ns, e.end_ns]
                                for e in line.events]
    return out


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s, e, w0, w1):
    return max(s, w0), min(e, w1)


def op_class(name: str) -> str:
    """``opcode/kind`` of an ``XLA Ops`` event, e.g. ``fusion/kOutput``."""
    op = _OP.match(name)
    kind = _KIND.search(name)
    return (op.group(1) if op else name.split(" ")[0][:40]) + \
        ("/" + kind.group(1) if kind else "")


def is_conv(name: str) -> bool:
    """A convolution, or a fusion XLA roots at one (``kind=kOutput``,
    which on the TPU is what XLA makes of a convolution); ``name`` is an
    op's name or its ``op_class``."""
    cls = op_class(name)
    return cls.startswith("convolution") or cls.endswith("/kOutput")


def module_base(name: str) -> str:
    return name.split("(")[0]


def reduce(ex: Dict, layers: Optional[Dict[str, List[str]]] = None,
           window: Optional[Tuple[float, float]] = None,
           top: int = 10) -> Dict:
    layers = layer_names() if layers is None else layers
    if window is None:
        marks = [(s, e) for _, n, s, e in ex["host"] if n == WINDOW]
        if not marks:
            raise ValueError(f"no {WINDOW!r} annotation in the trace")
        window = marks[0]
    w0, w1 = window
    win_ns = w1 - w0
    to_layer = {n: layer for layer, names in layers.items() for n in names}
    devices = {}
    program_n: Dict[str, int] = {}
    op_time: Dict[str, float] = {}
    busy_all: List[Tuple[float, float]] = []
    for dev_id, dev in sorted((int(k), v) for k, v in ex["devices"].items()):
        mods = sorted((s, e, module_base(n)) for n, s, e in dev["modules"])
        starts = [m[0] for m in mods]
        prog_s: Dict[str, float] = {}
        for s, e, n in mods:
            if w0 <= s < w1 and n in to_layer:
                program_n[to_layer[n]] = program_n.get(to_layer[n], 0) + 1
            s, e = _clip(s, e, w0, w1)
            if e > s:
                prog_s[n] = prog_s.get(n, 0.0) + (e - s) * 1e-9
        busy = []
        layer_op_s: Dict[str, Dict[str, float]] = {}
        for n, s, e in dev["ops"]:
            cs, ce = _clip(s, e, w0, w1)
            if ce <= cs:
                continue
            busy.append((cs, ce))
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
            cls = op_class(n)
            key = f"{mod} {cls}"
            op_time[key] = op_time.get(key, 0.0) + (ce - cs) * 1e-9
            by_cls = layer_op_s.setdefault(to_layer.get(mod, "other"), {})
            by_cls[cls] = by_cls.get(cls, 0.0) + (ce - cs) * 1e-9
        busy = _union(busy)
        busy_all += [(dev_id, s, e) for s, e in busy]
        layer_s: Dict[str, float] = {}
        for n, sec in prog_s.items():
            layer = to_layer.get(n)
            if layer:
                layer_s[layer] = layer_s.get(layer, 0.0) + sec
        devices[dev_id] = {
            "busy_s": sum(e - s for s, e in busy) * 1e-9,
            "program_s": prog_s, "layer_s": layer_s,
            "layer_op_s": layer_op_s}
    matched = {layer for d in devices.values() for layer in d["layer_s"]}
    return {
        "window_s": win_ns * 1e-9,
        "devices": devices,
        "program_n": program_n,
        "unmatched_layers": sorted(set(layers) - matched),
        "device_ops": sorted(op_time.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": _idle_gaps(ex["host"], busy_all, devices, w0, w1, top),
    }


def _idle_gaps(host, busy_all, devices, w0, w1, top):
    """Longest stretches with no op on a device, labelled by the host
    events overlapping them most (share of the gap in brackets)."""
    gaps = []
    for dev_id in devices:
        iv = [(s, e) for d, s, e in busy_all if d == dev_id]
        edges = [w0] + [x for s, e in iv for x in (s, e)] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, a, b, dev_id))
    gaps.sort(reverse=True)
    win = w1 - w0
    host = [h for h in host if h[1] != WINDOW and (h[3] - h[2]) < win / 2]
    out = []
    for length, a, b, dev_id in gaps[:top]:
        over: Dict[str, float] = {}
        for line, name, s, e in host:
            o = min(e, b) - max(s, a)
            if o > 0:
                key = f"{line.split('/')[0]}:{name}"
                over[key] = over.get(key, 0.0) + o
        best = sorted(over.items(), key=lambda kv: -kv[1])[:2]
        label = " | ".join(f"{k} ({min(v / length, 1.0):.0%})"
                           for k, v in best) or "no host event"
        out.append([f"TPU:{dev_id} {label}", length * 1e-9])
    return out
