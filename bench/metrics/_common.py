"""Helpers the per-layer readers share: every reader takes the record
of one traced run and keeps to its traced window ``[t0, t1]``
(``time.monotonic`` seconds on the host, the profiler's own clock on
the device)."""
from __future__ import annotations

import numpy as np


def in_window(rec, t) -> bool:
    return rec["t0"] <= t <= rec["t1"]


def ticks(rec):
    """(end, seconds, n_scored, spad) of every tick that ended inside
    the traced window."""
    return [t for t in rec["ticks"] if in_window(rec, t[0])]


def layer_seconds(rec, layer):
    """{device: seconds} the layer's programs ran in the traced window,
    or None when the trace holds none of them."""
    out = {d: v["layer_s"][layer] for d, v in rec["trace"]["devices"].items()
           if layer in v["layer_s"]}
    return out or None


def bucket_ticks(rec):
    """Ticks' worth of bucket programs that started in the window."""
    n = rec["trace"]["program_n"].get("bucket", 0)
    return n / rec["n_buckets"] if n else 0.0


def p95(values):
    return float(np.percentile(np.asarray(values), 95)) if len(values) \
        else None
