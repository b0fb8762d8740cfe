"""Load generator (bench/traffic.py): 95th percentile of how late the
generator sent its packets and closes, actual minus scheduled time."""
from _common import in_window, p95


def read(rec):
    lags = [lag for due, lag in rec["gen_lag"] if in_window(rec, due)]
    v = p95(lags)
    return None if v is None else v * 1e3
