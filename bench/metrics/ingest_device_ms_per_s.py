"""Ingest (serving/aggregator.DeviceIngest): device milliseconds of the
ring-update programs per traced second, summed over chips."""
from _common import layer_seconds


def read(rec):
    s = layer_seconds(rec, "ingest")
    return None if s is None else sum(s.values()) / \
        rec["trace"]["window_s"] * 1e3
