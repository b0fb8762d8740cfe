"""Placement (the LPT split of the bucket programs over the cell's
chips): the busiest chip's device time in the bucket programs over the
mean across the cell's chips, from the trace's ``layer_s["bucket"]``.
1 is an even split.  A one-chip cell has no split and reads nothing."""
from _common import layer_seconds


def read(rec):
    s = layer_seconds(rec, "bucket")
    if rec["chips"] < 2 or not s or not sum(s.values()):
        return None
    return max(s.values()) / (sum(s.values()) / rec["chips"])
