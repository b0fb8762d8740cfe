"""Bucket dispatch (serving/pipeline bucket programs ->
models/ecg_resnext.ecg_apply_stacked): device milliseconds of the bucket
programs per tick, summed over chips.  A tick is one run of every bucket
program; the count comes from the trace."""
from _common import bucket_ticks, layer_seconds


def read(rec):
    s = layer_seconds(rec, "bucket")
    n = bucket_ticks(rec)
    return None if s is None or not n else sum(s.values()) / n * 1e3
