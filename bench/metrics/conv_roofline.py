"""Kernel (the XLA convolutions inside the bucket programs): the least
time the chip could take for the convolutions it ran, over the device
time of the convolution ops.

Least time = convolution FLOPs of the zoo (bench/flops.py) at the rows
executed (the pad rung of the tick), over the bf16 peak: the served
path runs float32 at the default precision, one bf16 pass.  At these
sizes the compute bound is the larger one (about 90 KB of input against
4.8 GFLOP per window), so it sets the roofline.  Convolution ops are
the ops of the bucket programs that are convolutions or fusions rooted
at one (bench/trace_reduce.is_conv)."""
from _common import bucket_ticks


def read(rec):
    conv_s = sum(d["conv_s"] for d in rec["trace"]["devices"].values())
    n = bucket_ticks(rec)
    if not conv_s or not n:
        return None
    least = n * rec["conv_flops"] * rec["spad"] / rec["peak_flops"]
    return least / conv_s * 100.0
