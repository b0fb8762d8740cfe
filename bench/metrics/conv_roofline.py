"""Kernel (the XLA convolutions inside the bucket programs): the least
time the chip could take for the convolutions it ran, over the device
time of the convolution ops.

Least time = the family's convolution FLOPs per row
(``kernel_flops["conv"]``, bench/flops.py for the ECG zoo) at the rows
executed (the pad rung of the tick), over the bf16 peak: the served
path runs float32 at the default precision, one bf16 pass.  At these
sizes the compute bound is the larger one (about 90 KB of input against
4.8 GFLOP per window), so it sets the roofline.  Convolution ops are
the op classes of the bucket layer that are convolutions or fusions
rooted at one (bench/trace_reduce.is_conv)."""
from _common import bucket_ticks
from trace_reduce import is_conv


def read(rec):
    conv_s = sum(s for d in rec["trace"]["devices"].values()
                 for cls, s in d["layer_op_s"].get("bucket", {}).items()
                 if is_conv(cls))
    flops = rec["kernel_flops"].get("conv")
    n = bucket_ticks(rec)
    if not conv_s or not flops or not n:
        return None
    least = n * flops * rec["spad"] / rec["peak_flops"]
    return least / conv_s * 100.0
