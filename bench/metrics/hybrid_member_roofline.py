"""Hybrid member: the least time the chip could take for the hybrid
member's program, over the device time it took.

Least time = the family's FLOPs of the whole hybrid program per row
(``kernel_flops["hybrid"]``, bench/flops_granite4h.py) at the rows
executed (the pad rung of the tick), once a tick, over the bf16 peak:
the served path runs float32 at the default precision, one bf16 pass.
Its matmuls (some 470 GFLOP a row against 3 GB of weights a run at 64
rows) are compute bound, so the FLOPs set the roofline."""
import _hybrid


def read(rec):
    s, n = _hybrid.seconds(rec), _hybrid.ticks(rec)
    flops = rec["kernel_flops"].get("hybrid")
    if not s or not n or not flops:
        return None
    return n * flops * rec["spad"] / rec["peak_flops"] / s * 100.0
