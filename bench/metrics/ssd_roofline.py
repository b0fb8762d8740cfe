"""Kernel (the Pallas SSD scan, kernels/ssd_scan.py, inside the hybrid
member's program): the least time the chip could take for the scans'
FLOPs, over the device time of the scan ops.

Least time = the family's SSD FLOPs per row (``kernel_flops["ssd"]``,
bench/flops_granite4h.py: the chunked quadratic form and the state
passing over the window's tokens) at the rows executed, once a tick,
over the bf16 peak.  The scan ops are the op classes named after the
kernel (``ssd_scan``), which only the hybrid program holds."""
import _hybrid


def read(rec):
    secs = sum(s for d in rec["trace"]["devices"].values()
               for cls, s in d["layer_op_s"].get("other", {}).items()
               if "ssd_scan" in cls)
    n = _hybrid.ticks(rec)
    flops = rec["kernel_flops"].get("ssd")
    if not secs or not n or not flops:
        return None
    return n * flops * rec["spad"] / rec["peak_flops"] / secs * 100.0
