"""Whole tick: the members' FLOPs (the family's ``step_flops`` per
row) on the windows a tick scored, over the tick's wall time, over the
chips' bf16 peak: the share of the chips' peak the served step reaches
end to end."""
from _common import ticks


def read(rec):
    t = ticks(rec)
    secs = sum(x[1] for x in t)
    if not secs:
        return None
    work = sum(x[2] for x in t) * rec["step_flops"]
    return work / secs / (rec["chips"] * rec["peak_flops"]) * 100.0
