"""Hybrid member (serving/pipeline ``hybrid_member`` ->
models/granite_hybrid.granite_apply_stacked): device milliseconds of the
hybrid member's program (``jit_hybrid_member``) per tick, summed over
chips.  Nothing to read where no hybrid member is served."""
import _hybrid


def read(rec):
    s, n = _hybrid.seconds(rec), _hybrid.ticks(rec)
    return s / n * 1e3 if s and n else None
