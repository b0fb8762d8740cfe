"""Slot engine (serving/slots.SlotEngine.tick): mean wall time of the
ticks that ended in the traced window, as the engine's TickReport
gives it (it ends in block_until_ready and includes the host combine)."""
from _common import ticks


def read(rec):
    t = ticks(rec)
    return sum(x[1] for x in t) / len(t) * 1e3 if t else None
