"""Side models (the program's numpy vitals forests and labs regression,
run on the host once per slot in each tick's combine): host
milliseconds in their ``predict_proba`` calls per tick, over the ticks
that ended in the traced window.  The harness wraps each side model in
a ``TimedModel`` whose sink is ``rec["side"]``: (start, seconds) per
call."""
from _common import in_window, ticks


def read(rec):
    t = ticks(rec)
    calls = [s for start, s in rec["side"] if in_window(rec, start)]
    if not t or not calls:
        return None
    return sum(calls) / len(t) * 1e3
