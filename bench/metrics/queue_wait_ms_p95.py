"""Server (serving/server.EnsembleServer, serving/queues): 95th
percentile of the wait from submit to a worker's dequeue, from the
server's own spans (obs/spans.SpanRecorder)."""
from _common import in_window, p95


def read(rec):
    waits = [max(dq - sub, 0.0) for sub, dq, _ in rec["spans"]
             if in_window(rec, sub)]
    v = p95(waits)
    return None if v is None else v * 1e3
