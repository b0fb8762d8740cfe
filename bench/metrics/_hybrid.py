"""What the hybrid member's readers share.  The hybrid's program
(``jit_hybrid_member``) is in no layer of ``trace_names/``: its device
seconds come from the trace's per-program seconds, and its ops' from
the op classes of ops in no layer (``other``).  It runs once a tick
beside the zoo's ``n_buckets - 1`` bucket programs, so the bucket
programs that started in the traced window count its ticks."""
PROGRAM = "jit_hybrid_member"


def seconds(rec):
    """Device seconds of the hybrid program in the traced window,
    summed over chips."""
    return sum(d["program_s"].get(PROGRAM, 0.0)
               for d in rec["trace"]["devices"].values())


def ticks(rec):
    n = rec["trace"]["program_n"].get("bucket", 0)
    return n / (rec["n_buckets"] - 1) if n and rec["n_buckets"] > 1 \
        else 0.0
