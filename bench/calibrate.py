"""Readings that the limits of ``correct`` are set from, taken on the
chip in one process: the program's score gap over many seeds, and the
lower-precision control's gap on the same served windows.

    python3 bench/calibrate.py --workload <name> --seeds 12 --seconds 12

Each seed is a whole run of the cell (set-up, a short measured window at
the cell's own load, the reference) with ``control=True``: the reference
is computed a second time in bfloat16, put in the program's place on the
same sample and judged by the same checks.  One JSON line per seed goes
to standard output; the benchmark's own runs never run this.
"""
import argparse
import json
import sys
import time

import harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--base", type=int, default=3_000_000_000)
    ap.add_argument("--seconds", type=float, default=12.0)
    args = ap.parse_args(argv)
    for i in range(args.seeds):
        seed = args.base + 7919 * i
        t = time.monotonic()
        out = harness.run(args.workload, seed, args.seconds, False,
                          control=True)
        line = {"seed": seed, "correct": out["correct"],
                "attempted": out["attempted"], "failed": out["failed"],
                "checks": out["checks"], "control": out["control"],
                "readings": out["readings"],
                "metrics": {k: v["value"] for k, v in out["metrics"].items()},
                "memory_peak_bytes": out["device"]["memory_peak_bytes"],
                "seconds": time.monotonic() - t}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
