#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 benchmark/run.py --workload open_world_100k.walk --seed 7 \\
        --seconds 30 --trace 0

Prints informational lines and, last, the numbers the check compared
beside their limits on stderr; the last stdout line is the result object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, then ``checks``). Exits non-zero, with
no result, when JAX finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the raw profiler trace in this directory")
    args = ap.parse_args(argv)
    from benchmark import harness

    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), PROCESS_START,
                             trace_dir=args.trace_dir)
    except harness.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
