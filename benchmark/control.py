#!/usr/bin/env python3
"""Read the check's numbers for the program and for its control, on many
seeds in one process, at a cell's own size and load.

    python3 benchmark/control.py --workload open_world_100k.walk \\
        --seeds 101,102,103 --seconds 5

For each seed: the cell's world, its enter storm and warm ticks, a short
closed-loop window through the program, then the check of the sampled
ticks twice. Once for the program's own pairs. Once for the control: the
reference computed in bfloat16, the precision below the configuration's
float32 positions, put in the program's place for the same ticks. The
limits of the check sit between the program's largest reading (the lower
one) and the control's smallest (the upper one). One JSON line per seed,
then one with both readings. The benchmark's own runs never run this.
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


def control_ticks(ticks: list, before, capacity: int, pick: list) -> list:
    """The ticks with the picked ones' pairs replaced by the bfloat16
    reference's."""
    import ml_dtypes
    import numpy as np

    from benchmark import reference

    out = [dict(t) for t in ticks]
    for i in pick:
        prev = before if i == 0 else ticks[i - 1]["epoch"]
        cur = ticks[i]["epoch"]
        e, lv = reference.events(
            *(reference.interest_keys(ep.pos, ep.active, ep.space, ep.radius,
                                      ml_dtypes.bfloat16)
              for ep in (prev, cur)))
        out[i]["enters"] = np.stack([e // capacity, e % capacity], axis=1)
        out[i]["leaves"] = np.stack([lv // capacity, lv % capacity], axis=1)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    from benchmark import harness

    numbers = tuple(harness.LIMITS)
    cell = harness.load_cell(args.workload)
    try:
        harness.require_chips(cell["workload"]["chips"])
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    harness.configure_cache()
    engine = harness.build_engine(cell["config"])
    cap = engine.params.capacity
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        world, warm = harness.establish(engine, cell, seed)
        ticks, t0, t1 = harness.drive(
            engine, world, lambda n, el: el >= args.seconds,
            harness._no_annotation)
        before = warm[-1]["epoch"]
        pick = harness.sample(ticks, seed, cell["config"]["entities"])
        keys: dict = {}
        prog = harness.check(ticks, before, cap, pick, keys)
        ctrl = harness.check(control_ticks(ticks, before, cap, pick),
                             before, cap, pick, keys)
        row = {"seed": seed, "ticks": len(ticks),
               "window_s": t1 - t0, "compared_ticks": pick,
               "compared_pairs": prog["compared_pairs"],
               "modes": prog["modes"],
               "program": {k: prog[k] for k in numbers},
               "control": {k: ctrl[k] for k in numbers}}
        rows.append(row)
        print(json.dumps(row), flush=True)
        del ticks, world, warm, keys
    lower = {k: max(r["program"][k] for r in rows) for k in numbers}
    upper = {k: min(r["control"][k] for r in rows) for k in numbers}
    print(json.dumps({"workload": args.workload, "seeds": len(rows),
                      "lower": lower, "upper": upper,
                      "seconds": time.perf_counter() - PROCESS_START}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
