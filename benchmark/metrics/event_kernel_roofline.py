"""Least time the chips could take for the kernel's job (benchmark/
workcount.py, at the v5e peaks of benchmark/peaks.json) over the event
kernel's measured device time, both summed over the traced ticks."""

import sys

from benchmark.trace import mean
from benchmark.workcount import least_seconds


def read(run):
    tr = run["trace"]
    if not tr:
        return None
    kernel_s = mean(tr["chips"], "kernel_s")
    if kernel_s <= 0:
        return None
    least = 0.0
    bounds = set()
    for w in run["work"]:
        s, bound = least_seconds(w, run["peaks"], run["chips"])
        least += s
        bounds.add(bound)
    print(f"event_kernel_roofline: bound by {'/'.join(sorted(bounds))}; "
          f"least {least:.6f} s over {kernel_s:.6f} s of kernel time",
          file=sys.stderr)
    return 100.0 * least / kernel_s
