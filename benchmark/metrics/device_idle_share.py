"""1 - (union of device op intervals / traced window), mean over the
cell's chips (profiler trace)."""

from benchmark.trace import mean


def read(run):
    tr = run["trace"]
    if not tr:
        return None
    return 100.0 * (1.0 - mean(tr["chips"], "busy_s") / tr["window_s"])
