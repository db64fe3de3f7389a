"""Device self time per tick of the event kernel's ops, mean over the
cell's chips (profiler trace)."""

from benchmark.trace import mean


def read(run):
    tr = run["trace"]
    if not tr:
        return None
    k = mean(tr["chips"], "kernel_s")
    return k / len(run["ticks"]) * 1e3 if k > 0 else None
