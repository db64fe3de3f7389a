"""Device self time per tick of collective ops (the strip halo
``ppermute`` and the guard's reductions), mean over the cell's chips
(profiler trace)."""

from benchmark.trace import mean


def read(run):
    tr = run["trace"]
    if not tr or run["chips"] < 2:
        return None
    return mean(tr["chips"], "collective_s") / len(run["ticks"]) * 1e3
