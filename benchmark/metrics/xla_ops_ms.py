"""Device self time per tick of every op that is neither the event
kernel nor a collective: table build and sort, feature scatter, drain
(profiler trace, mean over the cell's chips)."""

from benchmark.trace import mean


def read(run):
    tr = run["trace"]
    if not tr:
        return None
    return mean(tr["chips"], "other_s") / len(run["ticks"]) * 1e3
