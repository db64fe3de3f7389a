"""Time per tick, as the median over the window of blocks of consecutive
dispatches that each span 250 ms or more (host clock): the tick period
without the host's rare pauses of up to seconds, which
``entity_updates_per_s`` takes in over its whole window, as it should."""

import statistics

BLOCK_S = 0.25  # a host-clock reading spans at least this


def read(run):
    t = [x["t_in"] for x in run["ticks"]]
    per, i = [], 0
    for j in range(1, len(t)):
        if t[j] - t[i] >= BLOCK_S:
            per.append((t[j] - t[i]) / (j - i))
            i = j
    return statistics.median(per) * 1e3 if per else None
