"""Share of window ticks the spatial engine ran on its all-gather
fallback program (its own ``total_fallbacks``)."""


def read(run):
    if run["chips"] < 2:
        return None
    return 100.0 * run["counters"]["fallbacks"] / len(run["ticks"])
