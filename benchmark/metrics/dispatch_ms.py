"""Mean host time per tick inside ``step_async`` (the benchmark's span
around the call)."""


def read(run):
    t = run["ticks"]
    return sum(x["dispatch_s"] for x in t) / len(t) * 1e3
