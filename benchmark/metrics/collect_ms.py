"""Mean host time per tick inside ``collect`` (the benchmark's span
around the call): the wait for the device, the readback and any paging."""


def read(run):
    t = run["ticks"]
    return sum(x["collect_s"] for x in t) / len(t) * 1e3
