"""Extra drain round trips per tick: launches of the program's drain
jits (sentinel ``jit_launches_total``, labels with "drain") over the
window's ticks."""


def read(run):
    return run["counters"]["drain_launches"] / len(run["ticks"])
