"""Process start to the window's start: imports, chip init, compiles or
cache loads, the enter storm and the warm ticks (host clock)."""


def read(run):
    return run["setup_s"]
