"""Active entities times ticks whose events reached the host inside the
window, over the window's seconds (host clock)."""


def read(run):
    close = run["window"]["close"]
    done = sum(t["active"] for t in run["ticks"] if t["t_out"] <= close)
    return done / run["window"]["seconds"]
