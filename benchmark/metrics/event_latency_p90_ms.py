"""90th percentile over every window tick of the time from its inputs
entering ``step_async`` to its last enter/leave pair on the host, paging
included (host clock)."""

import numpy as np


def read(run):
    return float(np.percentile([t["latency_s"] for t in run["ticks"]],
                               90)) * 1e3
