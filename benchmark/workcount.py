"""The work the event kernel's job needs per tick, from shapes and occupancy.

The job: each active entity is tested against every occupant of its 3×3
cell neighbourhood (cells of the configuration's ``cell_size``, same
space) in the current epoch, and again in the previous epoch; both
epochs' features (x, z, space, radius: 4 × 4 bytes an entity) are read
once; one mask bit per test is written (the enter mask over the current
epoch's tests, the leave mask over the previous epoch's).

    tests = Σ_epochs Σ_{active i} (occupants of i's 3×3 block − 1)
    ops   = OPS_PER_TEST · tests     (dx, dz, dx², dz², add, compare)
    bytes = 16 · (active_t + active_{t−1}) + tests / 8

Nothing here depends on how a kernel tiles the grid: a later kernel that
does the same job reads against the same count.
"""

from __future__ import annotations

import numpy as np

OPS_PER_TEST = 6
FEATURE_BYTES = 16


def neighbourhood_tests(pos, active, space, cell_size: float) -> int:
    """Σ over active entities of the other occupants of their 3×3 block."""
    idx = np.flatnonzero(active)
    if len(idx) == 0:
        return 0
    _, s = np.unique(np.asarray(space)[idx], return_inverse=True)
    cx = np.floor(np.asarray(pos)[idx, 0] / cell_size).astype(np.int64)
    cz = np.floor(np.asarray(pos)[idx, 1] / cell_size).astype(np.int64)
    cx -= cx.min() - 1
    cz -= cz.min() - 1
    occ = np.zeros((int(s.max()) + 1, int(cz.max()) + 2, int(cx.max()) + 2),
                   np.int64)
    np.add.at(occ, (s, cz, cx), 1)
    block = sum(np.roll(np.roll(occ, a, 1), b, 2)
                for a in (-1, 0, 1) for b in (-1, 0, 1))
    return int((block[s, cz, cx] - 1).sum())


def tick_work(prev, cur, cell_size: float) -> dict:
    """Tests, operations and bytes of one tick; ``prev``/``cur`` are
    (pos, active, space) of the two epochs."""
    tests = (neighbourhood_tests(*cur, cell_size)
             + neighbourhood_tests(*prev, cell_size))
    n_feat = int(np.count_nonzero(cur[1]) + np.count_nonzero(prev[1]))
    return {"tests": tests, "ops": OPS_PER_TEST * tests,
            "bytes": FEATURE_BYTES * n_feat + tests / 8}


def least_seconds(work: dict, peaks: dict, chips: int) -> tuple[float, str]:
    """The least time ``chips`` chips could take for ``work``, and which
    peak bounds it ("compute" or "memory")."""
    t_ops = work["ops"] / (peaks["flops_per_s"] * chips)
    t_mem = work["bytes"] / (peaks["hbm_bytes_per_s"] * chips)
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
