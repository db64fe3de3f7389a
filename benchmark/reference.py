"""The plain reference: AOI interest pairs and enter/leave events in numpy.

Written from the semantics alone (ROADMAP: brute-force pairwise
interest), sharing no code with the program:

    valid_t(i, j) = active_t(i) ∧ active_t(j) ∧ space_t(i) = space_t(j)
                    ∧ |pos_t(j) − pos_t(i)|² ≤ radius_t(i)² ∧ i ≠ j
    enter(t) = valid_t ∧ ¬valid_{t−1}      leave(t) = valid_{t−1} ∧ ¬valid_t

Distances are raw world distances (no wrap). The square distance is
computed as ``dx*dx + dz*dz`` with ``dx = x_j − x_i`` in float32, one
rounding per operation, which is what the configuration's float32
positions state. Candidates come from a bucket grid of side max radius
over each space, so only the 3×3 neighbouring buckets are tested; every
pair within the radius lies there.

A pair (i, j) is encoded as the int64 key ``i * capacity + j``; the
program's ``[k, 2]`` pair arrays are encoded the same way for comparison.
"""

from __future__ import annotations

import numpy as np


def pair_keys(pairs: np.ndarray, capacity: int) -> np.ndarray:
    """Sorted int64 keys of an ``[k, 2]`` array of (watcher, other) slots."""
    p = np.asarray(pairs, np.int64).reshape(-1, 2)
    return np.sort(p[:, 0] * capacity + p[:, 1])


def interest_keys(pos, active, space, radius, dtype=np.float32) -> np.ndarray:
    """Sorted keys of every valid (i, j) pair of one epoch. ``dtype`` is
    the precision positions, radii and the distance test are held in."""
    cap = len(active)
    idx = np.flatnonzero(active)
    if len(idx) == 0:
        return np.empty(0, np.int64)
    p = np.asarray(pos)[idx].astype(dtype)
    r = np.asarray(radius)[idx].astype(dtype)
    s = np.asarray(space)[idx].astype(np.int64)
    side = float(np.asarray(radius)[idx].max())
    bx = np.floor(np.asarray(pos)[idx, 0] / side).astype(np.int64) + 1
    bz = np.floor(np.asarray(pos)[idx, 1] / side).astype(np.int64) + 1
    w = int(bx.max()) + 2
    h = int(bz.max()) + 2
    key = (s * h + bz) * w + bx
    order = np.argsort(key, kind="stable")
    skey = key[order]
    out = []
    for dz in (-1, 0, 1):
        row = (s * h + bz + dz) * w + bx
        lo = np.searchsorted(skey, row - 1, side="left")
        hi = np.searchsorted(skey, row + 1, side="right")
        cnt = hi - lo
        q = np.repeat(np.arange(len(idx)), cnt)
        first = np.repeat(lo - np.concatenate([[0], np.cumsum(cnt)[:-1]]), cnt)
        c = order[np.arange(len(q)) + first]
        keep = c != q
        q, c = q[keep], c[keep]
        dx = p[c, 0] - p[q, 0]
        dzz = p[c, 1] - p[q, 1]
        d2 = dx * dx + dzz * dzz
        ok = (s[q] == s[c]) & (d2 <= r[q] * r[q])
        out.append(idx[q[ok]].astype(np.int64) * cap + idx[c[ok]])
    return np.sort(np.concatenate(out))


def events(prev_keys: np.ndarray, cur_keys: np.ndarray):
    """(enter keys, leave keys), each sorted."""
    return (np.setdiff1d(cur_keys, prev_keys, assume_unique=True),
            np.setdiff1d(prev_keys, cur_keys, assume_unique=True))


def mismatch(got: np.ndarray, want: np.ndarray) -> tuple[int, int]:
    """(missing, extra) between sorted key arrays, as multisets: a pair
    the program returned twice counts once as extra."""
    if len(got) == len(want) and np.array_equal(got, want):
        return 0, 0
    u, n = np.unique(got, return_counts=True)
    extra = int((n - 1).sum()) + len(np.setdiff1d(u, want, assume_unique=True))
    missing = len(np.setdiff1d(want, u, assume_unique=True))
    return missing, extra
