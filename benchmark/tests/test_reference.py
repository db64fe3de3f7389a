"""The reference against a brute-force all-pairs count, and the check's
multiset comparison."""

import numpy as np
import pytest

from benchmark import reference


def brute(pos, active, space, radius):
    n = len(active)
    keys = []
    for i in np.flatnonzero(active):
        for j in np.flatnonzero(active):
            if i == j or space[i] != space[j]:
                continue
            dx = np.float32(pos[j, 0]) - np.float32(pos[i, 0])
            dz = np.float32(pos[j, 1]) - np.float32(pos[i, 1])
            if dx * dx + dz * dz <= np.float32(radius[i]) * np.float32(radius[i]):
                keys.append(i * n + j)
    return np.sort(np.array(keys, np.int64))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_interest_keys_match_all_pairs(seed):
    rng = np.random.default_rng(seed)
    n = 300
    pos = (rng.random((n, 2)) * 600).astype(np.float32)
    active = rng.random(n) < 0.8
    space = rng.integers(1, 3, n).astype(np.int32)
    radius = rng.choice([50.0, 100.0], n).astype(np.float32)
    got = reference.interest_keys(pos, active, space, radius)
    assert np.array_equal(got, brute(pos, active, space, radius))


def test_events_and_mismatch():
    prev = np.array([1, 5, 9], np.int64)
    cur = np.array([1, 7, 9, 11], np.int64)
    enter, leave = reference.events(prev, cur)
    assert enter.tolist() == [7, 11] and leave.tolist() == [5]
    assert reference.mismatch(enter, enter) == (0, 0)
    assert reference.mismatch(np.array([7, 7, 11]), enter) == (0, 1)
    assert reference.mismatch(np.array([7, 12]), enter) == (1, 1)
    pairs = np.array([[2, 3], [0, 1]])
    assert reference.pair_keys(pairs, 10).tolist() == [1, 23]
