"""The kernel's work count against a direct count of each entity's 3x3
neighbourhood occupants."""

import numpy as np

from benchmark import workcount


def test_neighbourhood_tests_direct():
    rng = np.random.default_rng(3)
    n = 400
    pos = (rng.random((n, 2)) * 1500).astype(np.float32)
    active = rng.random(n) < 0.9
    space = rng.integers(1, 3, n).astype(np.int32)
    cell = 300.0
    cx = np.floor(pos[:, 0] / cell)
    cz = np.floor(pos[:, 1] / cell)
    want = 0
    for i in np.flatnonzero(active):
        near = (active & (space == space[i]) & (abs(cx - cx[i]) <= 1)
                & (abs(cz - cz[i]) <= 1))
        want += int(near.sum()) - 1
    assert workcount.neighbourhood_tests(pos, active, space, cell) == want


def test_tick_work_and_bound():
    pos = np.array([[10, 10], [20, 20], [900, 900]], np.float32)
    act = np.array([True, True, True])
    spc = np.ones(3, np.int32)
    w = workcount.tick_work((pos, act, spc), (pos, act, spc), 300.0)
    assert w["tests"] == 4  # two neighbours each way, both epochs
    assert w["ops"] == 4 * workcount.OPS_PER_TEST
    assert w["bytes"] == 16 * 6 + 4 / 8
    peaks = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    s, bound = workcount.least_seconds(w, peaks, 1)
    assert bound == "memory" and s == w["bytes"] / 1e9
    assert workcount.least_seconds(w, peaks, 4)[0] == s / 4
