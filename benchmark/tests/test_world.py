"""The traffic generator: fixed sizes per tick, every entity in the
world's spaces and inside the world."""

import numpy as np

from benchmark.world import World, load_traffic
from conftest import CHURN

CONFIG = {"entities": 800, "spaces": 2, "aoi_radius": 100.0,
          "world_extent": 2400.0}


def test_churn_keeps_sizes_and_spaces():
    w = World(CONFIG, CHURN, 1000, 2**31 + 7)
    prev = w.epoch()
    for _ in range(30):  # long enough to use up the never-used slots
        ep = w.advance()
        assert ep.meta_dirty
        assert np.count_nonzero(ep.active) == 800
        assert set(np.unique(ep.space[ep.active])) == {1, 2}
        assert (ep.pos >= 0).all() and (ep.pos < 2400).all()
        born = ep.active & ~prev.active
        assert np.count_nonzero(born) == 8  # 1% of 800
        prev = ep


def test_walk_moves_everyone_and_keeps_meta():
    w = World(CONFIG, load_traffic("walk"), 1000, 3)
    prev = w.epoch()
    ep = w.advance()
    assert not ep.meta_dirty
    moved = np.any(ep.pos != prev.pos, axis=1)
    assert not moved[~prev.active].any()
    assert np.count_nonzero(moved) >= 790  # a clipped step may not move
    assert np.abs(ep.pos - prev.pos).max() <= 10.0
