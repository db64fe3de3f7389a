"""``crowd_10k`` against the single-chip engine's rules, read through
``params_from_config`` as the harness reads it, without building the
engine at full size."""

import json
import os

import numpy as np
import pytest

from conftest import ROOT


@pytest.fixture(scope="module")
def cell():
    from goworld_tpu.config.read_config import AOIConfig
    from goworld_tpu.entity.aoi.batched import params_from_config

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "crowd_10k.json")) as f:
        cfg = json.load(f)
    return cfg, params_from_config(AOIConfig(**cfg["aoi"]))


def test_grid_and_slots(cell):
    cfg, p = cell
    assert cfg["chips"] == 1 and cfg["spaces"] == 1
    assert p.capacity == cfg["aoi"]["max_entities"]  # no rounding needed
    assert p.capacity >= 1.25 * cfg["entities"]
    assert p.grid_x * p.cell_size >= cfg["world_extent"]
    assert (p.grid_x - 1) * p.cell_size < cfg["world_extent"]  # the least
    assert p.cell_size >= cfg["aoi_radius"]
    # The game's default window: the crowd pages as a default game would.
    assert p.max_events == 65536


def test_deep_cells_kept(cell):
    from goworld_tpu.ops.neighbor import LANES, pallas_cell_capacity_ok

    cfg, p = cell
    assert p.cell_capacity == 512  # not clamped to one lane tile
    assert pallas_cell_capacity_ok(p.cell_capacity)
    assert p.lane_tiles == 4 and p.cell_lanes == 4 * LANES
    # The drain's flat event index, capacity * 9 * T * LANES, in int32.
    assert p.capacity * 9 * p.cell_lanes < 2**31


def test_walk_takes_the_single_pass_path(cell):
    """The single-pass guard's slack, (cell_size - radius) / 2, exceeds
    the walk step's largest move, so every walk tick takes the dual
    launch."""
    from benchmark.world import load_traffic

    cfg, p = cell
    step = load_traffic("walk")["step"]
    assert (p.cell_size - cfg["aoi_radius"]) / 2 > step * np.sqrt(2)


@pytest.mark.parametrize("seed", [3, 2**31 + 7, 4_000_000_017])
def test_storm_cells_fit(cell, seed):
    """No cell of a seeded world, the storm's, holds more than
    cell_capacity: nobody is dropped."""
    from benchmark.world import World, load_traffic

    cfg, p = cell
    ep = World(cfg, load_traffic("walk"), p.capacity, seed).epoch()
    pos = ep.pos[ep.active]
    cx = np.floor(pos[:, 0] / p.cell_size).astype(int) % p.grid_x
    cz = np.floor(pos[:, 1] / p.cell_size).astype(int) % p.grid_z
    pops = np.bincount(cz * p.grid_x + cx)
    assert len(pos) == cfg["entities"]
    assert 128 < pops.max() <= p.cell_capacity
