"""``seamless_250k`` against the strip engine's rules, read through
``params_from_config`` as the harness reads it, without building the
engine at full size."""

import json
import os

import pytest

from conftest import ROOT


@pytest.fixture(scope="module")
def cell():
    from goworld_tpu.config.read_config import AOIConfig
    from goworld_tpu.entity.aoi.batched import params_from_config

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "seamless_250k.json")) as f:
        cfg = json.load(f)
    aoi = AOIConfig(**cfg["aoi"])
    return cfg, aoi, params_from_config(aoi)


def test_grid_and_slots(cell):
    cfg, aoi, p = cell
    shards = aoi.mesh_shards
    assert shards == cfg["chips"] == 4 and aoi.shard_mode == "spatial"
    assert p.capacity == cfg["aoi"]["max_entities"]  # no rounding needed
    assert p.capacity % (8 * shards) == 0
    assert p.grid_x * p.cell_size >= cfg["world_extent"]
    assert (p.grid_x - 1) * p.cell_size < cfg["world_extent"]  # the least
    assert p.cell_size >= cfg["aoi_radius"]
    # Each chip's rows hold its strip's quarter with 20% to spare.
    assert p.capacity // shards >= 1.2 * cfg["entities"] / shards
    # The game's default window, kept whole on each chip.
    assert p.max_events == 65536


def test_strip_engine_rules(cell):
    from goworld_tpu.parallel.spatial import (
        MIN_STRIP_COLS,
        default_halo_cap,
        plan_strips,
        strip_cols_for,
    )

    cfg, aoi, p = cell
    shards = aoi.mesh_shards
    chunk = p.capacity // shards
    assert p.grid_x >= MIN_STRIP_COLS * shards
    assert default_halo_cap(p, shards) <= chunk
    cols = strip_cols_for(p.grid_x, shards, aoi.pallas_strip_cols or None)
    assert cols == 68
    # The density planner can place 4 capped strips over the grid.
    bounds = plan_strips([1] * p.grid_x, shards, max_cols=cols)
    assert bounds[0] == 0 and bounds[-1] == p.grid_x
    widths = bounds[1:] - bounds[:-1]
    assert widths.min() >= MIN_STRIP_COLS and widths.max() <= cols
