"""The spatial engine's inline event window is per chip.

A small copy of the benchmark's ``seamless_250k`` deployment (the same
density, cell, 25% slot slack and 4 strips; 6,400 entities on an 8 x 8
block of test_game squares) takes one walk tick: every entity steps
±10 per axis. That tick puts more than ``max_events / 4`` events a side
on a chip but fewer than ``max_events``, so a window divided over the
chips would page, and the per-chip window drains it inline with no drain
launch. The events must equal the plain reference's."""

import dataclasses

import numpy as np
import pytest

from benchmark import reference
from benchmark.world import World
from goworld_tpu.config.read_config import AOIConfig
from goworld_tpu.entity.aoi.batched import params_from_config
from goworld_tpu.parallel import make_mesh
from goworld_tpu.parallel.spatial import SpatialShardedNeighborEngine

# seamless_250k at 6,400 entities: 1.5625e-4 a square unit, cell 300,
# max_entities 25% over the population (a multiple of 8 x 4), the least
# grid covering the world, 4 strips.
CONFIG = {
    "aoi": {"backend": "tpu", "max_entities": 8000, "grid": 22,
            "cell_size": 300.0, "space_slots": 1, "cell_capacity": 64,
            "mesh_shards": 4, "shard_mode": "spatial"},
    "entities": 6400, "spaces": 1, "aoi_radius": 100.0,
    "world_extent": 6400.0,
}
WALK = {"move_share": 1.0, "step": 10.0, "despawn_share": 0.0,
        "free_ticks": 2, "teleport_share": 0.0}
# ~0.32 events an entity a side per walk tick (the 1-chip walk cell),
# ~520 a chip: between MAX_EVENTS / 4 and MAX_EVENTS.
MAX_EVENTS = 1024
SEED = 2**31 + 25


@pytest.mark.parametrize("backend", ["pallas_interpret", "jnp"])
def test_walk_tick_drains_inline_per_chip(backend, drain_launches):
    aoi = AOIConfig(**CONFIG["aoi"])
    params = dataclasses.replace(params_from_config(aoi),
                                 max_events=MAX_EVENTS)
    assert params.capacity == 8000 and params.grid_x * 300 >= 6400
    engine = SpatialShardedNeighborEngine(
        params, make_mesh(4), backend=backend, prewarm_fallback=False)
    assert engine.events_inline == MAX_EVENTS
    if backend != "jnp":
        assert engine.drain_inline == MAX_EVENTS  # in-kernel drain armed
    engine.reset()
    world = World(CONFIG, WALK, params.capacity, SEED)
    before = world.epoch()
    engine.step_async(*before.arrays(), meta_dirty=True).collect()  # storm
    tick = world.advance()
    pages0 = drain_launches()
    enters, leaves, dropped = engine.step_async(
        *tick.arrays(), meta_dirty=tick.meta_dirty).collect()
    assert drain_launches() == pages0, "a chip's window paged"
    assert engine.last_mode == "spatial" and dropped == 0

    want_e, want_l = reference.events(
        reference.interest_keys(*before.arrays()),
        reference.interest_keys(*tick.arrays()))
    got_e = reference.pair_keys(enters, params.capacity)
    got_l = reference.pair_keys(leaves, params.capacity)
    assert np.array_equal(got_e, want_e) and np.array_equal(got_l, want_l)
    # Events a side on each chip (a pair is emitted by its watcher's
    # strip): past a divided window, inside the per-chip one.
    for pairs in (enters, leaves):
        per_chip = np.bincount(engine.assign[pairs[:, 0]], minlength=4)
        assert per_chip.max() < MAX_EVENTS
        assert per_chip.max() > MAX_EVENTS // 4
