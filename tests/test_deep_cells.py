"""Grid cells deeper than one 128-lane tile, and the single-chip pager.

A small crowd (2,000 entities on a 400 x 400 world, cells of 100, grid
4) puts ~125 entities in a cell and up to ~165 in the fullest one, past
one lane tile. The engine at ``cell_capacity`` 256 and 512 (2 and 4
tiles a cell) must hide no one and give exactly the plain reference's
enter and leave pairs over the enter storm and walk ticks, each of which
pages past a small inline window. At populations one tile holds, a deep
engine gives the one-tile engine's pairs in the same order. Paging cuts
each page on the host, so pages of any length run one program.
"""

import jax
import numpy as np
import pytest

from benchmark import reference
from goworld_tpu.ops.neighbor import (
    LANES,
    NeighborEngine,
    NeighborParams,
    pallas_cell_capacity_ok,
)
from goworld_tpu.telemetry.metrics import REGISTRY

WORLD = 400.0
MAX_EVENTS = 4096  # the walk ticks' ~36k events a side page 8 times


def crowd_params(cell_capacity, max_events=MAX_EVENTS):
    return NeighborParams(capacity=2048, cell_size=100.0, grid_x=4,
                          grid_z=4, space_slots=1,
                          cell_capacity=cell_capacity,
                          max_events=max_events)


def crowd(n_active, seed):
    rng = np.random.default_rng(seed)
    n = 2048
    pos = (rng.random((n, 2), np.float32) * np.float32(WORLD))
    active = np.zeros(n, bool)
    active[:n_active] = True
    return (rng, pos, active, np.ones(n, np.int32),
            np.full(n, 100.0, np.float32))


def walk(rng, pos):
    """The reference bot's ±10 sync step, kept inside the world."""
    step = rng.uniform(-10, 10, pos.shape).astype(np.float32)
    return np.clip(pos + step, 0, np.float32(WORLD * (1 - 1e-6)))


def cell_populations(p, pos, active):
    cx = np.floor(pos[active, 0] / p.cell_size).astype(int) % p.grid_x
    cz = np.floor(pos[active, 1] / p.cell_size).astype(int) % p.grid_z
    return np.bincount(cz * p.grid_x + cx, minlength=p.grid_x * p.grid_z)


def drive(engine, n_active, seed, ticks):
    """Storm then walk ticks; yields (enters, leaves, dropped, want_e,
    want_l, pos, active) per tick, the wants from the plain reference."""
    rng, pos, active, space, radius = crowd(n_active, seed)
    prev = reference.interest_keys(pos, np.zeros_like(active), space,
                                   radius)
    engine.reset()
    for t in range(ticks):
        if t:
            pos = walk(rng, pos)
        enters, leaves, dropped = engine.step(pos, active, space, radius)
        cur = reference.interest_keys(pos, active, space, radius)
        want_e, want_l = reference.events(prev, cur)
        prev = cur
        yield enters, leaves, dropped, want_e, want_l, pos, active


@pytest.mark.parametrize("backend", ["pallas_interpret", "jnp"])
@pytest.mark.parametrize("cell_capacity", [256, 512])
def test_deep_cells_match_reference(backend, cell_capacity):
    p = crowd_params(cell_capacity)
    assert p.lane_tiles == cell_capacity // LANES
    engine = NeighborEngine(p, backend=backend)
    paged = REGISTRY.family("aoi_paged_events_total")
    before = {k: c.value for k, c in paged.children()}
    fullest = 0
    for t, (enters, leaves, dropped, want_e, want_l, pos, active) in (
            enumerate(drive(engine, 2000, 7, 4))):
        pops = cell_populations(p, pos, active)
        fullest = max(fullest, int(pops.max()))
        assert dropped == 0, f"tick {t}"
        assert len(want_e) > MAX_EVENTS, "the tick must page"
        for got, want in ((enters, want_e), (leaves, want_l)):
            assert reference.mismatch(
                reference.pair_keys(got, p.capacity), want) == (0, 0), t
        # The gauge carries this tick's fullest cell, off the header.
        occ = REGISTRY.family("aoi_cell_occupancy_max").children()[0][1]
        assert occ.value == pops.max()
    assert fullest > LANES, "no cell needed a second lane tile"
    after = {k: c.value for k, c in paged.children()}
    grew = {k[0]: after[k] - before.get(k, 0.0) for k in after}
    assert grew["enter"] > 0 and grew["leave"] > 0


def test_one_tile_populations_same_pairs_at_any_depth():
    """Every cell within one tile: 1 and 4 tiles a cell give the same
    pairs in the same order, and the reference's sets."""
    one = NeighborEngine(crowd_params(128), backend="pallas_interpret")
    deep = NeighborEngine(crowd_params(512), backend="pallas_interpret")
    for a, b in zip(drive(one, 1400, 11, 3), drive(deep, 1400, 11, 3)):
        pops = cell_populations(one.params, a[5], a[6])
        assert pops.max() <= LANES
        assert a[2] == b[2] == 0
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_array_equal(a[1], b[1])
        assert reference.mismatch(
            reference.pair_keys(a[0], 2048), a[3]) == (0, 0)


def test_deep_cell_drops_past_capacity():
    """A deep cell still drops, and counts, what it cannot hold."""
    p = NeighborParams(capacity=512, cell_size=100.0, grid_x=4, grid_z=4,
                       space_slots=1, cell_capacity=256, max_events=65536)
    engine = NeighborEngine(p, backend="pallas_interpret")
    engine.reset()
    pos = np.full((512, 2), 50.0, np.float32)
    pos[:, 0] += np.linspace(0, 40, 512, dtype=np.float32)
    _, _, dropped = engine.step(pos, np.ones(512, bool),
                                np.ones(512, np.int32),
                                np.full(512, 100.0, np.float32))
    assert dropped == 512 - 256
    occ = REGISTRY.family("aoi_cell_occupancy_max").children()[0][1]
    assert occ.value == 512


@pytest.mark.parametrize("cell_capacity, ok", [
    (1, True), (128, True), (256, True), (384, True), (512, True),
    (129, False), (200, False), (640, False),
])
def test_pallas_cell_capacity_rule(cell_capacity, ok):
    assert pallas_cell_capacity_ok(cell_capacity) == ok
    p = crowd_params(cell_capacity)
    if ok:
        NeighborEngine(p, backend="pallas_interpret")
    else:
        with pytest.raises(ValueError, match="cell_capacity"):
            NeighborEngine(p, backend="pallas_interpret")


class BackendCompiles:
    """Backend compiles while installed (jax.monitoring's duration
    events, as the benchmark's compile log counts them)."""

    def __init__(self):
        self.n = 0

    def _on(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)


@pytest.mark.parametrize("backend, seed", [("pallas_interpret", 5),
                                           ("jnp", 6)])
def test_pages_compile_nothing_new(backend, seed):
    """Ticks whose last pages hold different remainders run the drain
    program they already compiled and nothing else. Each backend has a
    world of its own, so neither finds the other's page lengths."""
    p = crowd_params(256, max_events=1000)
    engine = NeighborEngine(p, backend=backend)
    ticks = drive(engine, 1200, seed, 7)
    for _ in range(3):  # storm and two walk ticks compile what pages run
        next(ticks)
    remainders = set()
    with BackendCompiles() as compiles:
        for enters, leaves, dropped, want_e, want_l, _, _ in ticks:
            assert len(enters) > 2 * p.max_events
            remainders |= {len(enters) % p.max_events,
                           len(leaves) % p.max_events}
            assert reference.mismatch(
                reference.pair_keys(enters, p.capacity), want_e) == (0, 0)
            assert reference.mismatch(
                reference.pair_keys(leaves, p.capacity), want_l) == (0, 0)
    assert len(remainders) > 2
    assert compiles.n == 0
