"""Spatially sharded AOI (grid-strip halo exchange) must agree EXACTLY
with the single-device engine — including entities straddling and crossing
strip seams, migrations with hysteresis, density re-plans mid-run, event
storms past a chip's inline budget, cell-capacity drops at seam
cells, and the exact all-gather fallback ticks (teleports, halo overflow,
strip overflow)."""

import jax
import numpy as np
import pytest

from goworld_tpu.ops import NeighborEngine, NeighborParams
from goworld_tpu.parallel import make_mesh
from goworld_tpu.parallel.spatial import (
    SpatialShardedNeighborEngine,
    plan_strips,
)

# One params object shared by most tests: engines jit per (params, mesh,
# ...) via lru_cache, so sharing keeps the module's compile count low.
PARAMS = NeighborParams(
    capacity=512, cell_size=100.0, grid_x=64, grid_z=16,
    space_slots=4, cell_capacity=64, max_events=8192,
)
N = 512
WORLD_X = 6400.0  # grid_x * cell_size — every column distinct (no folding)


def make_engines(params=PARAMS, **kw):
    mesh = make_mesh(8)
    single = NeighborEngine(params, backend="jnp")
    kw.setdefault("prewarm_fallback", False)  # no daemon churn in tests
    spatial = SpatialShardedNeighborEngine(params, mesh, **kw)
    single.reset()
    spatial.reset()
    return single, spatial


def make_world(n_active, seed, world=WORLD_X, n_spaces=3):
    rng = np.random.default_rng(seed)
    pos = rng.uniform(0, world, size=(N, 2)).astype(np.float32)
    pos[:, 1] %= 1600.0
    active = np.zeros(N, bool)
    active[:n_active] = True
    space = rng.integers(0, n_spaces, size=N).astype(np.int32)
    radius = np.full(N, 100.0, np.float32)
    return rng, pos, active, space, radius


def to_sets(pairs, n=N):
    out = [set() for _ in range(n)]
    for a, b in pairs:
        out[int(a)].add(int(b))
    return out


def assert_tick_parity(single, spatial, pos, active, space, radius, tag=""):
    e1, l1, d1 = single.step(pos, active, space, radius)
    e2, l2, d2 = spatial.step(pos, active, space, radius)
    n = single.params.capacity
    assert to_sets(e1, n) == to_sets(e2, n), f"enters differ {tag}"
    assert to_sets(l1, n) == to_sets(l2, n), f"leaves differ {tag}"
    assert d1 == d2, f"dropped differ {tag}"
    return e1, l1


def test_randomized_parity_with_migrations_and_replans():
    """The headline oracle: random walk (seam straddlers AND crossers —
    64 columns over 8 shards put every 8th column at a seam) with spawn/
    despawn churn, density re-plans every 3 dispatches, and nonempty
    enter+leave sets in the same tick. Every tick must run the SPATIAL
    program (no fallback) and match the single-device stream exactly."""
    single, spatial = make_engines(replan_interval=3)
    rng, pos, active, space, radius = make_world(400, seed=7)
    saw_both = 0
    for tick in range(8):
        e1, l1 = assert_tick_parity(
            single, spatial, pos, active, space, radius, f"@ tick {tick}"
        )
        assert spatial.last_mode == "spatial", spatial.last_mode
        if tick and len(e1) and len(l1):
            saw_both += 1
        pos = np.clip(
            pos + rng.normal(0, 20, pos.shape), 0, WORLD_X
        ).astype(np.float32)
        # Churn: ~12 spawns/despawns per tick keeps meta dirty.
        active = active.copy()
        active[rng.integers(0, N, 12)] ^= True
    assert saw_both >= 4, "walk produced too few enter+leave ticks"
    assert spatial.total_migrations > 0, "no seam crossings exercised"
    assert spatial.total_fallbacks == 0


def test_seam_straddle_and_cross_exact():
    """Deterministic seam drill: two entities on opposite sides of a strip
    seam drift across it (through the hysteresis band) while staying AOI
    neighbors; a third pair enters and leaves radius in the same tick
    window. Events must match the single-device engine pair-for-pair."""
    single, spatial = make_engines()
    pos = np.zeros((N, 2), np.float32)
    active = np.zeros(N, bool)
    space = np.zeros(N, np.int32)
    radius = np.full(N, 100.0, np.float32)
    # Strip seam for 64 cols / 8 shards sits at x=800 (column 8). The
    # space-hash offset shifts columns identically in both engines and
    # is constant per space, so absolute world x is fine.
    active[:4] = True
    pos[0] = (795.0, 50.0)  # shard A side of the 800-seam
    pos[1] = (805.0, 50.0)  # shard B side — cross-seam AOI pair
    pos[2] = (2000.0, 50.0)
    pos[3] = (2250.0, 50.0)  # out of radius of 2
    for tick in range(6):
        assert_tick_parity(
            single, spatial, pos, active, space, radius, f"@ drill {tick}"
        )
        assert spatial.last_mode == "spatial"
        pos = pos.copy()
        pos[0, 0] += 60.0  # 0 marches across the seam and far past it
        pos[1, 0] -= 30.0  # 1 crosses the other way
        # 2↔3 oscillate in/out of radius: enter+leave in one tick window.
        pos[3, 0] = 2250.0 - (tick % 2) * 200.0
    assert spatial.total_migrations > 0


def test_event_storm_pages_chunked_drain(drain_launches):
    """First-tick enter storm past a chip's inline budget (max_events on
    each chip, 64 here) must page through the chunked drain with
    exactly-once pairs."""
    p = NeighborParams(
        capacity=512, cell_size=100.0, grid_x=32, grid_z=16,
        space_slots=4, cell_capacity=64, max_events=64,
    )
    single, spatial = make_engines(p)
    assert spatial.events_inline == p.max_events
    rng, pos, active, space, radius = make_world(400, seed=11, world=1200.0)
    e1, l1, _ = single.step(pos, active, space, radius)
    pages0 = drain_launches()
    e2, l2, _ = spatial.step(pos, active, space, radius)
    assert drain_launches() > pages0  # a chip's window really overflows
    assert to_sets(e1) == to_sets(e2)
    assert len(e1) == len(e2)  # exactly-once across chunks


def test_seam_cell_drop_consistency():
    """A grid cell over cell_capacity near a seam exists as COPIES on two
    shards; the slot-id tie-break must drop the same members everywhere —
    and the same members as the single-device engine."""
    p = NeighborParams(
        capacity=512, cell_size=100.0, grid_x=64, grid_z=16,
        space_slots=4, cell_capacity=8, max_events=8192,
    )
    single, spatial = make_engines(p, replan_interval=2)
    rng = np.random.default_rng(5)
    pos = rng.uniform(0, 6400, (N, 2)).astype(np.float32)
    pos[:, 1] %= 1600.0
    # 24 entities into one cell (capacity 8) ON a seam column. Only 420
    # active so the strips keep row slack and the SPATIAL path runs.
    pos[:24] = (805.0, 405.0)
    active = np.zeros(N, bool)
    active[:420] = True
    space = np.zeros(N, np.int32)
    radius = np.full(N, 100.0, np.float32)
    for tick in range(3):
        e1, l1, d1 = single.step(pos, active, space, radius)
        e2, l2, d2 = spatial.step(pos, active, space, radius)
        assert d1 == d2 and d1 > 0
        assert spatial.last_mode == "spatial", spatial.last_mode
        assert to_sets(e1) == to_sets(e2), f"drop enters differ @ {tick}"
        assert to_sets(l1) == to_sets(l2), f"drop leaves differ @ {tick}"
        pos = np.clip(
            pos + rng.normal(0, 10, pos.shape), 0, 6400
        ).astype(np.float32)
        pos[:, 1] %= 1600.0


def test_teleport_falls_back_exactly():
    """A mass teleport breaks the strip locality invariant (previous cell
    outside the halo): that tick must run the exact all-gather program —
    and still match the single-device stream (row→slot mapped)."""
    single, spatial = make_engines()
    rng, pos, active, space, radius = make_world(400, seed=3)
    for tick in range(5):
        assert_tick_parity(
            single, spatial, pos, active, space, radius, f"@ tp {tick}"
        )
        if tick in (1, 3):
            pos = rng.uniform(0, WORLD_X, (N, 2)).astype(np.float32)
            pos[:, 1] %= 1600.0
        else:
            pos = np.clip(
                pos + rng.normal(0, 5, pos.shape), 0, WORLD_X
            ).astype(np.float32)
    assert spatial.total_fallbacks >= 2
    assert "fallback" in spatial.last_mode or spatial.total_fallbacks


def test_hot_column_overflow_falls_back():
    """Everyone in ONE column: no strip split can hold them in one shard's
    row budget, so every tick falls back (reason=strip_overflow) — and the
    event stream stays exact (the hotspot-crowd worst case)."""
    single, spatial = make_engines()
    rng = np.random.default_rng(9)
    pos = np.zeros((N, 2), np.float32)
    pos[:, 0] = 850.0
    pos[:, 1] = rng.uniform(0, 1600.0, N).astype(np.float32)
    active = np.ones(N, bool)
    space = np.zeros(N, np.int32)
    radius = np.full(N, 100.0, np.float32)
    for tick in range(2):
        assert_tick_parity(
            single, spatial, pos, active, space, radius, f"@ hot {tick}"
        )
        assert spatial.last_mode == "fallback:strip_overflow"
        pos = pos.copy()
        pos[:, 1] = (pos[:, 1] + rng.normal(0, 10, N)) % 1600.0
    assert spatial.total_fallbacks == 2


def test_halo_overflow_falls_back():
    """A tiny halo budget + a crowd parked ON a seam overflows the band
    buffer: the tick falls back (reason=halo_overflow), stays exact, and
    recovers to the spatial path once the crowd disperses."""
    single, spatial = make_engines(halo_cap=24)
    rng, pos, active, space, radius = make_world(260, seed=13)
    # 30 rows parked in one seam band: past halo_cap 24 together with the
    # background (~12/side), but small enough that the strip's row budget
    # still holds (no strip_overflow masking it) — and 24 is enough for
    # the background alone, so the engine RECOVERS after dispersal.
    # One space for the crowd: the per-space hash offset would otherwise
    # scatter them over distinct columns and dilute the band.
    pos[:30, 0] = 801.0
    space[:30] = 0
    for tick in range(3):
        assert_tick_parity(
            single, spatial, pos, active, space, radius, f"@ halo {tick}"
        )
        if tick == 0:
            assert spatial.last_mode == "fallback:halo_overflow"
            # Disperse far from any seam band.
            pos = rng.uniform(0, WORLD_X, (N, 2)).astype(np.float32)
            pos[:, 1] %= 1600.0
            # (The teleport guard will keep the NEXT tick on the fallback
            # path too; the one after runs spatial again.)
    assert spatial.last_mode == "spatial", spatial.last_mode


def test_density_replan_rebalances_mid_run():
    """Skewed density (80% of entities in the left quarter of the torus)
    must produce a non-uniform equal-population split at the replan
    cadence, keep parity through the boundary move, and reduce the worst
    shard load vs the uniform split."""
    single, spatial = make_engines(replan_interval=2)
    rng = np.random.default_rng(21)
    pos = np.empty((N, 2), np.float32)
    k = int(N * 0.7)
    pos[:k, 0] = rng.uniform(0, WORLD_X / 2, k)
    pos[k:, 0] = rng.uniform(WORLD_X / 2, WORLD_X, N - k)
    pos[:, 1] = rng.uniform(0, 1600.0, N)
    active = np.ones(N, bool)
    active[320:] = False
    space = np.zeros(N, np.int32)
    radius = np.full(N, 100.0, np.float32)
    uniform_worst = None
    for tick in range(6):
        assert_tick_parity(
            single, spatial, pos, active, space, radius, f"@ replan {tick}"
        )
        if tick == 0:
            uniform_worst = spatial.shard_population.max()
        pos = np.clip(
            pos + rng.normal(0, 8, pos.shape), 0, WORLD_X
        ).astype(np.float32)
        pos[:, 1] %= 1600.0
    assert spatial.total_replans >= 1, "skew never triggered a re-plan"
    assert spatial.shard_population.max() <= uniform_worst
    widths = np.diff(spatial.boundaries)
    assert widths.max() > widths.min(), "split stayed uniform despite skew"
    assert spatial.total_fallbacks == 0


def test_seam_free_fast_path_parity_and_flag():
    """ISSUE 15 tentpole (b) on the jnp tier: radius 40 with ~4-unit
    drift keeps the replicated seam-free guard TRUE — the leave diff
    rides the CURRENT grid in one combined pass — while parity with the
    single-device engine must hold exactly. The engine reports the guard
    via last_fast_tick / aoi_spatial_fast_ticks_total; a despawn tick
    must break the guard (and the flag) without breaking parity."""
    from goworld_tpu import telemetry

    single, spatial = make_engines()
    rng, pos, active, space, radius = make_world(420, seed=29)
    radius = np.full(N, 40.0, np.float32)
    fast0 = telemetry.counter("aoi_spatial_fast_ticks_total").value
    spatial.step(pos, active, space, radius)  # enter storm
    single.step(pos, active, space, radius)
    saw_leaves = 0
    for tick in range(4):
        pos = pos + rng.normal(0, 3, pos.shape).astype(np.float32)
        np.clip(pos[:, 0], 0, WORLD_X, out=pos[:, 0])
        np.clip(pos[:, 1], 1.0, 1599.0, out=pos[:, 1])
        pos = pos.astype(np.float32)
        e1, l1 = assert_tick_parity(
            single, spatial, pos, active, space, radius, f"@ fast {tick}"
        )
        assert spatial.last_fast_tick, f"guard broke @ tick {tick}"
        saw_leaves += len(l1)
    assert saw_leaves > 0, "fast-path trace produced no leaves"
    assert telemetry.counter("aoi_spatial_fast_ticks_total").value >= (
        fast0 + 4
    )
    # A despawn makes the single-pass ineligible: the guard must drop it
    # back to the two-pass path, with the stream still exact.
    active = active.copy()
    active[:8] = False
    assert_tick_parity(single, spatial, pos, active, space, radius,
                       "@ despawn")
    assert not spatial.last_fast_tick
    assert spatial.total_fallbacks == 0


def test_pipelined_matches_sync():
    """step_async pipelining parity (depth 2) across migration ticks."""
    mesh = make_mesh(8)
    eng_sync = SpatialShardedNeighborEngine(
        PARAMS, mesh, prewarm_fallback=False
    )
    eng_pipe = SpatialShardedNeighborEngine(
        PARAMS, mesh, prewarm_fallback=False
    )
    eng_sync.reset()
    eng_pipe.reset()
    rng, pos, active, space, radius = make_world(450, seed=13)
    vel = rng.normal(0, 25.0, pos.shape).astype(np.float32)
    sync_stream, pipe_stream = [], []
    pending = None
    for t in range(6):
        e1, l1, _ = eng_sync.step(pos, active, space, radius)
        sync_stream.append((sorted(map(tuple, e1)), sorted(map(tuple, l1))))
        nxt = eng_pipe.step_async(pos, active, space, radius)
        if pending is not None:
            e2, l2, _ = pending.collect()
            pipe_stream.append(
                (sorted(map(tuple, e2)), sorted(map(tuple, l2)))
            )
        pending = nxt
        pos = np.clip(pos + vel, 0, WORLD_X).astype(np.float32)
        pos[:, 1] %= 1600.0
    e2, l2, _ = pending.collect()
    pipe_stream.append((sorted(map(tuple, e2)), sorted(map(tuple, l2))))
    assert sync_stream == pipe_stream


def brute_keys(pos, active, space, radius):
    """Every valid (watcher, other) pair of one epoch as ``i * N + j``,
    by brute force over all pairs."""
    d2 = ((pos[:, None, :] - pos[None, :, :]) ** 2).sum(-1)
    ok = (active[:, None] & active[None, :]
          & (space[:, None] == space[None, :])
          & (d2 <= radius[:, None] ** 2))
    np.fill_diagonal(ok, False)
    i, j = np.nonzero(ok)
    return set((i * len(pos) + j).tolist())


def pair_set(pairs):
    p = np.asarray(pairs, np.int64).reshape(-1, 2)
    return set((p[:, 0] * N + p[:, 1]).tolist())


def assert_events_exact(got, prev_keys, cur_keys, tag):
    enters, leaves, dropped = got
    assert dropped == 0, tag
    assert pair_set(enters) == cur_keys - prev_keys, f"enters differ {tag}"
    assert pair_set(leaves) == prev_keys - cur_keys, f"leaves differ {tag}"
    assert len(enters) == len(cur_keys - prev_keys), f"duplicates {tag}"


def walk_step(rng, pos, sigma, world=WORLD_X):
    pos = pos + rng.normal(0, sigma, pos.shape).astype(np.float32)
    np.clip(pos[:, 0], 0, world, out=pos[:, 0])
    np.clip(pos[:, 1], 1.0, 1599.0, out=pos[:, 1])
    return pos.astype(np.float32)


@pytest.mark.parametrize("case", ["walk", "replan", "pipelined"])
def test_incremental_relayout_exact(case):
    """Seam crossings move only the migrated rows: every tick's events
    equal the brute-force reference while the row layout is swapped in
    place. ``walk`` runs 24 ticks with the meta upload elided except on
    churn ticks; ``replan`` adopts density re-plans mid-walk, so whole
    rebuilds and swaps alternate; ``pipelined`` dispatches tick t+1 (its
    rows swapped) before tick t is collected, so tick t's pairs must map
    through the layout it was dispatched with."""
    single, spatial = make_engines(replan_interval=4 if case == "replan"
                                   else 0)
    del single
    rng, pos, active, space, radius = make_world(400, seed=41)
    if case == "replan":
        # 60% of the crowd on the left half: every strip still fits its
        # rows, but the uniform split is lopsided, so a later re-plan is
        # adopted.
        pos[:240, 0] *= 0.5
        pos[240:400, 0] = 0.5 * (WORLD_X + pos[240:400, 0])
    # The trajectory and its reference first, so the dispatches follow
    # each other as closely as a game loop's.
    ticks = []
    for tick in range(24):
        churn = tick % 6 == 5
        if churn:
            active = active.copy()
            active[rng.integers(0, N, 8)] ^= True
        ticks.append((pos, active, tick == 0 or churn,
                      brute_keys(pos, active, space, radius)))
        pos = walk_step(rng, pos, 25.0)
    prev_keys: set = set()
    pending = None
    rebuilds = 0
    for tick, (pos, active, dirty, keys) in enumerate(ticks):
        replans = spatial.total_replans
        pend = spatial.step_async(pos, active, space, radius,
                                  meta_dirty=dirty)
        replanned = spatial.total_replans > replans
        # A row the hysteresis keeps past a moved boundary can trip the
        # teleport guard on the dispatch that adopts the re-plan.
        assert spatial.last_mode == "spatial" or replanned, tick
        # The whole layout is rebuilt at set-up and on a dispatch that
        # adopts a re-plan, never for an ordinary seam crossing.
        rebuilds += tick == 0 or replanned
        assert spatial.total_relayouts["rebuild"] == rebuilds, tick
        if case == "pipelined":
            if pending is not None:
                # This dispatch swapped rows in place: the tick in flight
                # still holds the row->slot map it was dispatched with.
                held = np.asarray(pending[0]._enter_ctx[-1])
                assert np.array_equal(held, pending[-1]), tick
                assert_events_exact(pending[0].collect(), *pending[1:3],
                                    f"@ tick {tick - 1}")
            pending = (pend, prev_keys, keys, spatial.perm.copy())
        else:
            assert_events_exact(pend.collect(), prev_keys, keys,
                                f"@ tick {tick}")
        prev_keys = keys
    if pending is not None:
        assert_events_exact(pending[0].collect(), *pending[1:3], "@ last")
    assert spatial.total_row_moves > 0
    assert spatial.total_relayouts["incremental"] > 0
    if case == "replan":
        assert rebuilds >= 2
    assert spatial._jit_step._cache_size() == 1
    assert spatial.total_fallbacks <= spatial.total_replans


def test_full_strip_relayout_falls_back_exactly():
    """A strip whose rows are all placed has no free row for a slot
    moving in: the tick rebuilds the layout whole instead of swapping
    (a slot leaves the same tick, so the strip still fits), and a net
    inflow past its rows takes the strip-overflow path, deferring the
    moves; every tick stays exact, and a later crossing swaps again."""
    spatial = make_engines(replan_interval=0)[1]
    chunk = spatial.chunk  # 64 rows; strip s owns x in [800s, 800s + 800)
    pos = np.zeros((N, 2), np.float32)
    active = np.zeros(N, bool)
    space = np.zeros(N, np.int32)
    radius = np.full(N, 100.0, np.float32)
    rng = np.random.default_rng(43)
    # Strip 1 holds ``chunk`` entities, all in column 12, so no re-plan
    # can split them; strips 0 and 2 hold 40 each.
    active[:chunk + 80] = True
    pos[:chunk, 0] = rng.uniform(1205, 1295, chunk)
    pos[chunk:chunk + 40, 0] = rng.uniform(100, 600, 40)
    pos[chunk + 40:chunk + 80, 0] = rng.uniform(1700, 2300, 40)
    pos[:chunk + 80, 1] = rng.uniform(100, 1500, chunk + 80)
    prev_keys: set = set()

    def tick(tag, mode="spatial"):
        nonlocal prev_keys
        keys = brute_keys(pos, active, space, radius)
        before = dict(spatial.total_relayouts)
        assert_events_exact(spatial.step(pos, active, space, radius),
                            prev_keys, keys, tag)
        assert spatial.last_mode == mode, (tag, spatial.last_mode)
        prev_keys = keys
        return {k: spatial.total_relayouts[k] - before[k] for k in before}

    none = {"incremental": 0, "rebuild": 0}
    assert tick("setup") == {"incremental": 0, "rebuild": 1}
    assert spatial.shard_population[1] == chunk
    # Exchange: one of strip 1 walks into strip 2 while one of strip 0
    # walks into strip 1, which fits (64) but has no free row.
    pos[0] = (1590.0, 800.0)
    pos[chunk] = (790.0, 800.0)
    assert tick("staging") == none
    pos[0] = (1705.0, 800.0)
    pos[chunk] = (905.0, 800.0)
    assert tick("exchange") == {"incremental": 0, "rebuild": 1}
    assert spatial.shard_population[1] == chunk
    # Net inflow: two more walk in from strip 0 and none leave.
    pos[chunk + 1] = (790.0, 300.0)
    pos[chunk + 2] = (790.0, 1300.0)
    tick("staging 2")
    pos[chunk + 1] = (905.0, 300.0)
    pos[chunk + 2] = (905.0, 1300.0)
    assert tick("inflow", "fallback:strip_overflow") == none
    pos[chunk + 1] = (790.0, 300.0)  # still in strip 1's slack band
    pos[chunk + 2] = (790.0, 1300.0)
    assert tick("back", "fallback:strip_overflow") == none
    # Back past the band: the deferred moves cancel, nothing to relayout.
    pos[chunk + 1] = (690.0, 300.0)
    pos[chunk + 2] = (690.0, 1300.0)
    assert tick("back 2") == none
    # A crossing into strip 3, which has free rows, swaps.
    pos[chunk + 40] = (2390.0, 800.0)
    tick("staging 3")
    pos[chunk + 40] = (2505.0, 800.0)
    assert tick("cross") == {"incremental": 1, "rebuild": 0}
    assert spatial.total_fallbacks == 2


def test_plan_strips_properties():
    """Planner unit: boundaries cover [0, gx], honor the minimum width,
    and an 8x density skew pulls more columns into the sparse strips."""
    gx = 64
    uniform = plan_strips(np.full(gx, 10), 8)
    assert uniform[0] == 0 and uniform[-1] == gx
    assert (np.diff(uniform) >= 4).all()
    skew = np.full(gx, 1)
    skew[:8] = 100  # hot left edge
    bounds = plan_strips(skew, 8)
    assert (np.diff(bounds) >= 4).all()
    # Hot strips narrow to the floor; the sparse right side widens.
    assert np.diff(bounds)[0] <= np.diff(uniform)[0]
    assert np.diff(bounds).max() > np.diff(uniform).max()
    with pytest.raises(ValueError):
        plan_strips(np.full(16, 1), 8)  # 16 cols cannot host 8 strips


def test_constructor_validation():
    mesh = make_mesh(8)
    with pytest.raises(ValueError, match="grid_x"):
        SpatialShardedNeighborEngine(
            NeighborParams(capacity=512, grid_x=16, grid_z=16),
            mesh, prewarm_fallback=False,
        )
    with pytest.raises(ValueError, match="capacity"):
        SpatialShardedNeighborEngine(
            NeighborParams(capacity=520, grid_x=64, grid_z=16),
            mesh, prewarm_fallback=False,
        )
    with pytest.raises(ValueError):
        SpatialShardedNeighborEngine(PARAMS, make_mesh(1),
                                     prewarm_fallback=False)


def test_telemetry_counters_move():
    """aoi_halo_bytes_total / aoi_shard_migrations_total / shard gauges
    must reflect a run (the satellites' observability contract)."""
    from goworld_tpu import telemetry

    single, spatial = make_engines()
    halo0 = telemetry.counter("aoi_halo_bytes_total").value
    relayouts = telemetry.counter("aoi_shard_relayouts_total",
                                  labelnames=("kind",))
    kinds = ("incremental", "rebuild")
    relayouts0 = {k: relayouts.labels(k).value for k in kinds}
    rng, pos, active, space, radius = make_world(400, seed=17)
    for _ in range(6):
        spatial.step(pos, active, space, radius)
        pos = np.clip(
            pos + rng.normal(0, 40, pos.shape), 0, WORLD_X
        ).astype(np.float32)
    assert telemetry.counter("aoi_halo_bytes_total").value >= (
        halo0 + 6 * spatial.halo_bytes_per_tick
    )
    # The relayout counter moves with the engine's own counts: the set-up
    # rebuild, then swaps for the seam crossings.
    assert spatial.total_relayouts["rebuild"] == 1
    assert spatial.total_relayouts["incremental"] >= 1
    for k in kinds:
        assert relayouts.labels(k).value - relayouts0[k] == (
            spatial.total_relayouts[k])
    assert telemetry.gauge("aoi_shard_count").value == 8
    got = sum(
        int(telemetry.gauge("aoi_shard_entities", labelnames=("shard",))
            .labels(str(d)).value)
        for d in range(8)
    )
    assert got == int(spatial.shard_population.sum())
    assert spatial.halo_bytes_per_tick < spatial.allgather_bytes_per_tick


def test_fused_logic_randomized_oracle_with_migrations_and_replans():
    """ISSUE 12 satellite: fused entity logic on the SPATIAL engine. The
    logic inputs (sel/y/yaw/Column attrs) upload row-permuted through the
    same perm as positions; outputs come back in ROW space and map to
    slots through the dispatch-time perm SNAPSHOT — so strip migrations
    and density re-plans between dispatches can neither misroute a value
    nor reset a column to its default. Oracle: exact event parity with
    the single-device engine AND bit-exact trajectory parity with the
    same vmapped program applied host-side after each dispatch."""
    import jax

    from goworld_tpu.entity.columns import FusedProgram

    single, spatial = make_engines(replan_interval=3)
    rng, pos, active, space, radius = make_world(400, seed=7)

    def drift(x, y, z, yaw, dt, vx):
        return x + vx * dt, y, z, yaw + dt, vx

    prog = FusedProgram(drift, ("vx",))
    vfn = jax.jit(jax.vmap(drift, in_axes=(0, 0, 0, 0, None, 0)))
    y = np.zeros(N, np.float32)
    yaw = rng.uniform(0, 360, N).astype(np.float32)
    vx = rng.normal(0, 60, N).astype(np.float32)  # seam-crossing drift
    vx0 = vx.copy()
    sel = (rng.random(N) < 0.8).astype(np.int32)
    rpos, ryaw, rvx = pos.copy(), yaw.copy(), vx.copy()
    for tick in range(8):
        dt = np.float32(0.25)
        pend = spatial.step_async(
            pos, active, space, radius,
            logic=((prog,), sel, y, yaw, float(dt), (vx,)))
        e2, l2, d2 = pend.collect()
        e1, l1, d1 = single.step(rpos, active, space, radius)
        assert d1 == d2
        assert to_sets(e1) == to_sets(e2), f"fused enters differ @ {tick}"
        assert to_sets(l1) == to_sets(l2), f"fused leaves differ @ {tick}"
        assert spatial.last_mode == "spatial", spatial.last_mode
        # Row-space outputs → slot space through the perm snapshot.
        programs, sel_s, perm, outs = pend.fused
        assert perm is not None
        new_pos, new_y, new_yaw, new_vx = (np.asarray(a) for a in outs)
        rows = np.flatnonzero(sel_s[perm])
        slots = perm[rows]
        pos = pos.copy()
        pos[slots] = new_pos[rows]
        yaw[slots] = new_yaw[rows]
        vx[slots] = new_vx[rows]
        # Host-side reference of the same program.
        ox, _, _, oyaw, ovx = (np.asarray(a) for a in vfn(
            rpos[:, 0], y, rpos[:, 1], ryaw, dt, rvx))
        m = sel_s > 0
        rpos = rpos.copy()
        rpos[m, 0] = ox[m]
        ryaw[m] = oyaw[m]
        rvx[m] = ovx[m]
        assert np.array_equal(pos, rpos), f"trajectory diverged @ {tick}"
        assert np.array_equal(yaw, ryaw) and np.array_equal(vx, rvx)
    assert spatial.total_migrations > 0, "no strip migrations exercised"
    # A migration tick must never reset a column: vx is program-invariant
    # here, so any loss (a default-zero write) would show as a change.
    assert np.array_equal(vx[sel > 0], vx0[sel > 0])
    assert spatial.total_fallbacks == 0


def test_fused_logic_advances_on_fallback_ticks():
    """A teleport tick runs the exact all-gather fallback — the fused
    program must STILL advance (the fallback jit carries the logic too),
    with outputs row-mapped through the same perm-snapshot contract."""
    from goworld_tpu.entity.columns import FusedProgram

    single, spatial = make_engines()
    rng, pos, active, space, radius = make_world(300, seed=3)

    def drift(x, y, z, yaw, dt, vx):
        return x + vx * dt, y, z, yaw, vx

    prog = FusedProgram(drift, ("vx",))
    y = np.zeros(N, np.float32)
    yaw = np.zeros(N, np.float32)
    vx = np.full(N, 8.0, np.float32)
    sel = np.ones(N, np.int32)
    logic = ((prog,), sel, y, yaw, 0.5, (vx,))
    spatial.step_async(pos, active, space, radius, logic=logic).collect()
    # Mass teleport: previous cells escape the halo → exact fallback.
    pos2 = rng.uniform(0, WORLD_X, (N, 2)).astype(np.float32)
    pos2[:, 1] %= 1600.0
    pend = spatial.step_async(pos2, active, space, radius, logic=logic)
    pend.collect()
    assert "fallback" in spatial.last_mode, spatial.last_mode
    programs, sel_s, perm, outs = pend.fused
    new_pos = np.asarray(outs[0])
    rows = np.flatnonzero(sel_s[perm])
    slots = perm[rows]
    expect = pos2[slots, 0] + np.float32(8.0) * np.float32(0.5)
    assert np.array_equal(new_pos[rows, 0], expect.astype(np.float32))


def test_halo_span_on_traced_ticks():
    """A traced dispatch must leave a ``tick.halo`` span in the ring with
    the migration count and mode attributed (the observability clause of
    the telemetry satellite); untraced dispatches must add none."""
    from goworld_tpu.telemetry import tracing

    single, spatial = make_engines()
    rng, pos, active, space, radius = make_world(300, seed=23)
    spatial.step(pos, active, space, radius)  # untraced
    base = sum(1 for sp in tracing.snapshot() if sp["name"] == "tick.halo")
    saved = tracing.sample_rate()
    tracing.configure(sample_rate=1)
    try:
        scope = tracing.root_scope("test.tick")
        assert scope is not None
        with scope:
            spatial.step(pos, active, space, radius)
    finally:
        tracing.configure(sample_rate=saved)
    spans = [sp for sp in tracing.snapshot() if sp["name"] == "tick.halo"]
    assert len(spans) == base + 1
    assert spans[-1]["args"]["mode"] == "spatial"
    assert "migrations" in spans[-1]["args"]
