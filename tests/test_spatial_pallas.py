"""Strip+halo Pallas spatial tier (ISSUE 15): the strip-local kernel slab
engine must agree EXACTLY with the single-device engine — across strip
migrations, density re-plans, seam-cell capacity drops, event storms past
the inline budget, exact-fallback ticks, and fused-logic columns — while
a seam-free steady-state tick stays ONE SentinelJit launch with zero
steady-state retraces. Topology-aware strip→device placement is unit-
tested on stub devices (real coords don't exist on the CPU rig)."""

import jax
import numpy as np
import pytest

from goworld_tpu.ops import NeighborEngine, NeighborParams
from goworld_tpu.parallel import make_mesh
from goworld_tpu.parallel.spatial import (
    SpatialShardedNeighborEngine,
    plan_placement,
    plan_strips,
    ring_link_distance,
)
from goworld_tpu.telemetry import sentinel

# One params object shared by most tests: the interpreted kernel compiles
# per (params, mesh, halo_cap, cols_cap) via lru_cache, and that compile
# dominates this module's runtime — sharing keeps it to one set.
# grid_z 8 / space_slots 2 / strip_cols 10 bound the kernel grid at
# 2*8*12 programs per device through the interpreter.
PARAMS = NeighborParams(
    capacity=1024, cell_size=100.0, grid_x=64, grid_z=8,
    space_slots=2, cell_capacity=64, max_events=8192,
)
N = 1024
WORLD_X = 6400.0
WORLD_Z = 800.0
STRIP_COLS = 10


def make_engines(params=PARAMS, **kw):
    mesh = make_mesh(8)
    single = NeighborEngine(params, backend="jnp")
    kw.setdefault("prewarm_fallback", False)
    kw.setdefault("backend", "pallas_interpret")
    kw.setdefault("strip_cols", STRIP_COLS)
    spatial = SpatialShardedNeighborEngine(params, mesh, **kw)
    single.reset()
    spatial.reset()
    return single, spatial


def make_world(n_active, seed, n_spaces=2):
    rng = np.random.default_rng(seed)
    pos = rng.uniform(0, WORLD_X, size=(N, 2)).astype(np.float32)
    pos[:, 1] %= WORLD_Z
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


def test_pallas_strip_parity_with_migrations_replans_and_drops():
    """The headline oracle: random walk with spawn/despawn churn, density
    re-plans every 3 dispatches, seam crossings, and a 70-entity pile in
    ONE seam cell (capacity 64) so seam-cell drop tie-breaks are live —
    every tick must run the strip-local SPATIAL program and match the
    single-device stream exactly, drops included."""
    single, spatial = make_engines(replan_interval=3)
    rng, pos, active, space, radius = make_world(400, seed=7)
    # A pile on the strip seam at column 8 (64 cols / 8 shards): 70 rows
    # in one cell overflows cell_capacity 64 on a cell COPIED to two
    # shards — the slot-id tie-break must drop identically everywhere.
    pos[:70] = (805.0, 405.0)
    space[:70] = 0
    saw_drops = 0
    saw_both = 0
    for tick in range(5):
        e1, l1 = assert_tick_parity(
            single, spatial, pos, active, space, radius, f"@ tick {tick}"
        )
        assert spatial.last_mode == "spatial", spatial.last_mode
        if single.last_grid_dropped:
            saw_drops += 1
        if tick and len(e1) and len(l1):
            saw_both += 1
        # clip (not wrap) z: a 0→800 modular wrap is a REAL 800-unit
        # move that correctly trips the teleport guard — not this test.
        pos = pos + rng.normal(0, 20, pos.shape).astype(np.float32)
        np.clip(pos[:, 0], 0, WORLD_X, out=pos[:, 0])
        np.clip(pos[:, 1], 1.0, WORLD_Z - 1.0, out=pos[:, 1])
        pos = pos.astype(np.float32)
        active = active.copy()
        active[rng.integers(0, N, 12)] ^= True
    assert saw_drops >= 1, "seam-cell drops never exercised"
    assert saw_both >= 2, "walk produced too few enter+leave ticks"
    assert spatial.total_migrations > 0, "no seam crossings exercised"
    assert spatial.total_fallbacks == 0


def brute_keys(pos, active, space, radius):
    """Every valid (watcher, other) pair of one epoch as ``i * N + j``,
    by brute force over all pairs."""
    d2 = ((pos[:, None, :] - pos[None, :, :]) ** 2).sum(-1)
    ok = (active[:, None] & active[None, :]
          & (space[:, None] == space[None, :])
          & (d2 <= radius[:, None] ** 2))
    np.fill_diagonal(ok, False)
    i, j = np.nonzero(ok)
    return set((i * N + j).tolist())


def assert_events_exact(got, prev_keys, cur_keys, tag):
    enters, leaves, dropped = got
    assert dropped == 0, tag
    for pairs, want, side in ((enters, cur_keys - prev_keys, "enters"),
                              (leaves, prev_keys - cur_keys, "leaves")):
        p = np.asarray(pairs, np.int64).reshape(-1, 2)
        assert set((p[:, 0] * N + p[:, 1]).tolist()) == want, (
            f"{side} differ {tag}")
        assert len(p) == len(want), f"duplicate {side} {tag}"


@pytest.mark.parametrize("case,ticks", [("walk", 20), ("pipelined", 6)])
def test_pallas_strip_incremental_relayout_exact(case, ticks):
    """Seam crossings on the strip kernel move only the migrated rows:
    every tick's events equal the brute-force reference, with the meta
    upload elided except on churn ticks, while rows swap in place. The
    pipelined case dispatches tick t+1 (its rows swapped) before tick t
    is collected: tick t's pairs map through its own layout."""
    spatial = make_engines(replan_interval=0)[1]
    rng, pos, active, space, radius = make_world(400, seed=41)
    prev_keys: set = set()
    pending = None
    swaps = 0
    for tick in range(ticks):
        churn = tick % 6 == 5
        if churn:
            active = active.copy()
            active[rng.integers(0, N, 8)] ^= True
        keys = brute_keys(pos, active, space, radius)
        before = spatial.total_relayouts["incremental"]
        pend = spatial.step_async(pos, active, space, radius,
                                  meta_dirty=tick == 0 or churn)
        assert spatial.last_mode == "spatial", spatial.last_mode
        if spatial.total_relayouts["incremental"] > before and tick:
            swaps += 1
        if case == "pipelined":
            if pending is not None:
                assert_events_exact(pending[0].collect(), *pending[1:],
                                    f"@ tick {tick - 1}")
            pending = (pend, prev_keys, keys)
        else:
            assert_events_exact(pend.collect(), prev_keys, keys,
                                f"@ tick {tick}")
        prev_keys = keys
        pos = pos + rng.normal(0, 25, pos.shape).astype(np.float32)
        np.clip(pos[:, 0], 0, WORLD_X, out=pos[:, 0])
        np.clip(pos[:, 1], 1.0, WORLD_Z - 1.0, out=pos[:, 1])
        pos = pos.astype(np.float32)
    if pending is not None:
        assert_events_exact(pending[0].collect(), *pending[1:], "@ last")
    assert swaps >= 2, "too few ticks swapped rows"
    assert spatial.total_row_moves > 0
    # Only the set-up dispatch rebuilds the layout whole.
    assert spatial.total_relayouts["rebuild"] == 1
    assert spatial._jit_step._cache_size() == 1
    assert spatial.total_fallbacks == 0


def test_pallas_strip_fast_path_one_launch_trace_pin():
    """Seam-free steady-state ticks (radius 40, ~4-unit drift keeps the
    replicated guard TRUE) must (a) match the single-device stream, (b)
    report last_fast_tick, and (c) be ONE SentinelJit launch each on the
    strip step jit with exactly ONE compiled trace and ZERO steady-state
    retraces — the ISSUE 15 one-launch pin, SentinelJit-verified like
    test_fused_service_one_launch_trace_counts."""
    single, spatial = make_engines()
    rng, pos, active, space, radius = make_world(400, seed=11)
    radius = np.full(N, 40.0, np.float32)
    spatial.step(pos, active, space, radius)  # compile + enter storm
    single.step(pos, active, space, radius)
    launches0 = sentinel.launches_total("spatial_step_pallas")
    traces0 = sentinel.traces_total("spatial_step_pallas")
    retr0 = sentinel.steady_state_retraces()
    fast0 = spatial.total_fast_ticks
    ticks = 4
    saw_leaves = 0
    for tick in range(ticks):
        pos = pos + rng.normal(0, 3, pos.shape).astype(np.float32)
        np.clip(pos[:, 0], 0, WORLD_X, out=pos[:, 0])
        np.clip(pos[:, 1], 1.0, WORLD_Z - 1.0, out=pos[:, 1])
        pos = pos.astype(np.float32)
        e1, l1 = assert_tick_parity(
            single, spatial, pos, active, space, radius, f"@ fast {tick}"
        )
        assert spatial.last_mode == "spatial"
        assert spatial.last_fast_tick, f"guard broke @ tick {tick}"
        saw_leaves += len(l1)
    assert saw_leaves > 0, "fast-path trace produced no leaves"
    assert spatial.total_fast_ticks - fast0 == ticks
    assert sentinel.launches_total("spatial_step_pallas") - launches0 == ticks
    assert sentinel.traces_total("spatial_step_pallas") - traces0 == 0
    assert spatial._jit_step._cache_size() == 1
    assert sentinel.steady_state_retraces() - retr0 == 0


def test_pallas_strip_teleport_falls_back_exactly():
    """A mass teleport breaks strip locality: that tick must run the
    exact all-gather fallback (jnp program, flat-index paging) and STILL
    match the single-device stream — then recover to the strip program
    (rank paging) with parity intact across the mode switch."""
    single, spatial = make_engines()
    rng, pos, active, space, radius = make_world(400, seed=3)
    for tick in range(4):
        assert_tick_parity(
            single, spatial, pos, active, space, radius, f"@ tp {tick}"
        )
        if tick == 1:
            pos = rng.uniform(0, WORLD_X, (N, 2)).astype(np.float32)
            pos[:, 1] %= WORLD_Z
        else:
            pos = np.clip(
                pos + rng.normal(0, 5, pos.shape), 0, WORLD_X
            ).astype(np.float32)
            pos[:, 1] %= WORLD_Z
    assert spatial.total_fallbacks >= 1


def test_pallas_strip_event_storm_pages_chunked_drain(drain_launches):
    """First-tick enter storm past a chip's inline budget (max_events on
    each chip, 32 here) must page through the strip-local bit drain by
    event RANK with exactly-once pairs."""
    p = NeighborParams(
        capacity=1024, cell_size=100.0, grid_x=64, grid_z=8,
        space_slots=2, cell_capacity=64, max_events=32,
    )
    single, spatial = make_engines(p)
    assert spatial.events_inline == p.max_events
    rng, pos, active, space, radius = make_world(400, seed=11)
    e1, l1, _ = single.step(pos, active, space, radius)
    pages0 = drain_launches()
    e2, l2, _ = spatial.step(pos, active, space, radius)
    assert spatial.last_mode == "spatial"
    assert drain_launches() > pages0  # a chip's window really overflows
    assert to_sets(e1) == to_sets(e2)
    assert len(e1) == len(e2)  # exactly-once across chunks


def test_inkernel_drain_off_matches_on():
    """[aoi] pallas_inkernel_drain = false keeps the XLA rank-select
    drain as the ONLY event extraction: the same churny trace (spawn/
    despawn flips, seam drift, a first-tick enter storm) on two strip
    engines — kernel-emitted pairs vs XLA drain — must produce identical
    event streams every tick.  The in-kernel drain stage is a pure
    relocation of the same computation into the launch, never a
    different answer."""
    mesh = make_mesh(8)
    on = SpatialShardedNeighborEngine(
        PARAMS, mesh, backend="pallas_interpret", strip_cols=STRIP_COLS,
        prewarm_fallback=False)
    off = SpatialShardedNeighborEngine(
        PARAMS, mesh, backend="pallas_interpret", strip_cols=STRIP_COLS,
        prewarm_fallback=False, inkernel_drain=False)
    assert on.inkernel_drain and on.drain_inline == on.events_inline
    assert not off.inkernel_drain and off.drain_inline == 0
    on.reset()
    off.reset()
    rng, pos, active, space, radius = make_world(400, seed=23)
    for tick in range(4):
        e1, l1, d1 = on.step(pos, active, space, radius)
        e2, l2, d2 = off.step(pos, active, space, radius)
        assert to_sets(e1) == to_sets(e2), f"enters differ @ tick {tick}"
        assert to_sets(l1) == to_sets(l2), f"leaves differ @ tick {tick}"
        assert len(e1) == len(e2) and len(l1) == len(l2)  # exactly-once
        assert d1 == d2
        pos = pos + rng.normal(0, 20, pos.shape).astype(np.float32)
        np.clip(pos[:, 0], 0, WORLD_X, out=pos[:, 0])
        np.clip(pos[:, 1], 1.0, WORLD_Z - 1.0, out=pos[:, 1])
        pos = pos.astype(np.float32)
        active = active.copy()
        active[rng.integers(0, N, 12)] ^= True
    assert on.last_mode == "spatial" and off.last_mode == "spatial"
    assert on.total_fallbacks == 0 and off.total_fallbacks == 0


def test_inkernel_drain_storm_full_repage_parity(drain_launches):
    """A storm tick past the inline budget on the in-kernel drain engine
    must repage WHOLLY through the XLA rank-select (kernel emission is
    cell-major — a partial inline window is not rank-resumable) and
    still deliver the exact single-device stream exactly once."""
    p = NeighborParams(
        capacity=1024, cell_size=100.0, grid_x=64, grid_z=8,
        space_slots=2, cell_capacity=64, max_events=32,
    )
    single, spatial = make_engines(p)
    assert spatial.drain_inline == p.max_events  # armed, a chip's window
    rng, pos, active, space, radius = make_world(400, seed=11)
    launches0 = sentinel.launches_total("spatial_step_pallas")
    retr0 = sentinel.steady_state_retraces()
    ticks = 3
    saw_storms = 0
    for tick in range(ticks):
        pend = spatial.step_async(pos, active, space, radius)
        assert pend.full_repage, "in-kernel pending not marked full_repage"
        pages0 = drain_launches()
        e2, l2, _ = pend.collect()
        e1, l1, _ = single.step(pos, active, space, radius)
        if drain_launches() > pages0:
            saw_storms += 1  # a chip's window really overflowed
        assert to_sets(e1) == to_sets(e2), f"enters differ @ tick {tick}"
        assert len(e1) == len(e2)  # exactly-once across the full repage
        assert to_sets(l1) == to_sets(l2), f"leaves differ @ tick {tick}"
        # Big scrambles inside each strip band keep every tick stormy.
        pos = pos + rng.normal(0, 30, pos.shape).astype(np.float32)
        np.clip(pos[:, 0], 0, WORLD_X, out=pos[:, 0])
        np.clip(pos[:, 1], 1.0, WORLD_Z - 1.0, out=pos[:, 1])
        pos = pos.astype(np.float32)
    assert saw_storms >= 1, "no tick overflowed a chip's inline budget"
    # The acceptance pin: the storm pages through EXTRA drain launches,
    # but the STEP stays one launch per tick with zero steady retraces.
    assert (sentinel.launches_total("spatial_step_pallas") - launches0
            == ticks)
    assert sentinel.steady_state_retraces() - retr0 == 0


def test_pallas_strip_fused_logic_oracle():
    """Fused entity logic on the Pallas strip engine: row-permuted
    inputs, perm-snapshot writeback, exact event parity AND bit-exact
    trajectory parity with the host-side vmapped program — including
    across strip migrations (seam-crossing drift)."""
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
    sel = (rng.random(N) < 0.8).astype(np.int32)
    rpos, ryaw, rvx = pos.copy(), yaw.copy(), vx.copy()
    for tick in range(4):
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
        programs, sel_s, perm, outs = pend.fused
        assert perm is not None
        new_pos, new_y, new_yaw, new_vx = (np.asarray(a) for a in outs)
        rows = np.flatnonzero(sel_s[perm])
        slots = perm[rows]
        pos = pos.copy()
        pos[slots] = new_pos[rows]
        yaw[slots] = new_yaw[rows]
        vx[slots] = new_vx[rows]
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
    assert spatial.total_fallbacks == 0


def test_pallas_constructor_validation():
    mesh = make_mesh(8)
    with pytest.raises(ValueError, match="cell_capacity"):
        SpatialShardedNeighborEngine(
            NeighborParams(capacity=512, grid_x=64, grid_z=8,
                           cell_capacity=129),
            mesh, backend="pallas_interpret", prewarm_fallback=False,
        )
    with pytest.raises(ValueError, match="strip_cols"):
        # 8 strips of <= 4 columns cannot cover 64 columns.
        SpatialShardedNeighborEngine(
            PARAMS, mesh, backend="pallas_interpret", strip_cols=4,
            prewarm_fallback=False,
        )
    with pytest.raises(ValueError, match="ghost columns"):
        # The slab would wrap onto itself: cap + 4 > grid_x.
        SpatialShardedNeighborEngine(
            PARAMS, mesh, backend="pallas_interpret", strip_cols=61,
            prewarm_fallback=False,
        )


def test_plan_strips_max_cols_cap():
    """The planner honors the Pallas tier's width cap: an 8x density skew
    that would widen the sparse side past the cap is clamped, boundaries
    still cover [0, gx], and infeasible caps reject loudly."""
    gx = 64
    skew = np.full(gx, 1)
    skew[:8] = 100
    uncapped = plan_strips(skew, 8)
    assert np.diff(uncapped).max() > 12  # the skew really wants width
    capped = plan_strips(skew, 8, max_cols=12)
    assert capped[0] == 0 and capped[-1] == gx
    assert (np.diff(capped) >= 4).all()
    assert (np.diff(capped) <= 12).all()
    with pytest.raises(ValueError, match="max columns"):
        plan_strips(skew, 8, max_cols=7)  # 8 * 7 < 64


class _StubDev:
    def __init__(self, coords, core=0):
        self.coords = coords
        self.core_on_chip = core


def test_plan_placement_snake_beats_ring_on_grid():
    """On a 2x4 chip grid enumerated row-major (the naive mesh order
    pays a long wrap hop), the boustrophedon placement must make every
    ring link single-hop and strictly reduce total ring distance."""
    devs = [_StubDev((x, y, 0)) for y in range(2) for x in range(4)]
    order = plan_placement(devs)
    coords = [d.coords for d in devs]
    naive = ring_link_distance(coords, np.arange(8))
    placed = ring_link_distance(coords, order)
    assert placed < naive
    # Every consecutive link (incl. the wrap) is a nearest neighbor.
    for i in range(8):
        a = coords[int(order[i])]
        b = coords[int(order[(i + 1) % 8])]
        assert sum(abs(p - q) for p, q in zip(a, b)) == 1


def test_plan_placement_ring_fallback_without_coords():
    """Devices without coords (CPU rigs) keep ring order — and a snake
    that cannot beat the given order is not adopted."""
    class _Bare:
        pass

    assert np.array_equal(plan_placement([_Bare(), _Bare()]), [0, 1])
    # Already-optimal linear chain: snake must not shuffle it.
    devs = [_StubDev((x, 0, 0)) for x in range(4)]
    order = plan_placement(devs)
    coords = [d.coords for d in devs]
    assert ring_link_distance(coords, order) <= ring_link_distance(
        coords, np.arange(4))


def test_placement_engine_integration_identity_on_cpu():
    """On the virtual CPU mesh (no device coords) the topology placement
    must leave the mesh untouched — the jnp and placement-enabled
    engines share jit caches and event streams."""
    mesh = make_mesh(8)
    eng = SpatialShardedNeighborEngine(
        PARAMS, mesh, prewarm_fallback=False, placement="topology",
    )
    assert np.array_equal(eng.placement_order, np.arange(8))
    assert eng.mesh is mesh


def test_pallas_sharded_bench_structural_ratio():
    """The Pallas strip tier's structural halo bytes beat ITS all-gather
    equivalent by more than the jnp tier's 5.3x. Constructed (not
    stepped): the byte counts are structural per-tick payloads. The
    Pallas config is 2048 slots over a 192-column torus (24-column
    uniform strips in a 48-column slab), ``grid_z`` 8, ``halo_cap`` 128;
    the jnp config is 8192 slots over a 128-column torus, ``halo_cap``
    768 (tests/test_spatial.py::test_sharded_engine_exact_at_fixed_config
    steps it)."""
    mesh = make_mesh(8)
    pallas = SpatialShardedNeighborEngine(
        NeighborParams(capacity=2048, cell_size=100.0, grid_x=192,
                       grid_z=8, space_slots=1, cell_capacity=32,
                       max_events=16384),
        mesh, halo_cap=128, prewarm_fallback=False,
        backend="pallas_interpret", strip_cols=48,
    )
    ratio = pallas.allgather_bytes_per_tick / pallas.halo_bytes_per_tick
    assert ratio > 5.3, (
        f"pallas strip tier comms reduction {ratio:.2f}x must beat the "
        f"jnp tier's 5.3x"
    )
    jnp_eng = SpatialShardedNeighborEngine(
        NeighborParams(capacity=8192, cell_size=100.0, grid_x=128,
                       grid_z=128, space_slots=1, cell_capacity=32,
                       max_events=32768),
        mesh, halo_cap=768, prewarm_fallback=False, backend="jnp",
    )
    assert (jnp_eng.allgather_bytes_per_tick
            / jnp_eng.halo_bytes_per_tick) > 5.0
