"""Correctness tests for the batched AOI neighbor engine.

The oracle is a brute-force O(N^2) numpy computation of the same interest
semantics: entity j is in entity i's set iff both active (and grid-visible),
same space, j != i, and dist(i,j) <= radius_i. This mirrors how the
reference's AOI behavior is pinned by its CPU implementation (SURVEY.md §7.2
step 7: "correctness oracle = CPU manager on identical traces").

The engine is event-native (exact geometric sets, no max_neighbors
truncation): host-side sets are reconstructed incrementally from the
enter/leave stream and compared to the oracle each tick.
"""

import numpy as np
import pytest

from goworld_tpu.ops import NeighborEngine, NeighborParams
from goworld_tpu.ops.neighbor import LANES, _row_find_steps


def brute_force_sets(pos, active, space, radius):
    n = len(pos)
    out = []
    for i in range(n):
        if not active[i]:
            out.append(set())
            continue
        d2 = np.sum((pos - pos[i]) ** 2, axis=1)
        mask = (
            active
            & (space == space[i])
            & (d2 <= radius[i] ** 2)
            & (np.arange(n) != i)
        )
        out.append(set(np.nonzero(mask)[0].tolist()))
    return out


def pairs_to_setlist(pairs, n):
    out = [set() for _ in range(n)]
    for a, b in pairs:
        out[int(a)].add(int(b))
    return out


def apply_events(cur, enters, leaves):
    for a, b in leaves:
        cur[int(a)].discard(int(b))
    for a, b in enters:
        cur[int(a)].add(int(b))


def make_world(n, n_active, seed, world=1000.0, n_spaces=1):
    rng = np.random.default_rng(seed)
    pos = rng.uniform(0, world, size=(n, 2)).astype(np.float32)
    active = np.zeros(n, bool)
    active[:n_active] = True
    space = rng.integers(0, n_spaces, size=n).astype(np.int32)
    radius = np.full(n, 100.0, np.float32)
    return pos, active, space, radius


PARAMS = NeighborParams(
    capacity=256, cell_size=100.0, grid_x=16, grid_z=16,
    space_slots=4, cell_capacity=64, max_events=16384,
)


def engine(backend="jnp"):
    e = NeighborEngine(PARAMS, backend=backend)
    e.reset()
    return e


def test_first_tick_all_enters():
    eng = engine()
    pos, active, space, radius = make_world(256, 200, seed=0)
    enters, leaves, dropped = eng.step(pos, active, space, radius)
    assert len(leaves) == 0
    assert dropped == 0
    got = pairs_to_setlist(enters, 256)
    want = brute_force_sets(pos, active, space, radius)
    assert got == want


def test_incremental_diffs_match_oracle():
    eng = engine()
    rng = np.random.default_rng(1)
    pos, active, space, radius = make_world(256, 180, seed=1)
    cur = [set() for _ in range(256)]
    for tick in range(10):
        pos = pos + rng.normal(0, 15, size=pos.shape).astype(np.float32)
        pos = np.clip(pos, 0, 1500).astype(np.float32)
        enters, leaves, dropped = eng.step(pos, active, space, radius)
        assert dropped == 0
        apply_events(cur, enters, leaves)
        want = brute_force_sets(pos, active, space, radius)
        assert cur == want, f"tick {tick} mismatch"


def test_teleports_are_exact():
    """Unbounded per-tick movement (EnterSpace / cross-game migration lands
    an entity anywhere): the two-grid formulation must emit exact diffs."""
    eng = engine()
    rng = np.random.default_rng(7)
    pos, active, space, radius = make_world(256, 200, seed=7, world=1500.0)
    cur = [set() for _ in range(256)]
    for tick in range(6):
        pos = rng.uniform(0, 1500, size=pos.shape).astype(np.float32)  # all teleport
        enters, leaves, _ = eng.step(pos, active, space, radius)
        apply_events(cur, enters, leaves)
        want = brute_force_sets(pos, active, space, radius)
        assert cur == want, f"teleport tick {tick} mismatch"


def test_space_isolation():
    eng = engine()
    n = 256
    pos = np.zeros((n, 2), np.float32)  # everyone at the same point
    active = np.ones(n, bool)
    space = (np.arange(n) % 4).astype(np.int32)
    radius = np.full(n, 50.0, np.float32)
    enters, leaves, _ = eng.step(pos, active, space, radius)
    got = pairs_to_setlist(enters, n)
    for i in range(n):
        assert all(space[j] == space[i] for j in got[i])
        assert len(got[i]) == 64 - 1  # 256/4 per space minus self


def test_entity_deactivation_emits_leaves():
    eng = engine()
    pos, active, space, radius = make_world(256, 100, seed=2, world=300.0)
    enters, _, _ = eng.step(pos, active, space, radius)
    sets0 = pairs_to_setlist(enters, 256)
    # Deactivate entity 0 (destroy/migrate-out); its neighbors must see a leave.
    active2 = active.copy()
    active2[0] = False
    enters2, leaves2, _ = eng.step(pos, active2, space, radius)
    leave_sets = pairs_to_setlist(leaves2, 256)
    for j in sets0[0]:
        assert 0 in leave_sets[j], f"entity {j} did not see entity 0 leave"
    # And entity 0 lost all its neighbors.
    assert leave_sets[0] == sets0[0]


def test_asymmetric_radius():
    """Per-entity radius: big-radius entity sees small, not vice versa."""
    eng = engine()
    n = 256
    pos = np.zeros((n, 2), np.float32)
    active = np.zeros(n, bool)
    active[:2] = True
    pos[0] = (0.0, 0.0)
    pos[1] = (70.0, 0.0)
    space = np.zeros(n, np.int32)
    radius = np.full(n, 100.0, np.float32)
    radius[1] = 30.0
    enters, _, _ = eng.step(pos, active, space, radius)
    got = pairs_to_setlist(enters, n)
    assert got[0] == {1}
    assert got[1] == set()


def test_wraparound_no_false_neighbors():
    """Entities separated by more than a grid period still never match:
    distance filter kills torus aliases."""
    eng = engine()
    n = 256
    pos = np.zeros((n, 2), np.float32)
    active = np.zeros(n, bool)
    active[:2] = True
    # 16 cells * 100 = 1600 period: these two alias to the same cell.
    pos[0] = (50.0, 50.0)
    pos[1] = (50.0 + 1600.0, 50.0)
    space = np.zeros(n, np.int32)
    radius = np.full(n, 100.0, np.float32)
    enters, _, _ = eng.step(pos, active, space, radius)
    assert len(enters) == 0


def test_no_truncation_exact_sets():
    """Round-1's engine capped interest sets at max_neighbors (lowest-id-K);
    the event-native engine has no cap: 255 true neighbors all reported."""
    p = NeighborParams(
        capacity=256, cell_size=100.0, grid_x=16, grid_z=16,
        space_slots=4, cell_capacity=256, max_events=131072,
    )
    eng = NeighborEngine(p, backend="jnp")
    eng.reset()
    pos = np.zeros((256, 2), np.float32)
    active = np.ones(256, bool)
    space = np.zeros(256, np.int32)
    radius = np.full(256, 100.0, np.float32)
    enters, _, dropped = eng.step(pos, active, space, radius)
    assert dropped == 0
    got = pairs_to_setlist(enters, 256)
    assert all(len(got[i]) == 255 for i in range(256))


def test_negative_coordinates():
    eng = engine()
    pos, active, space, radius = make_world(256, 150, seed=3)
    pos = pos - 800.0  # straddle the origin
    enters, _, _ = eng.step(pos, active, space, radius)
    got = pairs_to_setlist(enters, 256)
    want = brute_force_sets(pos, active, space, radius)
    assert got == want


@pytest.mark.parametrize("backend", ["jnp", "pallas_interpret"])
def test_chunked_drain_small_buffer(backend):
    """max_events far below the first-tick enter storm: the chunked drain
    must page through MANY chunks (rank-based on the pallas path) and
    deliver every event exactly once."""
    p = NeighborParams(
        capacity=256, cell_size=100.0, grid_x=16, grid_z=16,
        space_slots=4, cell_capacity=64, max_events=64,
    )
    eng = NeighborEngine(p, backend=backend)
    eng.reset()
    pos, active, space, radius = make_world(256, 200, seed=0)
    enters, leaves, _ = eng.step(pos, active, space, radius)
    got = pairs_to_setlist(enters, 256)
    want = brute_force_sets(pos, active, space, radius)
    assert got == want
    # No duplicates across chunks, and the storm genuinely paged (>2 chunks).
    total = sum(len(s) for s in want)
    assert len(enters) == total
    assert total > 3 * p.max_events


def test_pallas_single_space_slot():
    """space_slots=1 (the headline bench config after the empty-slab fix)
    through the REAL kernel path (interpret): grid dim 1 on the slab axis
    must produce oracle-exact events — this shape had no coverage and
    chip day would otherwise run it first on hardware."""
    p = NeighborParams(
        capacity=256, cell_size=100.0, grid_x=16, grid_z=16,
        space_slots=1, cell_capacity=64, max_events=65536,
    )
    eng = NeighborEngine(p, backend="pallas_interpret")
    ref = NeighborEngine(p, backend="jnp")
    eng.reset()
    ref.reset()
    rng = np.random.default_rng(13)
    pos, active, space, radius = make_world(256, 220, seed=13, n_spaces=1)
    for tick in range(3):
        enters, leaves, dropped = eng.step(pos, active, space, radius)
        e2, l2, d2 = ref.step(pos, active, space, radius)
        assert dropped == d2 == 0
        assert pairs_to_setlist(enters, 256) == pairs_to_setlist(e2, 256)
        assert pairs_to_setlist(leaves, 256) == pairs_to_setlist(l2, 256)
        if tick == 0:
            want = brute_force_sets(pos, active, space, radius)
            assert pairs_to_setlist(enters, 256) == want
        pos = np.clip(
            pos + rng.normal(0, 20, pos.shape), 0, 1600
        ).astype(np.float32)


DRAIN_WORLD = dict(
    capacity=256, cell_size=100.0, grid_x=16, grid_z=16, space_slots=4,
    cell_capacity=64,
)


# 8 and 64 force storm paging; 8192 covers the non-paging shape (> any
# event count this world produces) without the compile cost of a
# production-sized budget. At 256 rows 8 takes the search row-find, 64
# and 8192 the step row-find.
@pytest.mark.parametrize("max_events, steps", [
    (8, False), (64, True), (8192, True),
], ids=["8", "64", "8192"])
def test_drain_matches_jnp(max_events, steps):
    """The Pallas engine's event stream, paged at ``max_events``, is the
    jnp engine's unpaged stream pair for pair and in the same order (row,
    then halo cell, then lane), so every budget gives the same stream."""
    assert _row_find_steps(DRAIN_WORLD["capacity"], max_events) is steps
    eng = NeighborEngine(NeighborParams(max_events=max_events, **DRAIN_WORLD),
                         backend="pallas_interpret")
    ref = NeighborEngine(NeighborParams(max_events=8192, **DRAIN_WORLD),
                         backend="jnp")
    eng.reset()
    ref.reset()
    rng = np.random.default_rng(11)
    pos, active, space, radius = make_world(256, 200, seed=7)
    for tick in range(4):
        got = eng.step(pos, active, space, radius)
        want = ref.step(pos, active, space, radius)
        for which in (0, 1):
            np.testing.assert_array_equal(
                np.asarray(got[which]), np.asarray(want[which]),
                err_msg=f"tick {tick} {'enters leaves'.split()[which]}")
        pos = pos + rng.uniform(-30, 30, pos.shape).astype(np.float32)


def test_drain_crowded_row():
    """One entity whose events fill several word groups (a group is one
    halo cell's 128 lanes) and several words of one group: 24 neighbours
    share its cell (lanes 0-24, three words) and others sit in three more
    cells of its 3x3 block. Paged 16 events at a time, so its row
    straddles pages; each tick's events are the brute-force diff."""
    from goworld_tpu.ops.neighbor import bins_reference

    n = 64
    p = NeighborParams(capacity=n, cell_size=100.0, grid_x=8, grid_z=8,
                       space_slots=1, cell_capacity=64, max_events=16)
    rng = np.random.default_rng(21)
    pos = rng.uniform(0, 800, (n, 2)).astype(np.float32)
    pos[0] = (195.0, 195.0)  # near the corner of cells (1, 1) and (2, 2)
    pos[1:25] = rng.uniform(110, 190, (24, 2))  # its own cell, in range
    pos[25:31] = (205.0, 150.0) + rng.uniform(0, 20, (6, 2))
    pos[31:37] = (150.0, 205.0) + rng.uniform(0, 20, (6, 2))
    pos[37:40] = (205.0, 205.0) + rng.uniform(0, 20, (3, 2))
    active = np.ones(n, bool)
    space = np.zeros(n, np.int32)
    radius = np.full(n, 99.0, np.float32)
    cx, cz, _ = bins_reference(p, pos, space)
    nbrs = brute_force_sets(pos, active, space, radius)[0]
    cells = {(int(cx[j]), int(cz[j])) for j in nbrs}
    assert len(nbrs) >= 36 and len(cells) >= 4
    assert sum(1 for j in nbrs if (cx[j], cz[j]) == (cx[0], cz[0])) > 16

    eng = NeighborEngine(p, backend="pallas_interpret")
    eng.reset()
    before = [set() for _ in range(n)]
    for tick in range(3):
        enters, leaves, dropped = eng.step(pos, active, space, radius)
        assert dropped == 0
        now = brute_force_sets(pos, active, space, radius)
        assert pairs_to_setlist(enters, n) == [
            a - b for a, b in zip(now, before)], tick
        assert pairs_to_setlist(leaves, n) == [
            b - a for a, b in zip(now, before)], tick
        assert len(enters) == sum(len(x) for x in pairs_to_setlist(enters, n))
        before = now
        pos = (pos + rng.uniform(-40, 40, pos.shape)).astype(np.float32)
        pos[0] = (195.0, 195.0)


def _decode_strip(p, packed, cx, cz, sm, table, gx_ext):
    """numpy: every set bit of the packed mask in rank order, as (row,
    other) through the strip slab's cell arithmetic (x not wrapped)."""
    out = []
    for r in range(packed.shape[0]):
        for c in range(packed.shape[1] * 16):
            if (int(packed[r, c // 16]) >> (c % 16)) & 1:
                hc, lane = divmod(c, LANES)
                czz = (cz[r] + hc // 3 - 1) % p.grid_z
                cxx = cx[r] + hc % 3 - 1
                bucket = (sm[r] * p.grid_z + czz) * gx_ext + cxx
                out.append((r, table[bucket * LANES + lane]))
    return np.asarray(out, np.int32).reshape(-1, 2)


def test_drain_strip_slab():
    """``_drain_bits`` on a strip slab (``gx_ext``, ``wrap_x=False``, own
    rows fewer than ``capacity``), paged, against a numpy decode of the
    same packed mask; ranks past the total read the sentinel."""
    import functools

    import jax
    import jax.numpy as jnp

    from goworld_tpu.ops.neighbor import _drain_bits

    p = NeighborParams(capacity=256, cell_size=100.0, grid_x=16, grid_z=6,
                       space_slots=2, cell_capacity=64, max_events=64)
    rows, gx_ext, page = 40, 9, 64
    rng = np.random.default_rng(5)
    bits = rng.random((rows, 72 * 16)) < 0.01
    bits[3, ::5] = True  # a crowded row: every group, every word
    bits[7] = False
    packed = np.zeros((rows, 72), np.int64)
    for b in range(16):
        packed |= bits[:, b::16].astype(np.int64) << b
    packed = packed.astype(np.uint32).view(np.int32)
    cx = rng.integers(1, gx_ext - 1, rows).astype(np.int32)
    cz = rng.integers(0, p.grid_z, rows).astype(np.int32)
    sm = rng.integers(0, p.space_slots, rows).astype(np.int32)
    table = rng.integers(0, p.capacity, p.space_slots * p.grid_z * gx_ext
                         * LANES).astype(np.int32)
    want = _decode_strip(p, packed, cx, cz, sm, table, gx_ext)
    drain = jax.jit(functools.partial(
        _drain_bits, p, max_events=page, gx_ext=gx_ext, wrap_x=False))
    args = [jnp.asarray(a) for a in (packed, cx, cz, sm, table)]
    chunks = []
    for start in range(0, len(want) + page, page):
        pairs, total = drain(*args, jnp.int32(start))
        assert int(total) == len(want)
        chunks.append(np.asarray(pairs))
    got = np.concatenate(chunks)
    np.testing.assert_array_equal(got[:len(want)], want)
    assert (got[len(want):] == p.capacity).all()


def _row_of_rank_ref(counts, start, max_events):
    """numpy row-of-rank and the mask of ranks below the total."""
    starts = np.cumsum(counts) - counts
    j = start + np.arange(max_events)
    return np.searchsorted(starts, j, "right") - 1, j < counts.sum()


def _sparse_counts(n, seed):
    rng = np.random.default_rng(seed)
    return np.where(rng.random(n) < 0.7, 0, rng.integers(1, 5, n))


ROW_OF_RANK_CASES = {
    # name: (row counts, start rank, max_events)
    "zero_rows_start_middle_end": ([0, 0, 3, 0, 2, 0, 0, 1, 0, 0], 0, 8),
    "start_inside_a_row": ([2, 5, 0, 3, 1], 3, 4),
    "start_at_total": ([1, 2, 3], 6, 4),
    "start_past_total": ([1, 2, 3], 9, 4),
    "events_beyond_total": ([0, 4, 0, 1, 0], 2, 32),
    "events_far_below_rows": (_sparse_counts(1000, 3), 611, 5),
    "sparse_page": (_sparse_counts(500, 4), 17, 64),
}


@pytest.mark.parametrize("steps", [True, False], ids=["step", "search"])
@pytest.mark.parametrize("case", list(ROW_OF_RANK_CASES))
def test_row_of_rank_matches_searchsorted(case, steps, monkeypatch):
    """Both row-find formulations give numpy's row of every rank below the
    total, and a row in range for the ranks past it."""
    import jax.numpy as jnp

    from goworld_tpu.ops import neighbor

    counts, start, max_events = ROW_OF_RANK_CASES[case]
    counts = np.asarray(counts, np.int32)
    monkeypatch.setattr(neighbor, "_row_find_steps", lambda n, e: steps)
    cum = jnp.cumsum(jnp.asarray(counts))
    row = np.asarray(neighbor._row_of_rank(
        jnp.asarray(counts), cum, cum - counts, jnp.int32(start), max_events))
    want, valid = _row_of_rank_ref(counts, start, max_events)
    assert row.shape == (max_events,) and row.dtype == np.int32
    assert ((row >= 0) & (row < len(counts))).all()
    np.testing.assert_array_equal(row[valid], want[valid])


@pytest.mark.parametrize("n_rows, max_events, steps", [
    (128_000, 65_536, True),  # one chip at 102,400 entities, and its pager
    (512_000, 16_384, False),  # entity-sharded, 409,600 entities on 4 chips
    (256, 8, False),
    (256, 64, True),
])
def test_row_find_shape_rule(n_rows, max_events, steps):
    """Step when its n_rows scatter updates are no more than search's
    max_events * ceil(log2(n_rows + 1)) gathers."""
    assert _row_find_steps(n_rows, max_events) is steps


@pytest.mark.slow
def test_table_sort_fallback_branch_matches_oracle():
    """_build_table's argsort fallback — taken when (num_buckets+1)*capacity
    overflows the fused single-array sort's int32 space — must produce the
    same event streams as the fused branch. Production's largest grids
    (cell_100 sweep at 102k entities) run THIS branch, so it needs coverage
    beyond the small-grid configs every other test uses (code-review r4)."""
    p = NeighborParams(
        capacity=1024, cell_size=100.0, grid_x=512, grid_z=512,
        space_slots=8, cell_capacity=4, max_events=65536,
    )
    assert (p.num_buckets + 1) * p.capacity >= 2**31  # really the fallback
    eng = NeighborEngine(p, backend="jnp")
    eng.reset()
    rng = np.random.default_rng(21)
    pos = rng.uniform(0, 51200.0, (1024, 2)).astype(np.float32)
    active = rng.random(1024) < 0.9
    space = rng.integers(0, 5, 1024).astype(np.int32)
    radius = np.full(1024, 100.0, np.float32)
    enters, _, dropped = eng.step(pos, active, space, radius)
    assert dropped == 0
    got = pairs_to_setlist(enters, 1024)
    want = brute_force_sets(pos, active, space, radius)
    assert got == want


def test_radius_exceeding_cell_size_rejected():
    eng = engine()
    pos, active, space, radius = make_world(256, 10, seed=5)
    radius[:] = 150.0  # > cell_size 100 → 3x3 gather would miss neighbors
    with pytest.raises(ValueError, match="cell_size"):
        eng.step(pos, active, space, radius)


def test_grid_capacity_drop_reported():
    """More entities in one cell than cell_capacity: dropped count surfaces
    via the engine diagnostics (entities become invisible, never silently)."""
    p = NeighborParams(
        capacity=256, cell_size=100.0, grid_x=16, grid_z=16,
        space_slots=4, cell_capacity=16, max_events=65536,
    )
    eng = NeighborEngine(p, backend="jnp")
    eng.reset()
    pos = np.full((256, 2), 50.0, np.float32)  # all in one cell
    active = np.ones(256, bool)
    space = np.zeros(256, np.int32)
    radius = np.full(256, 90.0, np.float32)
    _, _, dropped = eng.step(pos, active, space, radius)
    assert dropped == 256 - 16  # cell holds 16 of 256
    assert eng.last_grid_dropped == 240


def test_drop_window_event_consistency():
    """Entities dropped by cell overflow are invisible (validity includes
    grid visibility), and the event stream must remain consistent across the
    drop window: host sets reconstructed from events always equal the
    oracle-with-visibility, with no stale pairs left behind."""
    p = NeighborParams(
        capacity=64, cell_size=100.0, grid_x=8, grid_z=8,
        space_slots=2, cell_capacity=8, max_events=16384,
    )
    eng = NeighborEngine(p, backend="jnp")
    eng.reset()
    rng = np.random.default_rng(11)
    n = 64
    active = np.ones(n, bool)
    space = np.zeros(n, np.int32)
    radius = np.full(n, 100.0, np.float32)
    pos = rng.uniform(0, 800, (n, 2)).astype(np.float32)
    cur = [set() for _ in range(n)]
    saw_drop = False
    for tick in range(12):
        if tick % 3 == 1:
            # Cram half the world into one cell → guaranteed overflow.
            pos[: n // 2] = rng.uniform(10, 90, (n // 2, 2)).astype(np.float32)
        else:
            pos = rng.uniform(0, 800, (n, 2)).astype(np.float32)
        enters, leaves, dropped = eng.step(pos, active, space, radius)
        saw_drop |= dropped > 0
        apply_events(cur, enters, leaves)
        # Oracle with visibility: recompute which entities made it into the
        # grid (stable argsort order = first-come per cell).
        vis = _visible_mask(p, pos, active, space)
        want = brute_force_sets(pos, vis, space, radius)
        assert cur == want, f"tick {tick}: stale/missing pairs after drops"
    assert saw_drop, "test never exercised a drop window"


def _visible_mask(p, pos, active, space):
    """Replicates the engine's deterministic first-come-per-cell visibility
    (binning via the shared numpy mirror, neighbor.bins_reference)."""
    from goworld_tpu.ops.neighbor import bins_reference

    cx, cz, sm = bins_reference(p, pos, space)
    bucket = (sm * p.grid_z + cz) * p.grid_x + cx
    vis = np.zeros(len(pos), bool)
    counts: dict[int, int] = {}
    order = np.argsort(np.where(active, bucket, p.num_buckets), kind="stable")
    for i in order:
        if not active[i]:
            continue
        b = int(bucket[i])
        c = counts.get(b, 0)
        if c < p.cell_capacity:
            vis[i] = True
            counts[b] = c + 1
    return vis


def test_determinism():
    pos, active, space, radius = make_world(256, 200, seed=4)
    e1, e2 = engine(), engine()
    a, _, _ = e1.step(pos, active, space, radius)
    b, _, _ = e2.step(pos, active, space, radius)
    assert np.array_equal(a, b)


def test_step_async_pipeline_matches_sync():
    """Depth-2 pipelining (dispatch t+1 before collecting t) must deliver the
    exact same event stream as synchronous stepping."""
    eng_sync, eng_pipe = engine(), engine()
    rng = np.random.default_rng(3)
    pos, active, space, radius = make_world(256, 220, seed=3)
    vel = rng.normal(0, 30.0, pos.shape).astype(np.float32)

    sync_stream, pipe_stream = [], []
    pending = None
    for t in range(8):
        enters, leaves, _ = eng_sync.step(pos, active, space, radius)
        sync_stream.append((sorted(map(tuple, enters)), sorted(map(tuple, leaves))))
        nxt = eng_pipe.step_async(pos, active, space, radius)
        if pending is not None:
            e2, l2, _ = pending.collect()
            pipe_stream.append((sorted(map(tuple, e2)), sorted(map(tuple, l2))))
        pending = nxt
        pos = np.clip(pos + vel, 0, 1500).astype(np.float32)
    e2, l2, _ = pending.collect()
    pipe_stream.append((sorted(map(tuple, e2)), sorted(map(tuple, l2))))
    assert sync_stream == pipe_stream


def test_wait_device_then_collect_matches_sync():
    """wait_device() (the bench's post-step drain-latency seam) must not
    perturb the event stream: step_async + wait_device + collect == step."""
    eng_sync, eng_wait = engine(), engine()
    pos, active, space, radius = make_world(256, 220, seed=5)
    rng = np.random.default_rng(5)
    for _ in range(4):
        e1, l1, _ = eng_sync.step(pos, active, space, radius)
        pend = eng_wait.step_async(pos, active, space, radius)
        pend.wait_device()
        assert pend.is_ready()
        e2, l2, _ = pend.collect()
        assert sorted(map(tuple, e1)) == sorted(map(tuple, e2))
        assert sorted(map(tuple, l1)) == sorted(map(tuple, l2))
        pos = np.clip(pos + rng.normal(0, 30.0, pos.shape), 0, 1500).astype(
            np.float32)


# --- Pallas path (interpret mode = the kernel itself, CPU-executed) ---------

PALLAS_PARAMS = NeighborParams(
    capacity=128, cell_size=100.0, grid_x=4, grid_z=4,
    space_slots=2, cell_capacity=64, max_events=8192,
)


def test_pallas_kernel_matches_jnp_reference():
    e1 = NeighborEngine(PALLAS_PARAMS, backend="jnp")
    e2 = NeighborEngine(PALLAS_PARAMS, backend="pallas_interpret")
    e1.reset()
    e2.reset()
    rng = np.random.default_rng(2)
    pos = rng.uniform(0, 400, (128, 2)).astype(np.float32)
    active = np.zeros(128, bool)
    active[:100] = True
    space = rng.integers(0, 2, 128).astype(np.int32)
    radius = np.full(128, 100.0, np.float32)

    def canon(pairs):
        return sorted(map(tuple, np.asarray(pairs).tolist()))

    for tick in range(4):
        pos = np.clip(
            pos + rng.normal(0, 20, pos.shape).astype(np.float32), 0, 400
        ).astype(np.float32)
        a1 = e1.step(pos, active, space, radius)
        a2 = e2.step(pos, active, space, radius)
        assert canon(a1[0]) == canon(a2[0]), f"tick {tick} enters differ"
        assert canon(a1[1]) == canon(a2[1]), f"tick {tick} leaves differ"
        assert a1[2] == a2[2], f"tick {tick} dropped differ"


def test_pallas_kernel_oracle_and_drops():
    """Pallas path against the brute-force oracle, including an overflow
    tick (cell_capacity < occupants) where both paths must agree on the
    visibility-folded semantics."""
    p = NeighborParams(
        capacity=64, cell_size=100.0, grid_x=4, grid_z=4,
        space_slots=2, cell_capacity=8, max_events=8192,
    )
    e1 = NeighborEngine(p, backend="jnp")
    e2 = NeighborEngine(p, backend="pallas_interpret")
    e1.reset()
    e2.reset()
    rng = np.random.default_rng(5)
    active = np.ones(64, bool)
    space = np.zeros(64, np.int32)
    radius = np.full(64, 80.0, np.float32)
    cur = [set() for _ in range(64)]
    saw_drop = False
    for tick in range(6):
        if tick == 2:
            pos = np.full((64, 2), 50.0, np.float32)  # everyone in one cell
        else:
            pos = rng.uniform(0, 400, (64, 2)).astype(np.float32)
        a1 = e1.step(pos, active, space, radius)
        a2 = e2.step(pos, active, space, radius)
        saw_drop |= a1[2] > 0
        assert sorted(map(tuple, a1[0].tolist())) == sorted(map(tuple, a2[0].tolist()))
        assert sorted(map(tuple, a1[1].tolist())) == sorted(map(tuple, a2[1].tolist()))
        assert a1[2] == a2[2]
        apply_events(cur, a1[0], a1[1])
        vis = _visible_mask(p, pos, active, space)
        want = brute_force_sets(pos, vis, space, radius)
        assert cur == want, f"tick {tick}"
    assert saw_drop


def test_pallas_drift_into_overflow_emits_leaves():
    """Entities DRIFT (small per-tick displacement — the single-launch fast
    path's territory) until one cell exceeds cell_capacity. The dropped
    entity's neighbors must still receive their leave events, which only the
    two-launch path can emit (the dropped entity is absent from the current
    table entirely) — i.e. ``fast`` must be vetoed by ``dropped_c > 0``
    (code-review r3 finding: teleport-based drop tests always forced the
    slow path via the displacement guard, leaving this hole untested)."""
    p = NeighborParams(
        capacity=64, cell_size=100.0, grid_x=4, grid_z=4,
        space_slots=2, cell_capacity=8, max_events=8192,
    )
    e1 = NeighborEngine(p, backend="jnp")
    e2 = NeighborEngine(p, backend="pallas_interpret")
    e1.reset()
    e2.reset()
    rng = np.random.default_rng(11)
    active = np.ones(64, bool)
    space = np.zeros(64, np.int32)
    radius = np.full(64, 60.0, np.float32)
    # 12 entities ringed just outside one cell, drifting INTO it (cap 8);
    # everyone else far away and static.
    pos = np.full((64, 2), 350.0, np.float32)
    pos[:12] = 50.0 + rng.uniform(-45.0, 45.0, (12, 2)).astype(np.float32)
    pos[:12, 0] += 60.0  # start in the neighboring cell
    cur = [set() for _ in range(64)]
    saw_drop = False
    for tick in range(16):
        a1 = e1.step(pos, active, space, radius)
        a2 = e2.step(pos, active, space, radius)
        saw_drop |= a1[2] > 0
        assert sorted(map(tuple, a1[0].tolist())) == sorted(map(tuple, a2[0].tolist())), f"tick {tick} enters"
        assert sorted(map(tuple, a1[1].tolist())) == sorted(map(tuple, a2[1].tolist())), f"tick {tick} leaves"
        assert a1[2] == a2[2], f"tick {tick} dropped"
        apply_events(cur, a1[0], a1[1])
        vis = _visible_mask(p, pos, active, space)
        want = brute_force_sets(pos, vis, space, radius)
        assert cur == want, f"tick {tick} interest sets"
        # drift: ~8 units/tick toward the target cell — well under the
        # fast-path displacement bound (cell 100, radius 60 -> D <= 20).
        pos[:12, 0] -= 8.0
    assert saw_drop, "scenario never overflowed the cell"


def test_pallas_cell_capacity_cap():
    with pytest.raises(ValueError, match="cell_capacity"):
        NeighborEngine(
            NeighborParams(
                capacity=64, cell_size=100.0, grid_x=4, grid_z=4,
                space_slots=2, cell_capacity=LANES + 1, max_events=64,
            ),
            backend="pallas_interpret",
        )


@pytest.mark.parametrize("backend", ["jnp", "pallas_interpret"])
def test_mid_run_reset_reenters_cleanly(backend):
    """Freeze/restore re-entry: reset() mid-run must behave exactly like a
    fresh engine — full enter storm, no stale carried state (the pallas
    path carries the previous grid in engine state since round 3)."""
    p = NeighborParams(
        capacity=128, cell_size=100.0, grid_x=8, grid_z=8,
        space_slots=2, cell_capacity=32, max_events=8192,
    )
    eng = NeighborEngine(p, backend=backend)
    eng.reset()
    pos, active, space, radius = make_world(128, 100, seed=3, world=700)
    for _ in range(3):
        eng.step(pos, active, space, radius)
        pos = np.clip(pos + 11.0, 0, 700).astype(np.float32)

    eng.reset()  # restore re-entry
    e1, l1, _ = eng.step(pos, active, space, radius)

    fresh = NeighborEngine(p, backend=backend)
    fresh.reset()
    e2, l2, _ = fresh.step(pos, active, space, radius)
    assert pairs_to_setlist(e1, 128) == pairs_to_setlist(e2, 128)
    assert len(l1) == len(l2) == 0  # nothing to leave after a reset


@pytest.mark.parametrize("backend", ["jnp", "pallas_interpret"])
def test_pipelined_step_async_matches_sync(backend):
    """The bench's production loop: dispatch tick t+1 BEFORE collecting
    tick t (one in-flight PendingStep). Must produce the identical stream —
    in particular the pallas path's carried grid arrays are referenced by
    the in-flight step's paging context and must not be clobbered."""
    p = NeighborParams(
        capacity=128, cell_size=100.0, grid_x=8, grid_z=8,
        space_slots=2, cell_capacity=32, max_events=64,  # tiny → paging too
    )
    sync_eng = NeighborEngine(p, backend=backend)
    pipe_eng = NeighborEngine(p, backend=backend)
    sync_eng.reset()
    pipe_eng.reset()
    rng = np.random.default_rng(21)
    pos, active, space, radius = make_world(128, 110, seed=21, world=700)
    vel = rng.normal(0, 20, pos.shape).astype(np.float32)

    sync_stream, pipe_stream = [], []
    pending = None
    for _ in range(6):
        e1, l1, _ = sync_eng.step(pos, active, space, radius)
        sync_stream.append((sorted(map(tuple, e1)), sorted(map(tuple, l1))))
        nxt = pipe_eng.step_async(pos, active, space, radius)
        if pending is not None:
            e2, l2, _ = pending.collect()
            pipe_stream.append((sorted(map(tuple, e2)), sorted(map(tuple, l2))))
        pending = nxt
        pos = np.clip(pos + vel, 0, 700).astype(np.float32)
    e2, l2, _ = pending.collect()
    pipe_stream.append((sorted(map(tuple, e2)), sorted(map(tuple, l2))))
    assert sync_stream == pipe_stream


@pytest.mark.parametrize("backend", ["jnp", "pallas_interpret"])
def test_meta_dirty_false_reuses_device_meta(backend):
    """meta_dirty=False (positions-only upload) must produce the identical
    event stream as full uploads while active/space/radius are unchanged —
    and the engine state must keep the TRUE meta so a later dirty tick
    diffs correctly."""
    p = PALLAS_PARAMS
    e1 = NeighborEngine(p, backend=backend)
    e2 = NeighborEngine(p, backend=backend)
    e1.reset()
    e2.reset()
    rng = np.random.default_rng(9)
    n = p.capacity
    pos = rng.uniform(0, 400, (n, 2)).astype(np.float32)
    act = np.ones(n, bool)
    act[n // 2:] = False
    spc = (np.arange(n) % 2).astype(np.int32)
    rad = np.full(n, 90.0, np.float32)

    def canon(pairs):
        return sorted(map(tuple, np.asarray(pairs).tolist()))

    a1 = e1.step(pos, act, spc, rad)  # first tick uploads meta on both
    a2 = e2.step(pos, act, spc, rad)
    assert canon(a1[0]) == canon(a2[0])
    for tick in range(3):
        pos = np.clip(
            pos + rng.normal(0, 15, pos.shape).astype(np.float32), 0, 400
        ).astype(np.float32)
        a1 = e1.step(pos, act, spc, rad)
        a2 = e2.step_async(pos, act, spc, rad, meta_dirty=False).collect()
        assert canon(a1[0]) == canon(a2[0]), f"tick {tick} enters"
        assert canon(a1[1]) == canon(a2[1]), f"tick {tick} leaves"
    # Now actually change meta (spawn the dormant half) — a dirty tick must
    # pick it up and both engines agree again.
    act[:] = True
    a1 = e1.step(pos, act, spc, rad)
    a2 = e2.step(pos, act, spc, rad)  # meta_dirty defaults True
    assert canon(a1[0]) == canon(a2[0])
    assert canon(a1[1]) == canon(a2[1])


def test_many_folded_spaces_origin_clusters_no_drops():
    """Dozens of spaces folded into 4 slots, each clustering entities near
    the origin (the universal game-world spawn pattern): the per-space hash
    spreading in _bins must keep bucket occupancy near-uniform — without
    it, every space's origin cells pile onto the same buckets and overflow
    cell_capacity (seen live at 100 bots: 1.6k entities invisible/tick)."""
    p = NeighborParams(
        capacity=2048, cell_size=100.0, grid_x=16, grid_z=16,
        space_slots=4, cell_capacity=64, max_events=65536,
    )
    eng = NeighborEngine(p, backend="jnp")
    eng.reset()
    rng = np.random.default_rng(3)
    n = 2048
    pos = rng.uniform(0, 300, (n, 2)).astype(np.float32)  # all near origin
    active = np.ones(n, bool)
    space = (np.arange(n) % 50).astype(np.int32)  # ~41 entities x 50 spaces
    radius = np.full(n, 100.0, np.float32)
    enters, _, dropped = eng.step(pos, active, space, radius)
    assert dropped == 0, f"{dropped} entities dropped despite spreading"
    got = pairs_to_setlist(enters, n)
    want = brute_force_sets(pos, active, space, radius)
    assert got == want


def test_step_jit_emits_no_donation_warning():
    """Nothing in the step jits donates buffers anymore (no output can
    alias the previous-position input), so lowering a FRESH config must
    not emit jax's 'Some donated buffers were not usable' warning — the
    noise that polluted every multichip dryrun log (ISSUE 2)."""
    import warnings

    # A capacity used nowhere else: the lru-cached jit must actually lower.
    p = NeighborParams(
        capacity=40, cell_size=100.0, grid_x=8, grid_z=8, space_slots=1,
        cell_capacity=8, max_events=128,
    )
    eng = NeighborEngine(p, backend="jnp")
    eng.reset()
    rng = np.random.default_rng(0)
    pos = rng.uniform(0, 700, (40, 2)).astype(np.float32)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        eng.step(pos, np.ones(40, bool), np.zeros(40, np.int32),
                 np.full(40, 50.0, np.float32))
        eng.step(pos + 1.0, np.ones(40, bool), np.zeros(40, np.int32),
                 np.full(40, 50.0, np.float32))
    donated = [w for w in caught if "donated" in str(w.message)]
    assert not donated, [str(w.message) for w in donated]
