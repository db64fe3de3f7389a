"""Sharded (multi-device) AOI engine must agree exactly with the
single-device engine on identical inputs — run on the virtual 8-device CPU
mesh (conftest.py), the analog of the reference testing its multi-process
cluster on localhost (SURVEY.md §4.3)."""

import numpy as np
import pytest

from goworld_tpu.ops import NeighborEngine, NeighborParams
from goworld_tpu.parallel import ShardedNeighborEngine, make_mesh

PARAMS = NeighborParams(
    capacity=512, cell_size=100.0, grid_x=16, grid_z=16,
    space_slots=4, cell_capacity=64, max_events=8192,
)


def make_world(n, n_active, seed, world=1200.0, n_spaces=3):
    rng = np.random.default_rng(seed)
    pos = rng.uniform(0, world, size=(n, 2)).astype(np.float32)
    active = np.zeros(n, bool)
    active[:n_active] = True
    space = rng.integers(0, n_spaces, size=n).astype(np.int32)
    radius = np.full(n, 100.0, np.float32)
    return pos, active, space, radius


def to_sets(pairs, n):
    out = [set() for _ in range(n)]
    for a, b in pairs:
        out[int(a)].add(int(b))
    return out


@pytest.mark.parametrize("backend", ["jnp", "pallas_interpret"])
def test_sharded_matches_single_device(backend):
    mesh = make_mesh(8)
    single = NeighborEngine(PARAMS, backend="jnp")
    sharded = ShardedNeighborEngine(PARAMS, mesh, backend=backend)
    single.reset()
    sharded.reset()

    rng = np.random.default_rng(7)
    pos, active, space, radius = make_world(512, 400, seed=7)
    for tick in range(5):
        pos = np.clip(
            pos + rng.normal(0, 20, pos.shape), 0, 1500
        ).astype(np.float32)
        e1, l1, d1 = single.step(pos, active, space, radius)
        e2, l2, d2 = sharded.step(pos, active, space, radius)
        assert to_sets(e1, 512) == to_sets(e2, 512), f"enters differ @ tick {tick}"
        assert to_sets(l1, 512) == to_sets(l2, 512), f"leaves differ @ tick {tick}"
        assert d1 == d2


def test_sharded_pipeline_matches_sync():
    """step_async pipelining (round-2 parity with the single-device engine):
    depth-2 dispatch/collect must produce the same event stream, with one
    packed readback per collect."""
    mesh = make_mesh(8)
    eng_sync = ShardedNeighborEngine(PARAMS, mesh)
    eng_pipe = ShardedNeighborEngine(PARAMS, mesh)
    eng_sync.reset()
    eng_pipe.reset()
    rng = np.random.default_rng(13)
    pos, active, space, radius = make_world(512, 450, seed=13)
    vel = rng.normal(0, 25.0, pos.shape).astype(np.float32)

    sync_stream, pipe_stream = [], []
    pending = None
    for t in range(6):
        e1, l1, _ = eng_sync.step(pos, active, space, radius)
        sync_stream.append((sorted(map(tuple, e1)), sorted(map(tuple, l1))))
        nxt = eng_pipe.step_async(pos, active, space, radius)
        if pending is not None:
            e2, l2, _ = pending.collect()
            pipe_stream.append((sorted(map(tuple, e2)), sorted(map(tuple, l2))))
        pending = nxt
        pos = np.clip(pos + vel, 0, 1500).astype(np.float32)
    e2, l2, _ = pending.collect()
    pipe_stream.append((sorted(map(tuple, e2)), sorted(map(tuple, l2))))
    assert sync_stream == pipe_stream


@pytest.mark.parametrize("backend", ["jnp", "pallas_interpret"])
def test_sharded_chunked_drain_small_buffer(backend):
    p = NeighborParams(
        capacity=512, cell_size=100.0, grid_x=16, grid_z=16,
        space_slots=4, cell_capacity=64, max_events=128,
    )
    mesh = make_mesh(8)
    single = NeighborEngine(PARAMS, backend="jnp")  # big buffer reference
    sharded = ShardedNeighborEngine(p, mesh, backend=backend)  # tiny buffer, must chunk
    single.reset()
    sharded.reset()
    pos, active, space, radius = make_world(512, 400, seed=11)
    e1, _, _ = single.step(pos, active, space, radius)
    e2, _, _ = sharded.step(pos, active, space, radius)
    assert to_sets(e1, 512) == to_sets(e2, 512)
    assert len(e1) == len(e2)  # exactly-once across chunks


def test_capacity_must_divide():
    mesh = make_mesh(8)
    with pytest.raises(ValueError):
        ShardedNeighborEngine(
            NeighborParams(capacity=520, grid_x=8, grid_z=8), mesh
        )


@pytest.mark.parametrize("backend", ["jnp", "pallas_interpret"])
def test_sharded_fast_path_parity(backend):
    """Drive the sharded SINGLE-PASS fast path non-trivially: radius 40 with
    ~4-unit/tick drift keeps the displacement guard TRUE (2*disp + r <=
    cell_size) while churn produces nonempty enter AND leave sets every
    tick. The default PARAMS (radius == cell_size) makes the guard false on
    any motion, so without this test the fast branches in
    _sharded_step/_sharded_step_pallas would be invisible to the suite
    (code-review r3 finding)."""
    mesh = make_mesh(8)
    single = NeighborEngine(PARAMS, backend="jnp")
    sharded = ShardedNeighborEngine(PARAMS, mesh, backend=backend)
    single.reset()
    sharded.reset()

    rng = np.random.default_rng(11)
    pos, active, space, radius = make_world(512, 400, seed=11, world=600.0)
    radius = np.full(512, 40.0, np.float32)
    saw_leaves = 0
    for tick in range(5):
        pos = np.clip(
            pos + rng.normal(0, 3, pos.shape), 0, 600
        ).astype(np.float32)
        e1, l1, d1 = single.step(pos, active, space, radius)
        e2, l2, d2 = sharded.step(pos, active, space, radius)
        assert to_sets(e1, 512) == to_sets(e2, 512), f"enters differ @ {tick}"
        assert to_sets(l1, 512) == to_sets(l2, 512), f"leaves differ @ {tick}"
        assert d1 == d2
        saw_leaves += len(l1)
        if tick:
            assert len(e1) > 0  # churn keeps both streams nonempty
    assert saw_leaves > 0, "fast-path trace produced no leaves"


@pytest.mark.slow
def test_pod_1m_sharded_shape_validation():
    """BASELINE config 5 at FULL slot count: the 1,048,576-slot sharded
    engine compiles and steps on the 8-device CPU mesh (VERDICT r3 #6 —
    nothing had ever stepped the 1M configuration). Assertions:

    - sharded == single-device event streams, both ticks (full equality,
      not a sample) — the storm tick pages each shard's chunked drain;
    - an independent numpy brute-force oracle over 256 sampled entities
      (the 'subsampled oracle') agrees with both;
    - zero grid drops at production-shaped density (per-cell lambda=1;
      same-slot spaces whose dense regions hash-collide onto a shared
      bucket stack to lambda=2, still far inside cell_capacity=24 — at
      lambda=4 the 1M-bucket Poisson tail really does overflow: measured
      2 drops in the first run of this test);
    - the 1M config runs the table build's argsort fallback branch
      ((num_buckets+1)*capacity >= 2^31) at its real production scale.

    Scaling note: per-shard memory is the [N/D, 9*cell_capacity] candidate
    block (~113 MB i32 here); a v5e-16 pod shards the same program over 16
    chips with the all-gather riding ICI — the shapes validated here are
    the pod shapes with D=8 instead of 16.
    """
    n = 1_048_576
    n_spaces = 64
    p = NeighborParams(
        capacity=n, cell_size=100.0, grid_x=512, grid_z=512,
        space_slots=4, cell_capacity=24, max_events=524288,
    )
    assert (p.num_buckets + 1) * p.capacity >= 2**31  # argsort fallback
    mesh = make_mesh(8)
    single = NeighborEngine(p, backend="jnp")
    sharded = ShardedNeighborEngine(p, mesh, backend="jnp")
    single.reset()
    sharded.reset()
    rng = np.random.default_rng(9)
    # Each space's population clusters in its own 12800-unit region (game
    # worlds are dense, not uniform over the torus): ~0.8 AOI neighbors
    # per entity -> a ~800k-pair first-tick storm through per-shard paging.
    space = (np.arange(n) % n_spaces).astype(np.int32)
    origin = rng.uniform(0, 51200.0 - 12800.0, (n_spaces, 2)).astype(np.float32)
    pos = (
        origin[space] + rng.uniform(0, 12800.0, (n, 2))
    ).astype(np.float32)
    active = np.ones(n, bool)
    radius = np.full(n, 50.0, np.float32)

    def subsample_oracle(pos, sample):
        """Exact interest sets for the sampled entities, chunked numpy."""
        sets = {}
        for i in sample:
            same = space == space[i]
            d2 = np.sum((pos - pos[i]) ** 2, axis=1)
            members = np.flatnonzero(same & (d2 <= 50.0 * 50.0) & active)
            sets[int(i)] = set(int(j) for j in members if j != i)
        return sets

    sample = rng.choice(n, 256, replace=False)
    for tick in range(2):
        e1, l1, d1 = single.step(pos, active, space, radius)
        e2, l2, d2 = sharded.step(pos, active, space, radius)
        assert d1 == d2 == 0
        assert to_sets(e1, n) == to_sets(e2, n), f"enters differ @ {tick}"
        assert to_sets(l1, n) == to_sets(l2, n), f"leaves differ @ {tick}"
        if tick == 0:
            # The storm must overflow the per-shard inline budget (65,536)
            # so the 1M-scale chunked paging really runs.
            assert len(e1) > p.max_events, (len(e1), p.max_events)
            storm = to_sets(e1, n)
            want = subsample_oracle(pos, sample)
            for i, members in want.items():
                assert storm[i] == members, f"oracle mismatch @ entity {i}"
        pos = np.clip(
            pos + rng.normal(0, 3, pos.shape), 0, 51200.0
        ).astype(np.float32)


@pytest.mark.slow
def test_sharded_structural_at_scale():
    """BASELINE config 5 is 1M entities over a v5e-16 pod; real multi-chip
    hardware isn't reachable here, so validate the STRUCTURE at the largest
    CPU-feasible scale: 65,536 slots sharded over 8 virtual devices, first-
    tick enter storm FORCED through per-shard chunked paging (inline budget
    1,024/shard vs ~2.3k enters/shard), then a drift tick, sharded ==
    single throughout."""
    p = NeighborParams(
        capacity=65536, cell_size=100.0, grid_x=64, grid_z=64,
        space_slots=4, cell_capacity=64, max_events=8192,
    )
    mesh = make_mesh(8)
    single = NeighborEngine(p, backend="jnp")
    sharded = ShardedNeighborEngine(p, mesh)
    single.reset()
    sharded.reset()
    rng = np.random.default_rng(5)
    n = p.capacity
    pos = rng.uniform(0, 6400, (n, 2)).astype(np.float32)
    active = np.ones(n, bool)
    active[n // 2:] = rng.random(n - n // 2) < 0.5
    space = rng.integers(0, 64, n).astype(np.int32)
    radius = np.full(n, 80.0, np.float32)
    for tick in range(2):
        e1, l1, d1 = single.step(pos, active, space, radius)
        e2, l2, d2 = sharded.step(pos, active, space, radius)
        assert d1 == d2
        assert to_sets(e1, n) == to_sets(e2, n), f"enters differ @ {tick}"
        assert to_sets(l1, n) == to_sets(l2, n), f"leaves differ @ {tick}"
        if tick == 0:
            # The storm must overflow the per-shard inline budget so the
            # chunked drain actually pages at this scale.
            assert len(e1) > p.max_events, (len(e1), p.max_events)
        pos = np.clip(pos + rng.normal(0, 3, pos.shape), 0, 6400).astype(np.float32)
