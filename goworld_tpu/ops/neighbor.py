"""Batched AOI neighbor engine — the TPU-native hot loop.

What the reference does per entity move (Space.go:253-261 → go-aoi
``Moved(aoi, x, z)`` → synchronous OnEnterAOI/OnLeaveAOI callbacks), this
engine does for *all* entities of *all* spaces in one launch per tick.

Design (round 2): the engine is **event-native**. The reference's AOI (and
round 1's engine) materializes per-entity neighbor *sets* and diffs them;
sets are exactly what a TPU is bad at (variable degree, top-k truncation,
huge [N, candidates] intermediates). But the *product* the host consumes is
the enter/leave event stream — so the engine computes events directly as a
pairwise predicate diff and never materializes a neighbor list at all:

    valid_t(i, j) = av_t(i) ∧ av_t(j) ∧ space_t(i) = space_t(j)
                    ∧ dist_t(i, j) ≤ radius_t(i) ∧ i ≠ j
    enter(t) = valid_t ∧ ¬valid_{t-1}        leave(t) = valid_{t-1} ∧ ¬valid_t

where ``av`` (active-and-visible) folds grid-capacity drops into validity,
keeping the event stream *exactly* consistent for host-side incremental sets
even across drop windows. There is **no max_neighbors truncation**: interest
sets are the exact geometric sets, a superset of go-aoi semantics (which has
a single uniform distance, reference TODO.md:17 — per-entity radius is
supported here).

Enumeration uses two spatial-hash grids per tick, both with **static
shapes**:

- **enter pass** bins entities by their *current* positions: any pair valid
  at t is within radius ≤ cell_size, hence inside the 3×3 cell neighborhood.
- **leave pass** bins by the *previous* positions: any pair valid at t-1 is
  inside the previous grid's 3×3 neighborhood.

Each pass evaluates both epochs' predicates per pair (positions of both
ticks ride along as features), so arbitrarily large per-tick movement —
teleports, cross-game migration (EnterSpace, Entity.go:956-1115) — is exact:
no movement bound, no stale interest.

Two execution paths with identical semantics:

- **Pallas kernel** (TPU): entities packed into a dense per-cell layout
  ``[space_slot, gz, gx, F, 128]`` (the boids layout, ops/boids.py); one
  program per cell DMAs its 3×3 halo block HBM→VMEM, evaluates the pairwise
  predicates for 128 × 1152 pairs on the VPU, and bit-packs the event mask
  16-bits-per-word with integer shift-adds — no [N, candidates] float
  intermediate ever reaches HBM (round 1 shipped ~200 MB × several per
  tick). Around the kernel everything is gathers, cumsums and sorts — no
  large TPU scatters (round 2's feature scatter and nonzero-based drain
  were both scatter-bound).
- **jnp reference** (CPU tests / oracle): the same two-grid pairwise math
  over gathered candidate id matrices.

The engine is a pure function of (previous tick's inputs, current inputs);
device state is just the previous (pos, active, space, radius). Stateless-
per-tick is what keeps freeze/restore and migration semantics intact
(SURVEY.md §5.8): on restart the host simply re-uploads positions and takes
one enter storm.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from goworld_tpu.telemetry import sentinel
from goworld_tpu.telemetry.phases import engine_span

# Launch/trace accounting for every step jit built below; this module
# already owns the process's first jax import, so the persistent
# compile-cache listener installs here too.
sentinel.install_compile_cache_listener()

LANES = 128  # Pallas cell capacity = one TPU lane dimension
_PACK = 16  # event-mask bits packed per i32 word
_F = 8  # feature count (sublane multiple of 8)

# Feature rows in the dense cell layout. Epoch A = the epoch whose positions
# the grid is binned by; epoch B = the other epoch. The kernel computes
# valid_A ∧ ¬valid_B, so the same kernel serves both passes with A/B swapped.
# Empty slots carry NaN in their x rows instead of a separate occupancy row:
# NaN poisons d2 for both the query and candidate side of any pair touching
# an empty slot, and IEEE `NaN <= r2` is false — so 8 rows (one sublane
# tile) do the work 10-gated-to-16 did in round 2, halving feats traffic
# and the kernel's halo DMA.
_FX_A, _FZ_A, _FS_A, _FR_A = 0, 1, 2, 3
_FX_B, _FZ_B, _FS_B, _FR_B = 4, 5, 6, 7


@dataclasses.dataclass(frozen=True)
class NeighborParams:
    """Static configuration of a neighbor engine (shapes are compiled in)."""

    capacity: int = 16384  # max entity slots (N)
    cell_size: float = 100.0  # grid cell side; must be >= max AOI distance
    grid_x: int = 64  # grid extent in cells (wraps modulo)
    grid_z: int = 64
    space_slots: int = 8  # space-id folding slots for the shared grid
    cell_capacity: int = 64  # M: max entities visible per grid cell
    max_events: int = 65536  # enter/leave pairs fetched per host round trip
    # Pallas-drain word-find (identical results, different gather shapes;
    # the row-find before it is chosen by shape, _row_find_steps):
    #   bsearch: binary-search word-find (log2(W) random scalar gathers/event)
    #   grouped: two contiguous-row gathers ([E, G] group cumsums, then
    #            [E, W/G] words) per event
    drain_mode: str = "bsearch"

    def __post_init__(self) -> None:
        if self.drain_mode not in ("bsearch", "grouped"):
            raise ValueError(
                f"drain_mode must be bsearch|grouped, got {self.drain_mode!r}"
            )
        if self.grid_x < 4 or self.grid_z < 4:
            # 3x3 neighborhoods must touch 9 distinct buckets after wrap.
            raise ValueError("grid_x and grid_z must be >= 4")
        if self.capacity % 8 != 0:
            raise ValueError("capacity must be a multiple of 8 (TPU sublanes)")
        # The Pallas drain's flat event-index space is capacity*9*LANES held
        # in int32 (ADVICE r2: overflow above ~1.86M slots must fail loudly).
        if self.capacity * 9 * LANES >= 2**31:
            raise ValueError(
                f"capacity {self.capacity} overflows the int32 event index "
                f"space (capacity * 9 * {LANES} must be < 2^31); shard the "
                f"engine instead (parallel.mesh)"
            )

    @property
    def num_buckets(self) -> int:
        return self.space_slots * self.grid_z * self.grid_x


# --- shared binning ----------------------------------------------------------


def _bins(p: NeighborParams, pos: jax.Array, space: jax.Array):
    """Wrapped (cell_x, cell_z, space_slot) coordinates per entity.

    Spaces sharing a slot are SPREAD across the torus by a per-space hash
    offset (in whole cells): game worlds cluster entities near similar
    coordinates in every space (spawn points at the origin), so without the
    offset, dozens of folded spaces pile their origin cells onto the same
    buckets and overflow cell_capacity (seen live: 1.6k entities dropped
    per tick at 100 bots). The offset is constant per space, so within-
    space geometry — the only thing the pair predicate accepts — is
    untouched.
    """
    cx = jnp.mod(jnp.floor(pos[:, 0] / p.cell_size).astype(jnp.int32), p.grid_x)
    cz = jnp.mod(jnp.floor(pos[:, 1] / p.cell_size).astype(jnp.int32), p.grid_z)
    # Two distinct Knuth-style multiplicative hashes (int32 wraparound is
    # fine — only the low bits survive the mod).
    ox = jnp.mod(space * jnp.int32(-1640531527), p.grid_x)
    oz = jnp.mod(space * jnp.int32(40503), p.grid_z)
    cx = jnp.mod(cx + ox, p.grid_x)
    cz = jnp.mod(cz + oz, p.grid_z)
    sm = jnp.mod(space, p.space_slots)
    return cx, cz, sm


def bins_reference(p: NeighborParams, pos: np.ndarray, space: np.ndarray):
    """Numpy mirror of :func:`_bins` (same hash constants, same int32
    wraparound) for host-side oracles — tests and the dryrun's engineered
    drop-count formula use THIS so a change to the binning scheme has a
    single source of truth."""
    s32 = space.astype(np.int32)
    with np.errstate(over="ignore"):
        ox = (s32 * np.int32(-1640531527)) % np.int32(p.grid_x)
        oz = (s32 * np.int32(40503)) % np.int32(p.grid_z)
    cx = (
        np.floor(pos[:, 0] / p.cell_size).astype(np.int32) % p.grid_x + ox
    ) % p.grid_x
    cz = (
        np.floor(pos[:, 1] / p.cell_size).astype(np.int32) % p.grid_z + oz
    ) % p.grid_z
    sm = s32 % p.space_slots
    return cx, cz, sm


def sorted_ranks(key: jax.Array, n: int, num_buckets: int):
    """Stable sort of bucket keys + within-bucket ranks, shared by the
    neighbor and boids table builds.

    Returns (order, sorted_key, rank): ``order`` is the stable argsort of
    ``key`` (sentinel ``num_buckets`` for inactive rows sorts last),
    ``rank`` the position of each sorted row within its key run.

    Fused single-array sort when ``(num_buckets+1)*n`` fits int32:
    key*n + iota is unique, sorts by (key, iota) — the stable-argsort
    order — and decomposes back without the pair-sort's payload lanes or
    the key[order] regather (the table build was 17.8 ms of the 112 ms
    on-chip tick, 2026-07-30; sort is its dominant term). Ranks come from
    segment boundaries + cummax — O(N) scan instead of searchsorted's
    log(N) gather passes.
    """
    iota = jnp.arange(n, dtype=jnp.int32)
    if (num_buckets + 1) * n < 2**31:
        fused = jnp.sort(key * jnp.int32(n) + iota)
        order = jax.lax.rem(fused, jnp.int32(n))
        sorted_key = fused // jnp.int32(n)
    else:
        order = jnp.argsort(key).astype(jnp.int32)  # stable
        sorted_key = key[order]
    boundary = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), sorted_key[1:] != sorted_key[:-1]]
    )
    first = jax.lax.cummax(jnp.where(boundary, iota, 0))
    return order, sorted_key, iota - first


def sorted_ranks_by(key: jax.Array, tie: jax.Array, n_rows: int):
    """Stable (key, tie) lexicographic sort + within-key-run ranks.

    Like :func:`sorted_ranks`, but ties within a bucket break by ``tie``
    (the entity SLOT id) instead of row position. The spatially sharded
    engine's strip-local table builds use this (parallel/spatial.py): a
    seam cell's rows exist as copies on two shards in different local
    orders, so cell-capacity drop choices must key on something globally
    stable — slot order, which is also exactly the single-device engine's
    row order. Returns (order, sorted_key, rank)."""
    iota = jnp.arange(n_rows, dtype=jnp.int32)
    sorted_key, _, order = jax.lax.sort(
        (key, tie, iota), num_keys=2
    )
    boundary = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), sorted_key[1:] != sorted_key[:-1]]
    )
    first = jax.lax.cummax(jnp.where(boundary, iota, 0))
    return order, sorted_key, iota - first


def _build_table(
    p: NeighborParams, bucket: jax.Array, active: jax.Array, stride: int
):
    """Bin entities into a [num_buckets * stride] slot table.

    Rank-within-bucket is derived from a stable argsort (deterministic).
    Entities beyond ``min(cell_capacity, stride)`` in a cell are dropped —
    invisible this tick, with the drop folded into the validity predicate so
    the event stream stays consistent. Returns
    (table i32[num_buckets*stride] with sentinel N, slot i32[N] with -1 for
    dropped/inactive, dropped_count, order, dst) — order/dst let callers
    scatter per-entity features into the same layout.
    """
    n = p.capacity
    cap = min(p.cell_capacity, stride)
    key = jnp.where(active, bucket, p.num_buckets)
    order, sorted_key, rank = sorted_ranks(key, n, p.num_buckets)
    ok = (sorted_key < p.num_buckets) & (rank < cap)
    dropped = jnp.sum((sorted_key < p.num_buckets) & ~ok).astype(jnp.int32)
    table_size = p.num_buckets * stride
    dst = jnp.where(ok, sorted_key * stride + rank, table_size)
    table = jnp.full((table_size,), n, dtype=jnp.int32)
    table = table.at[dst].set(order.astype(jnp.int32), mode="drop")
    slot_sorted = jnp.where(ok, dst, -1).astype(jnp.int32)
    slot = jnp.zeros((n,), jnp.int32).at[order].set(slot_sorted)
    return table, slot, dropped, order, dst


def _fast_guard(p: NeighborParams, ppos, pact, pspc, prad, pos, act, spc,
                dropped_c):
    """Single-pass eligibility: True when every pair valid in EITHER epoch
    provably sits inside the CURRENT grid's 3x3 halo — no entity
    deactivated, changed space, was capacity-dropped this tick, or moved
    more than (cell_size − r_prev)/2 (two points in cells ≥ 2 apart are
    > cell_size apart, and dist_now(a,b) ≤ r_prev + 2·max_disp for any
    previously-valid pair). Shared by the jnp, pallas and sharded steps."""
    both = pact & act
    deact = jnp.any(pact & ~act)
    spchg = jnp.any(both & (pspc != spc))
    disp = jnp.sqrt(
        jnp.max(jnp.where(both, jnp.sum((pos - ppos) ** 2, axis=1), 0.0))
    )
    prad_max = jnp.max(jnp.where(pact, prad, 0.0))
    return (
        (~deact)
        & (~spchg)
        & (dropped_c == 0)
        & (2.0 * disp + prad_max <= p.cell_size)
    )


def _pair_valid(
    q_av, q_space, q_r2, q_x, q_z, c_av, c_space, c_x, c_z, not_self
):
    """The per-pair interest predicate for one epoch (shared jnp/oracle)."""
    dx = c_x - q_x
    dz = c_z - q_z
    d2 = dx * dx + dz * dz
    return q_av & c_av & (q_space == c_space) & (d2 <= q_r2) & not_self


# --- jnp reference path ------------------------------------------------------


def _gather_cands(p: NeighborParams, table: jax.Array, cx, cz, sm) -> jax.Array:
    """Candidate id matrix [Q, 9*M] from each query's 3x3 cell block."""
    m = p.cell_capacity
    parts = []
    for dz in (-1, 0, 1):
        for dx in (-1, 0, 1):
            cxx = jnp.mod(cx + dx, p.grid_x)
            czz = jnp.mod(cz + dz, p.grid_z)
            b = (sm * p.grid_z + czz) * p.grid_x + cxx
            idx = (b * m)[:, None] + jnp.arange(m, dtype=jnp.int32)[None, :]
            parts.append(table[idx])
    return jnp.concatenate(parts, axis=1)  # [Q, 9M]


def _epoch_mask(
    p: NeighborParams,
    cand: jax.Array,  # i32[Q, 9M] candidate ids (sentinel N)
    q_ids: jax.Array,  # i32[Q] global ids of the queries
    q_pos, q_av, q_space, q_radius,  # query-side epoch arrays, [Q]
    pos, av, space,  # full per-entity epoch arrays, [N]
) -> jax.Array:
    n = p.capacity
    safe = jnp.minimum(cand, n - 1)
    # x and z gathered separately: a trailing dim of 2 would be padded to 128
    # lanes by TPU tiling (64x memory blowup on the [Q, 9M] intermediates).
    not_self = (cand < n) & (cand != q_ids[:, None])
    return _pair_valid(
        q_av[:, None],
        q_space[:, None],
        (q_radius * q_radius)[:, None],
        q_pos[:, 0][:, None],
        q_pos[:, 1][:, None],
        av[safe],
        space[safe],
        pos[:, 0][safe],
        pos[:, 1][safe],
        not_self,
    )


def _step_jnp(
    p: NeighborParams,
    ppos, pact, pspc, prad,  # previous-tick inputs (device state)
    pos, act, spc, rad,  # current-tick inputs
):
    """Two-grid pairwise diff, jnp path. Returns
    (enter_ids [N, 9M], leave_ids [N, 9M], n_enters, n_leaves, dropped)."""
    n = p.capacity
    m = p.cell_capacity
    q_ids = jnp.arange(n, dtype=jnp.int32)

    cxc, czc, smc = _bins(p, pos, spc)
    cxp, czp, smp = _bins(p, ppos, pspc)
    buc_c = (smc * p.grid_z + czc) * p.grid_x + cxc
    buc_p = (smp * p.grid_z + czp) * p.grid_x + cxp
    table_c, slot_c, dropped_c, _, _ = _build_table(p, buc_c, act, m)
    table_p, slot_p, _, _, _ = _build_table(p, buc_p, pact, m)
    av_c = slot_c >= 0
    av_p = slot_p >= 0

    # Enter pass: candidates from the current grid.
    cand_c = _gather_cands(p, table_c, cxc, czc, smc)
    vc = _epoch_mask(p, cand_c, q_ids, pos, av_c, spc, rad, pos, av_c, spc)
    vp_on_c = _epoch_mask(p, cand_c, q_ids, ppos, av_p, pspc, prad, ppos, av_p, pspc)
    enter_mask = vc & ~vp_on_c

    # Single-pass fast path (_fast_guard): the leave mask is just
    # vp_on_c & ~vc over cand_c, both already computed. Other ticks pay the
    # second gather + epoch-mask pair on the previous grid.
    fast = _fast_guard(p, ppos, pact, pspc, prad, pos, act, spc, dropped_c)

    def fast_fn():
        return vp_on_c & ~vc, cand_c

    def slow_fn():
        cand_p = _gather_cands(p, table_p, cxp, czp, smp)
        vp = _epoch_mask(p, cand_p, q_ids, ppos, av_p, pspc, prad,
                         ppos, av_p, pspc)
        vc_on_p = _epoch_mask(p, cand_p, q_ids, pos, av_c, spc, rad,
                              pos, av_c, spc)
        return vp & ~vc_on_p, cand_p

    leave_mask, cand_l = jax.lax.cond(fast, fast_fn, slow_fn)

    enter_ids = jnp.where(enter_mask, cand_c, n)
    leave_ids = jnp.where(leave_mask, cand_l, n)
    n_enters = jnp.sum(enter_mask).astype(jnp.int32)
    n_leaves = jnp.sum(leave_mask).astype(jnp.int32)
    return enter_ids, leave_ids, n_enters, n_leaves, dropped_c


def _drain_ids(ids: jax.Array, n: int, max_events: int, start_flat: jax.Array):
    """Compact one chunk of events from an id matrix.

    ``ids`` is i32[Q, W] with sentinel ``n`` in non-event slots. Returns
    (pairs i32[max_events, 2], flat_positions i32[max_events]) for the first
    ``max_events`` events at flat index >= start_flat. Host pages through by
    passing last_flat+1 as the next start.
    """
    q, w = ids.shape
    total = q * w
    flat = ids.reshape(-1)
    mask = (flat < n) & (jnp.arange(total, dtype=jnp.int32) >= start_flat)
    # Event k lives at the first flat index whose inclusive running count
    # reaches k+1: one O(total) cumsum + max_events binary searches. The
    # nonzero(size=...) formulation this replaces lowers to a total-sized
    # scatter, which XLA:CPU executes serially — 62 ms of the 150 ms
    # pinned-floor tick at [2048, 576]; the cumsum+searchsorted form is
    # ~2 ms there with the identical (index-ascending, total-filled)
    # output contract. total < 2^31 is a NeighborParams invariant, so the
    # int32 cumsum cannot overflow.
    csum = jnp.cumsum(mask.astype(jnp.int32))
    ranks = jnp.arange(1, max_events + 1, dtype=jnp.int32)
    idx = jnp.searchsorted(csum, ranks, side="left").astype(jnp.int32)
    valid = idx < total
    idx = jnp.where(valid, idx, total)
    safe = jnp.minimum(idx, total - 1)
    ent = jnp.where(valid, safe // w, n)
    oth = jnp.where(valid, flat[safe], n)
    return jnp.stack([ent, oth], axis=1), idx


def _pack_out(p: NeighborParams, enter_pairs, enter_idx, leave_pairs, leave_idx,
              n_enters, n_leaves, dropped):
    """Assemble the single packed host readback (ONE fetch per tick).

    out i32[3 + 2*max_events, 2]:
        out[0] = (n_enters, n_leaves)          total event counts
        out[1] = (dropped, 0)                  grid-capacity drop diagnostic
        out[2] = (enter_last_flat, leave_last_flat)  resume cursors
        out[3          : 3+E]  = first E enter pairs (slot, other)
        out[3+E : 3+2E]        = first E leave pairs
    """
    e = p.max_events
    header = jnp.stack(
        [
            jnp.stack([n_enters, n_leaves]),
            jnp.stack([dropped, jnp.int32(0)]),
            jnp.stack([enter_idx[e - 1], leave_idx[e - 1]]),
        ]
    ).astype(jnp.int32)
    return jnp.concatenate([header, enter_pairs, leave_pairs], axis=0)


def _step_packed_jnp(p: NeighborParams, ppos, pact, pspc, prad, pos, act, spc, rad):
    enter_ids, leave_ids, n_e, n_l, dropped = _step_jnp(
        p, ppos, pact, pspc, prad, pos, act, spc, rad
    )
    n = p.capacity
    ep, ei = _drain_ids(enter_ids, n, p.max_events, jnp.int32(0))
    lp, li = _drain_ids(leave_ids, n, p.max_events, jnp.int32(0))
    out = _pack_out(p, ep, ei, lp, li, n_e, n_l, dropped)
    return enter_ids, leave_ids, out


# --- Pallas path -------------------------------------------------------------


def _scatter_feats(p: NeighborParams, dst, order, feats_a, feats_b,
                   gx_ext: int | None = None):
    """Build the dense cell feature layout with ONE row-vector scatter.

    ``order``/``dst`` come from _build_table: sorted entity order and each
    sorted entity's flat slot (or table_size for dropped). All 8 feature
    rows ride a single [N, F] scatter into a NaN-initialized [TS, F] flat
    layout — measured 5x cheaper on-chip than 8 gathers through the table
    (2026-07-30; empty slots inherit NaN x, which is exactly the occupancy
    poisoning the kernel's validity math wants, see the _F comment).

    feats_a = (x, z, space, radius) of the epoch the grid is binned by;
    feats_b = the same four for the other epoch. Returns
    f32[space_slots, gz+2, gx+2, F, LANES] with a torus halo ring.

    ``gx_ext`` generalizes the x extent to a STRIP-LOCAL slab
    (parallel/spatial.py's Pallas tier): the extent already INCLUDES its
    ghost columns — real entities exchanged from the neighbor strips live
    there, so only z gets the torus wrap pad and x gets none. None keeps
    the full-torus layout (both dims wrap-padded).
    """
    gxe = p.grid_x if gx_ext is None else gx_ext
    table_size = p.space_slots * p.grid_z * gxe * LANES
    vals = jnp.stack(
        [f.astype(jnp.float32) for f in feats_a]
        + [f.astype(jnp.float32) for f in feats_b],
        axis=1,
    )  # [N, F]
    flat = jnp.full((table_size, _F), jnp.nan, jnp.float32)
    flat = flat.at[dst].set(vals[order], mode="drop")
    cells = flat.reshape(p.space_slots, p.grid_z, gxe, LANES, _F)
    cells = cells.transpose(0, 1, 2, 4, 3)  # [S, gz, gxe, F, LANES]
    # Halo ring per space slab: torus wrap on z always; on x only for the
    # full-torus layout (a strip slab's x halo holds real ghost rows).
    pad_x = (1, 1) if gx_ext is None else (0, 0)
    return jnp.pad(cells, ((0, 0), (1, 1), pad_x, (0, 0), (0, 0)), mode="wrap")


def _event_kernel(p: NeighborParams, dual: bool, drain_inline: int,
                  cells_hbm, *refs):
    """One program per grid cell: DMA the 3x3 halo block, evaluate
    valid_A ∧ ¬valid_B for all 128 × 1152 pairs, bit-pack the mask.

    ``dual`` additionally emits valid_B ∧ ¬valid_A (the leave mask) into the
    second half of the output words — the single-launch fast path when every
    epoch-B pair is guaranteed to sit inside epoch-A's 3x3 halo
    (_step_pallas's displacement guard).

    ``drain_inline > 0`` additionally DRAINS the masked events inside the
    same launch (ISSUE 19 leg b): a second input plane carries each tabled
    lane's SLOT id and OWN flag, and the kernel appends the (query slot,
    other slot) pair of every own-row event to a compacted pairs output
    through SMEM cursors — exact because the TPU grid executes
    SEQUENTIALLY on a core, so the cursors are plain scalar state. The
    pairs block is (8, LANES) tiles (``pair_tiles``/``untile_pairs``,
    sentinel ``capacity``): enters fill events [0, drain_inline) and, when
    dual, leaves fill [drain_inline, 2*drain_inline). Events past a
    region's budget are not emitted; the caller's authoritative popcount
    header detects the overflow and repages the whole tick from rank 0
    (emission is cell-major, not the XLA drain's row-major rank order, so
    a partial inline window cannot be resumed). Per-event selection is
    VPU-shaped — a masked min-reduce for the next set bit, masked sums for
    the slot scalars, a read-modify-write of one tile for the store —
    because Mosaic lowers no cumsum and stores no scalar to VMEM
    (tests/test_v5e_compile.py compiles it for v5e).

    The halo DMA is double-buffered across grid steps: ~7.7k sequential
    73 KB copies at the headline config are latency-bound, and the serial
    start();wait() of round 2 made that latency ~half the kernel's runtime
    (measured on-chip 2026-07-30); prefetching cell k+1 during cell k's
    pair math hides it.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if drain_inline:
        (so_hbm, out_ref, pairs_ref, scratch, sem, so_scratch, so_sem,
         cur_ref) = refs
    else:
        out_ref, scratch, sem = refs
        so_hbm = pairs_ref = so_scratch = so_sem = cur_ref = None

    s = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    rows = pl.num_programs(1)
    gx = pl.num_programs(2)
    lin = (s * rows + i) * gx + j
    total = pl.num_programs(0) * rows * gx
    slot = jax.lax.rem(lin, 2)
    nslot = jax.lax.rem(lin + 1, 2)

    def halo_copy(idx_lin, buf):
        s2 = idx_lin // (rows * gx)
        r = jax.lax.rem(idx_lin, rows * gx)
        return pltpu.make_async_copy(
            cells_hbm.at[s2, pl.ds(r // gx, 3), pl.ds(jax.lax.rem(r, gx), 3)],
            scratch.at[buf],
            sem.at[buf],
        )

    @pl.when(lin == 0)
    def _():
        halo_copy(lin, slot).start()

    @pl.when(lin + 1 < total)
    def _():
        halo_copy(lin + 1, nslot).start()

    if drain_inline:
        # Slot/own plane of THIS cell's 3x3 block: latency hides under the
        # pair math below (waited only at emission time).
        so_copy = pltpu.make_async_copy(
            so_hbm.at[s, pl.ds(i, 3), pl.ds(j, 3)], so_scratch, so_sem
        )
        so_copy.start()

        @pl.when(lin == 0)
        def _():
            cur_ref[0, 0] = 0
            cur_ref[1, 0] = drain_inline
            pairs_ref[...] = jnp.full(pairs_ref.shape, p.capacity, jnp.int32)

    halo_copy(lin, slot).wait()
    c = scratch[slot]  # [3, 3, F, LANES]
    cand = c.transpose(2, 0, 1, 3).reshape(_F, 9 * LANES)
    q = c[1, 1]  # [F, LANES]

    # Self-pairs: the center cell is candidate block 4 (row-major 3x3).
    lane = jax.lax.broadcasted_iota(jnp.int32, (LANES, 9 * LANES), 0)
    cidx = jax.lax.broadcasted_iota(jnp.int32, (LANES, 9 * LANES), 1)
    not_self = cidx != 4 * LANES + lane

    def valid(fx, fz, fs, fr):
        # Empty slots have NaN x (see _F comment): d2 goes NaN for any pair
        # touching one, and `NaN <= r2` is false — no occupancy rows needed.
        dx = cand[fx][None, :] - q[fx][:, None]
        dz = cand[fz][None, :] - q[fz][:, None]
        d2 = dx * dx + dz * dz
        r2 = (q[fr] * q[fr])[:, None]
        return (
            (q[fs][:, None] == cand[fs][None, :]) & (d2 <= r2) & not_self
        )

    v_a = valid(_FX_A, _FZ_A, _FS_A, _FR_A)
    v_b = valid(_FX_B, _FZ_B, _FS_B, _FR_B)

    # Bit-pack 16 candidate bits per i32 word via TWO half-word MXU matmuls.
    # Round 2's single matmul (weights up to 2^15) lost the LSB of sums near
    # 2^16 on hardware (f32 MXU emulation); round 3's integer shift-add
    # rewrite was exact but needs a [LANES, W, 16] reshape Mosaic's
    # infer-vector-layout rejects ("unsupported shape cast", seen on-chip
    # 2026-07-30). Splitting the word into 8-bit halves keeps the
    # Mosaic-supported matmul shape AND exactness: each half's weights are
    # 2^0..2^7 (exact in bf16) and its per-word sum is <= 255, exactly
    # representable under any MXU accumulation scheme; lo + 256*hi <= 65535
    # is exact in f32 on the VPU.
    w_words = 9 * LANES // _PACK
    c_iota = jax.lax.broadcasted_iota(jnp.int32, (9 * LANES, w_words), 0)
    w_iota = jax.lax.broadcasted_iota(jnp.int32, (9 * LANES, w_words), 1)
    bit = c_iota - w_iota * _PACK  # bit index within the word, or out of range
    half = _PACK // 2
    pmat_lo = jnp.where(
        (bit >= 0) & (bit < half), jnp.exp2(bit.astype(jnp.float32)), 0.0
    )
    pmat_hi = jnp.where(
        (bit >= half) & (bit < _PACK),
        jnp.exp2((bit - half).astype(jnp.float32)),
        0.0,
    )

    def pack(mask):
        mf = mask.astype(jnp.float32)
        lo = jnp.dot(mf, pmat_lo, preferred_element_type=jnp.float32)
        hi = jnp.dot(mf, pmat_hi, preferred_element_type=jnp.float32)
        return (lo + 256.0 * hi).astype(jnp.int32)  # [LANES, W]

    enter = pack(v_a & ~v_b)
    if dual:
        out_ref[0, 0, 0] = jnp.concatenate([enter, pack(v_b & ~v_a)], axis=1)
    else:
        out_ref[0, 0, 0] = enter

    if drain_inline:
        so_copy.wait()
        q_slots = so_scratch[1, 1, 0:1]  # [1, LANES] this cell's slot ids
        # [LANES, 1] query ownership (a lane-to-sublane move Mosaic takes
        # as a square transpose).
        own_col = jnp.transpose(jnp.broadcast_to(
            so_scratch[1, 1, 1:2], (LANES, LANES)))[:, 0:1] > 0
        slots9 = jnp.concatenate(
            [so_scratch[a, b, 0:1] for a in range(3) for b in range(3)]
        )  # [9, LANES] candidate slot ids, halo cell row-major
        il = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
        i9 = jax.lax.broadcasted_iota(jnp.int32, (9, LANES), 0)
        l9 = jax.lax.broadcasted_iota(jnp.int32, (9, LANES), 1)
        ic = jax.lax.broadcasted_iota(jnp.int32, (LANES, 9 * LANES), 1)
        # Bit (query lane r, candidate c) at row-major rank r * 9*LANES + c.
        rank = lane * (9 * LANES) + ic
        tsub = jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 0)
        tlane = jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 1)
        none = jnp.int32(LANES * 9 * LANES)

        def emit(mask, ci, lim):
            """Append every set bit of ``mask`` whose query lane is OWN, in
            row-major order, as a (query slot, other slot) pair, up to the
            region's budget ``lim``."""
            key = jnp.where(mask & own_col, rank, none)
            count = jnp.sum(jnp.where(key < none, 1, 0))
            cur = cur_ref[ci, 0]
            n_emit = jnp.clip(lim - cur, 0, count)

            def body(jj, prev):
                nxt = jnp.min(jnp.where(key > prev, key, none))
                row = nxt // (9 * LANES)
                col = jax.lax.rem(nxt, 9 * LANES)
                qs = jnp.sum(jnp.where(il == row, q_slots, 0))
                other = jnp.sum(jnp.where(
                    (i9 == col // LANES) & (l9 == jax.lax.rem(col, LANES)),
                    slots9, 0))
                e = cur + jj
                t = e // (4 * LANES)
                k2 = 2 * jax.lax.rem(e // LANES, 4)
                at = tlane == jax.lax.rem(e, LANES)
                pairs_ref[t] = jnp.where(
                    at & (tsub == k2), qs,
                    jnp.where(at & (tsub == k2 + 1), other, pairs_ref[t]))
                return nxt

            jax.lax.fori_loop(0, n_emit, body, jnp.int32(-1))
            cur_ref[ci, 0] = cur + n_emit

        emit(v_a & ~v_b, 0, drain_inline)
        if dual:
            emit(v_b & ~v_a, 1, 2 * drain_inline)


def pair_tiles(cap: int) -> int:
    """(8, LANES) tiles of the in-kernel drain's pairs block for ``cap``
    events: event e sits in tile e // 512, lane e % 128, query on sublane
    2 * (e // 128 % 4) and other on the sublane after it."""
    return -(-cap // (4 * LANES))


def untile_pairs(tiles: jax.Array, cap: int) -> jax.Array:
    """The kernel's pairs tiles as i32[2, cap] (row 0 query, row 1 other)."""
    t = tiles.shape[0]
    flat = tiles.reshape(t, 4, 2, LANES).transpose(2, 0, 1, 3)
    return flat.reshape(2, t * 4 * LANES)[:, :cap]


@functools.lru_cache(maxsize=None)
def _compiled_event_kernel(p: NeighborParams, interpret: bool,
                           rows: int | None = None, dual: bool = False,
                           cols: int | None = None, drain_inline: int = 0):
    """``rows`` limits the kernel to a slab of grid rows (cells input is then
    the slab plus its 2 halo rows): the sharded engine launches one slab per
    device (parallel/mesh.py). ``cols`` limits it to a slab of grid COLUMNS
    the same way — the spatially sharded Pallas tier launches one strip-
    local column slab per device (parallel/spatial.py); the kernel body is
    row/column symmetric, so both ride the same program. ``dual`` emits
    enter+leave masks in one launch (words [0, W) enter, [W, 2W) leave).
    ``drain_inline`` adds the in-kernel event drain (see _event_kernel): a
    second input (the i32 slot/own plane, cells geometry with 2 planes in
    place of the F features) and a second output, the compacted pairs
    tiles i32[pair_tiles(cap), 8, LANES] with cap = drain_inline * (2 if
    dual else 1); its constant index map keeps the block VMEM-resident
    across the whole sequential grid."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if rows is None:
        rows = p.grid_z
    if cols is None:
        cols = p.grid_x
    w_words = (9 * LANES // _PACK) * (2 if dual else 1)
    kernel = functools.partial(_event_kernel, p, dual, drain_inline)
    words_spec = pl.BlockSpec(
        (1, 1, 1, LANES, w_words),
        lambda s, i, j: (s, i, j, 0, 0),
        memory_space=pltpu.VMEM,
    )
    words_shape = jax.ShapeDtypeStruct(
        (p.space_slots, rows, cols, LANES, w_words), jnp.int32
    )
    if not drain_inline:
        return pl.pallas_call(
            kernel,
            grid=(p.space_slots, rows, cols),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=words_spec,
            out_shape=words_shape,
            scratch_shapes=[
                pltpu.VMEM((2, 3, 3, _F, LANES), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
            interpret=interpret,
            name="aoi_event_kernel",
        )
    tiles = pair_tiles(drain_inline * (2 if dual else 1))
    return pl.pallas_call(
        kernel,
        grid=(p.space_slots, rows, cols),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(
            words_spec,
            pl.BlockSpec(
                (tiles, 8, LANES), lambda s, i, j: (0, 0, 0),
                memory_space=pltpu.VMEM,
            ),
        ),
        out_shape=(
            words_shape,
            jax.ShapeDtypeStruct((tiles, 8, LANES), jnp.int32),
        ),
        scratch_shapes=[
            pltpu.VMEM((2, 3, 3, _F, LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((3, 3, 2, LANES), jnp.int32),
            pltpu.SemaphoreType.DMA,
            pltpu.SMEM((2, 1), jnp.int32),
        ],
        interpret=interpret,
        name="aoi_event_kernel",
    )


def _row_find_steps(n_rows: int, max_events: int) -> bool:
    """Whether the drain's row-find takes the step formulation at this
    static shape: when one scatter of ``n_rows`` updates is no more work
    than the binary search's ``max_events * ceil(log2(n_rows + 1))``
    gathers.

    On a v5e a scatter of N updates costs about one gather pass over N
    elements: an [E]-wide random gather runs ~7.1 ns an element whatever
    it reads (0.4675 ms at E = 65,536), a 128,000-update scatter ~0.6 ms.
    So the two counts compare directly. The single-chip step and its
    pager (128,000 rows against 65,536 * 17) and the strip engine (own
    rows only) take step; the entity-sharded engine at 409,600 entities
    on 4 chips (512,000 rows against 16,384 * 19) keeps search.
    """
    return n_rows <= max_events * n_rows.bit_length()


def _row_of_rank(row_counts, row_cum, row_starts, start_rank, max_events):
    """i32[max_events]: the row that holds each event rank of
    [start_rank, start_rank + max_events), clipped to [0, n_rows). Ranks
    at or past the total get an arbitrary row; the caller masks them.

    ``row_cum`` / ``row_starts`` are the inclusive / exclusive cumsums of
    ``row_counts``. Two formulations, chosen by ``_row_find_steps``:
    - step: row-of-rank over the contiguous range is a monotone step
      function. Each row whose events intersect the range writes its id
      at its first position in it, and a running max fills forward. At
      most one row straddles ``start_rank`` and the others start at
      distinct ranks, so the positions are distinct; every other row
      aims past the end at ``max_events + row`` and is dropped. The
      targets are then unique, which the scatter is told (0.60 ms a side
      at the single-chip shape on a v5e, against 0.88 ms unhinted).
    - search: ``searchsorted(row_starts, rank, "right") - 1``, a binary
      search of ceil(log2(n_rows + 1)) serial [E] gather passes.
    """
    n_rows = row_counts.shape[0]
    with jax.named_scope("row_find"):
        if _row_find_steps(n_rows, max_events):
            rows = jnp.arange(n_rows, dtype=jnp.int32)
            first_pos = row_starts - start_rank
            intersects = ((row_counts > 0) & (row_cum > start_rank)
                          & (first_pos < max_events))
            target = jnp.where(intersects, jnp.maximum(first_pos, 0),
                               max_events + rows)
            seed = jnp.full((max_events,), -1, jnp.int32).at[target].set(
                rows, mode="drop", unique_indices=True)
            row = jax.lax.cummax(seed)
        else:
            j = start_rank + jnp.arange(max_events, dtype=jnp.int32)
            row = jnp.searchsorted(row_starts, j, side="right") - 1
        return jnp.clip(row.astype(jnp.int32), 0, n_rows - 1)


def _drain_bits(
    p: NeighborParams,
    packed_e: jax.Array,  # i32[N, W] per-entity packed event mask
    cx, cz, sm,  # i32[N] bin coords of the pass's grid
    table: jax.Array,  # i32[num_buckets * LANES] id table of the pass's grid
    start_flat: jax.Array,  # EVENT RANK to resume from (name kept for the
    max_events: int | None = None,  # shared pager call signature)
    gx_ext: int | None = None,  # strip-local x extent (parallel/spatial.py)
    wrap_x: bool = True,  # False: x is a strip slab, halo cols are physical
):
    """Pallas-path drain: extract the (entity, other) pairs for event RANKS
    [start_rank, start_rank + max_events) out of the packed bit mask.

    Hierarchical rank-select instead of ``jnp.nonzero`` (round 2): nonzero's
    ``bincount(cumsum(mask))`` lowering scatter-adds over the full
    N * 9 * LANES flat space (118M elements at the headline config — a
    multi-second TPU scatter). Here the only full-size ops are popcounts
    and per-axis cumsums; each requested event then finds its row
    (``_row_of_rank``, under the nested scope ``row_find``), its word
    (``drain_mode``) and its bit by a 16-wide prefix compare.

    Candidate c of entity i maps to halo cell c // LANES (row-major 3x3) and
    lane c % LANES. Returns (pairs i32[max_events, 2], row_counts' total) —
    paging resumes at start_rank + max_events.

    ``gx_ext``/``wrap_x`` generalize the candidate-cell arithmetic to a
    STRIP-LOCAL slab (parallel/spatial.py): ``cx`` is then the local slab
    column, the bucket space is ``space_slots * grid_z * gx_ext``, and x
    offsets index physical ghost columns instead of wrapping the torus
    (every own query's 3x3 block is inside the slab by the strip
    ownership invariant, so no x clamp is needed). ``packed_e`` may hold
    fewer rows than ``capacity`` there (own rows only); the pair's entity
    side is then a ROW index the caller maps to a slot.
    """
    with jax.named_scope("aoi.drain"):
        if max_events is None:
            max_events = p.max_events
        start_rank = start_flat
        n = p.capacity
        gxl = p.grid_x if gx_ext is None else gx_ext
        pc = jax.lax.population_count(packed_e)  # [N, W]
        row_counts = jnp.sum(pc, axis=1)  # [N]
        row_cum = jnp.cumsum(row_counts)  # inclusive
        row_starts = row_cum - row_counts  # exclusive
        total = row_cum[-1]

        j = start_rank + jnp.arange(max_events, dtype=jnp.int32)
        valid = j < total
        row = _row_of_rank(row_counts, row_cum, row_starts, start_rank,
                           max_events)
        k = j - row_starts[row]  # event rank within its row

        # Word selection by binary search over the row's inclusive word-count
        # cumsum: computed ONCE as [N, W] and probed with ceil(log2(W+1)) flat
        # [E] gathers. (The round-3 predecessor gathered each event's full
        # 72-word row and re-cumsummed it — [E, W] traffic ~7x this, measured
        # on-chip 2026-07-30.)
        nw = pc.shape[1]
        word_cum = jnp.cumsum(pc, axis=1)  # [N, W] inclusive
        if p.drain_mode == "grouped":
            # Two-level select via CONTIGUOUS row gathers: the bsearch mode's
            # ~log2(W) random scalar gathers per event are latency-bound on
            # TPU; here each event pulls its row's [G] group cumsums and the
            # [gsz] words of the chosen group in two row gathers, then finds
            # group/word with wide prefix compares (VPU-friendly).
            # Invariant: word w holds rank k iff word_cum[w] > k and
            # word_cum[w-1] <= k, so index = count of inclusive cumsums <= k.
            gsz = 8
            ng = (nw + gsz - 1) // gsz
            pad = ng * gsz - nw
            # edge-pad: padded words repeat the last cumsum (popcount 0).
            wc_pad = jnp.pad(word_cum, ((0, 0), (0, pad)), mode="edge")
            group_cum = wc_pad[:, gsz - 1 :: gsz]  # [N, G] inclusive per group
            g_rows = group_cum[row]  # [E, G]
            g = jnp.sum((g_rows <= k[:, None]).astype(jnp.int32), axis=1)
            g = jnp.minimum(g, ng - 1)
            # The chosen group's word cumsums per event: [E, gsz].
            idx = (row * (ng * gsz) + g * gsz)[:, None] + jnp.arange(
                gsz, dtype=jnp.int32
            )[None, :]
            wg = wc_pad.reshape(-1)[idx]
            wi = jnp.sum((wg <= k[:, None]).astype(jnp.int32), axis=1)
            w = jnp.minimum(g * gsz + wi, nw - 1)
            ev = jnp.arange(max_events)
            # Exclusive cumsum at w: last word of the previous group when the
            # event is the group's first word, else the group-local neighbor.
            prev_in_group = wg[ev, jnp.maximum(wi - 1, 0)]
            prev_group_end = jnp.where(
                g > 0, g_rows[ev, jnp.maximum(g - 1, 0)], 0)
            word_start = jnp.where(wi > 0, prev_in_group, prev_group_end)
            kk = k - word_start  # set-bit rank within the word
        else:
            wc_flat = word_cum.reshape(-1)
            pc_flat = pc.reshape(-1)
            base = row * nw
            lo = jnp.zeros((max_events,), jnp.int32)
            hi = jnp.full((max_events,), nw, jnp.int32)
            for _ in range(max(1, nw.bit_length())):
                mid = jnp.minimum((lo + hi) // 2, nw - 1)
                gt = wc_flat[base + mid] > k
                hi = jnp.where(gt, mid, hi)
                lo = jnp.where(gt, lo, mid + 1)
            w = jnp.minimum(lo, nw - 1)
            word_start = wc_flat[base + w] - pc_flat[base + w]
            kk = k - word_start  # set-bit rank within the word

        word = packed_e[row, w]
        bits = (word[:, None] >> jnp.arange(_PACK, dtype=jnp.int32)) & 1
        bcum = jnp.cumsum(bits, axis=1)  # inclusive set-bit counts
        b = jnp.sum((bcum <= kk[:, None]).astype(jnp.int32), axis=1)
        b = jnp.minimum(b, _PACK - 1)

        c = w * _PACK + b  # candidate index within the row's 3x3 halo
        hc = c // LANES
        lane = c % LANES
        dzo = hc // 3 - 1
        dxo = hc % 3 - 1
        czz = jnp.mod(cz[row] + dzo, p.grid_z)
        if wrap_x:
            cxx = jnp.mod(cx[row] + dxo, gxl)
        else:
            cxx = cx[row] + dxo  # strip slab: ghost columns are physical
        bucket = (sm[row] * p.grid_z + czz) * gxl + cxx
        other = table[bucket * LANES + lane]
        ent = jnp.where(valid, row, n)
        other = jnp.where(valid, other, n)
        return jnp.stack([ent, other], axis=1), total


def _step_pallas(
    p: NeighborParams, interpret: bool,
    ppos, pact, pspc, prad,  # previous-tick inputs
    pcx, pcz, psm, ptable, pslot, porder, pdst,  # prev tick's CARRIED grid
    pos, act, spc, rad,  # current-tick inputs
):
    """Pallas passes + XLA postlude. The previous grid's bins/table/slot are
    carried in engine state (they were this tick's current grid last tick),
    so only ONE argsort+table build runs per tick.

    Launch strategy (measured on-chip 2026-07-30: the second feats+kernel
    pass was ~88 ms of a 271 ms tick at 102k entities): when NO entity
    deactivated, changed space, was capacity-dropped, or moved more than
    (cell_size − r_prev)/2 since the previous tick, every pair valid in
    EITHER epoch sits inside the 3x3 halo of the CURRENT grid — two points
    in cells ≥ 2 apart are > cell_size apart, and dist_now(a,b) ≤ r_prev +
    2·max_disp for any previously-valid pair — so ONE dual-output launch on
    the current grid yields both masks. Despawn / space-hop / teleport /
    drop ticks take the exact two-launch path (enter on the current grid,
    leave on the previous). Returns the paging contexts, the packed
    readback, and the current grid artifacts for the next carry."""
    kernel = _compiled_event_kernel(p, interpret)
    kernel_dual = _compiled_event_kernel(p, interpret, dual=True)

    # Stage names (jax.named_scope) ride each op's metadata into the
    # compiled program and the profiler's trace: aoi.table, aoi.feats,
    # aoi.guard, aoi.gather, aoi.drain (inside _drain_bits too), aoi.pack;
    # the kernel is named aoi_event_kernel.
    with jax.named_scope("aoi.table"):
        cxc, czc, smc = _bins(p, pos, spc)
        cxp, czp, smp = pcx, pcz, psm
        buc_c = (smc * p.grid_z + czc) * p.grid_x + cxc
        table_c, slot_c, dropped_c, order_c, dst_c = _build_table(
            p, buc_c, act, LANES
        )
        table_p, slot_p = ptable, pslot

    # Each epoch's x row is poisoned by its OWN slot validity: an entity
    # outside epoch E's table (inactive or capacity-dropped that tick) must
    # be invalid under E even when its row is written through the OTHER
    # epoch's table — e.g. a fresh spawn's stale previous position must not
    # suppress its enter event.
    with jax.named_scope("aoi.feats"):
        xs_c = jnp.where(slot_c >= 0, pos[:, 0], jnp.nan)
        xs_p = jnp.where(slot_p >= 0, ppos[:, 0], jnp.nan)
        cur_feats = (xs_c, pos[:, 1], spc, rad)
        prev_feats = (xs_p, ppos[:, 1], pspc, prad)
        cells_c = _scatter_feats(p, dst_c, order_c, cur_feats, prev_feats)

    # dropped_c == 0 is required: a capacity-dropped entity is absent from
    # table_c entirely, so the single-launch path could never see its
    # epoch-B pairs — its neighbors' leave events must come from the
    # previous grid, where it is still tabled (code-review r3 finding).
    with jax.named_scope("aoi.guard"):
        fast = _fast_guard(p, ppos, pact, pspc, prad, pos, act, spc,
                           dropped_c)

    w_words = 9 * LANES // _PACK

    def per_entity(packed_cells, slot):
        with jax.named_scope("aoi.gather"):
            nw = packed_cells.shape[-1]
            flat = packed_cells.reshape(-1, nw)
            safe = jnp.maximum(slot, 0)
            return jnp.where((slot >= 0)[:, None], flat[safe], 0)

    # Each branch returns its PER-ENTITY masks with the grid artifacts the
    # leave mask was computed on (current grid in fast mode, previous
    # otherwise) — the cond unifies them without per-array selects. The
    # slot gather runs INSIDE the branch so the fast path pays exactly one
    # [N, 2W] gather over the dual kernel's output instead of two [N, W]
    # gathers (the gather stage was ~7 ms of the 112 ms on-chip tick).
    def fast_fn():
        pk2 = per_entity(kernel_dual(cells_c), slot_c)  # i32[N, 2W]
        return (pk2[:, :w_words], pk2[:, w_words:],
                cxc, czc, smc, table_c)

    def slow_fn():
        with jax.named_scope("aoi.feats"):
            cells_p = _scatter_feats(p, pdst, porder, prev_feats, cur_feats)
        return (per_entity(kernel(cells_c), slot_c),
                per_entity(kernel(cells_p), slot_p),
                cxp, czp, smp, table_p)

    packed_e, packed_l, lcx, lcz, lsm, ltable = (
        jax.lax.cond(fast, fast_fn, slow_fn)
    )
    with jax.named_scope("aoi.drain"):
        n_enters = jnp.sum(
            jax.lax.population_count(packed_e)).astype(jnp.int32)
        n_leaves = jnp.sum(
            jax.lax.population_count(packed_l)).astype(jnp.int32)

    ep, _ = _drain_bits(p, packed_e, cxc, czc, smc, table_c, jnp.int32(0))
    lp, _ = _drain_bits(p, packed_l, lcx, lcz, lsm, ltable, jnp.int32(0))
    # Rank-based paging resumes at max_events, so the cursor row is unused.
    with jax.named_scope("aoi.pack"):
        zero = jnp.int32(0)
        header = jnp.stack(
            [
                jnp.stack([n_enters, n_leaves]),
                jnp.stack([dropped_c, zero]),
                jnp.stack([zero, zero]),
            ]
        ).astype(jnp.int32)
        out = jnp.concatenate([header, ep, lp], axis=0)
    # Paging context: everything _drain_bits needs for overflow chunks.
    enter_ctx = (packed_e, cxc, czc, smc, table_c)
    leave_ctx = (packed_l, lcx, lcz, lsm, ltable)
    next_grid = (cxc, czc, smc, table_c, slot_c, order_c, dst_c)
    return enter_ctx, leave_ctx, out, next_grid


# --- fused entity logic ------------------------------------------------------
#
# [aoi] fuse_logic (ROADMAP item 2, the AsyncTaichi inter-kernel-fusion
# end-state): per-class pure tick programs (entity/columns.columnar_tick)
# ride the SAME device launch as the AOI step. The fused wrapper never
# changes what the step computes — the diff runs on the dispatched epoch
# exactly as before — it additionally applies each program elementwise to
# the dispatched (pos, y, yaw, columns) and returns the results as extra
# outputs. The host writes them back just before the NEXT dispatch
# (aoi/batched.py _consume_fused), so the program's output becomes the
# next dispatched epoch: logic rides the AOI cadence, trajectories are
# bit-identical to running the same vmapped program host-side after each
# dispatch, and every engine's event-exactness machinery (fast guards,
# carried grids, strip layout) is untouched.


def _fused_program_apply(prog, x, y, z, yaw, dt, cols):
    """One program, vmapped over every row (masking is the caller's)."""
    vfn = jax.vmap(prog.fn, in_axes=(0, 0, 0, 0, None) + (0,) * len(cols))
    return vfn(x, y, z, yaw, dt, *cols)


def _apply_fused_logic(programs, pos, y, yaw, sel, dt, cols):
    """Apply each fused program to its rows (``sel == k+1``; 0 = no
    program). ``cols`` is the flat per-program concatenation of column
    arrays. The Python loop over ``programs`` runs at TRACE time — the
    compiled launch contains only the unrolled elementwise ops. Returns
    (new_pos [N,2], new_y, new_yaw, new_cols tuple)."""
    with jax.named_scope("aoi.logic"):
        x = pos[:, 0]
        z = pos[:, 1]
        new = [x, y, z, yaw]
        out_cols = list(cols)
        off = 0
        for k, prog in enumerate(programs):
            nc = len(prog.columns)
            pc = tuple(cols[off + i] for i in range(nc))
            outs = _fused_program_apply(prog, x, y, z, yaw, dt, pc)
            m = sel == jnp.int32(k + 1)
            for i in range(4):
                new[i] = jnp.where(m, outs[i].astype(new[i].dtype), new[i])
            for i in range(nc):
                base = out_cols[off + i]
                out_cols[off + i] = jnp.where(
                    m, outs[4 + i].astype(base.dtype), base)
            off += nc
        new_pos = jnp.stack([new[0], new[2]], axis=1)
        return new_pos, new[1], new[3], tuple(out_cols)


def _step_packed_fused_jnp(
    p: NeighborParams, programs,
    ppos, pact, pspc, prad, pos, act, spc, rad, y, yaw, sel, dt, *cols,
):
    """The jnp step plus the fused entity logic in one launch (gwlint
    HOT_PATHS: body must stay loop-free — the trace-time program loop
    lives in _apply_fused_logic)."""
    enter_ids, leave_ids, out = _step_packed_jnp(
        p, ppos, pact, pspc, prad, pos, act, spc, rad
    )
    new_pos, new_y, new_yaw, new_cols = _apply_fused_logic(
        programs, pos, y, yaw, sel, dt, cols
    )
    return enter_ids, leave_ids, out, (new_pos, new_y, new_yaw) + new_cols


def _step_packed_fused_pallas(
    p: NeighborParams, interpret: bool, programs,
    ppos, pact, pspc, prad,
    pcx, pcz, psm, ptable, pslot, porder, pdst,
    pos, act, spc, rad, y, yaw, sel, dt, *cols,
):
    """The Pallas step plus the fused entity logic in one launch (the
    logic is jnp elementwise around the kernel; XLA fuses it into the
    same executable — still exactly one dispatch per tick)."""
    enter_ctx, leave_ctx, out, next_grid = _step_pallas(
        p, interpret,
        ppos, pact, pspc, prad,
        pcx, pcz, psm, ptable, pslot, porder, pdst,
        pos, act, spc, rad,
    )
    new_pos, new_y, new_yaw, new_cols = _apply_fused_logic(
        programs, pos, y, yaw, sel, dt, cols
    )
    return enter_ctx, leave_ctx, out, next_grid, (
        (new_pos, new_y, new_yaw) + new_cols
    )


@functools.lru_cache(maxsize=None)
def _jitted_step_packed_fused(params: NeighborParams, backend: str,
                              programs: tuple):
    """One jit per (params, backend, program tuple): the program set is
    part of the compiled launch. Program churn (a new class adopted) is a
    new trace — rare, like a tier jump, and prewarmable
    (NeighborEngine.warmup_fused)."""
    if backend == "jnp":
        fn = functools.partial(_step_packed_fused_jnp, params, programs)
    else:
        fn = functools.partial(
            _step_packed_fused_pallas, params,
            backend == "pallas_interpret", programs,
        )
    return sentinel.SentinelJit(f"aoi_step_fused_{backend}",
                                jax.jit(_named("aoi_step_fused", fn)))


# --- sync cadence tier pass ([sync]; rides the step launch) ------------------
#
# Adaptive per-client sync (ROADMAP item 5): each (subject, watcher)
# interest pair is classified into a sync cadence tier by distance and
# approach rate. The classification is ONE batched sweep over the edge
# list — all clients' range queries amortized into a single gather pass —
# and it rides the SAME device launch as the AOI step, so a steady-state
# tick stays one launch. The formula mirrors entity/slabs.classify_tiers
# (the host fallback used by non-batched backends), pinned equal by
# tests/test_synctier.py's parity oracle.


def _tier_pass(pos, ppos, radius, subj, wat, n_tiers: int,
               near_ratio: float, far_ratio: float):
    """uint8[Ecap] tier per padded edge: subj/wat are int32 slot ids with
    sentinel >= capacity on pad rows (tier 0 there — full rate is the
    conservative default). Distance uses the CURRENT epoch; a pair whose
    distance shrank since the PREVIOUS epoch is approaching and drops one
    tier toward full rate."""
    with jax.named_scope("aoi.tier"):
        n = pos.shape[0]
        valid = (subj >= 0) & (subj < n) & (wat >= 0) & (wat < n)
        s = jnp.clip(subj, 0, n - 1)
        w = jnp.clip(wat, 0, n - 1)
        d = pos[s] - pos[w]
        d2 = d[:, 0] * d[:, 0] + d[:, 1] * d[:, 1]
        pd = ppos[s] - ppos[w]
        pd2 = pd[:, 0] * pd[:, 0] + pd[:, 1] * pd[:, 1]
        r = radius[w]
        r2 = jnp.maximum(r * r, jnp.float32(1e-12))
        ratio = jnp.sqrt(d2 / r2)
        span = max(far_ratio - near_ratio, 1e-9)
        tier = 1 + jnp.floor(
            (ratio - near_ratio) / span * (n_tiers - 1)).astype(jnp.int32)
        tier = jnp.clip(tier, 0, n_tiers - 1)
        tier = jnp.where(ratio <= near_ratio, 0, tier)
        tier = jnp.where(d2 < pd2, jnp.maximum(tier - 1, 0), tier)
        return jnp.where(valid, tier, 0).astype(jnp.uint8)


def _edge_verdicts(p: NeighborParams, out, subj, wat):
    """uint8[2*max_events]: per INLINE event row of the packed ``out``,
    1 = the event is a real edge-state change against the dispatched edge
    snapshot (an enter whose (subj, wat) edge is absent / a leave whose
    edge is present), 0 = a no-op the idempotent interest guards would
    swallow. This is the device half of the fused interest-edge delivery:
    the host decode applies verdict-1 rows through a thin bulk edge
    update and drops verdict-0 rows wholesale (unless the edge churned
    after the snapshot — the host-side delta log re-checks those).

    Keys are ``subj * (capacity+1) + wat`` in int32, so the caller must
    guarantee ``(capacity+1)**2 < 2**31`` (the batched service gates on
    this and falls back to host verdicts otherwise). Pad rows of the
    edge snapshot carry the slot sentinel ``capacity`` on both sides —
    their key is the maximum, so real keys never collide with them."""
    e = p.max_events
    n = p.capacity
    keys = jnp.sort(subj.astype(jnp.int32) * jnp.int32(n + 1)
                    + wat.astype(jnp.int32))
    rows = out[3:3 + 2 * e]
    # Event pairs are (watcher, other); the edge table keys
    # (subject=other, watcher) — see Entity._edge_update.
    k = rows[:, 1] * jnp.int32(n + 1) + rows[:, 0]
    idx = jnp.clip(jnp.searchsorted(keys, k), 0, keys.shape[0] - 1)
    present = keys[idx] == k
    return jnp.concatenate(
        [~present[:e], present[e:]]).astype(jnp.uint8)


@functools.lru_cache(maxsize=None)
def _jitted_step_packed_tiered(params: NeighborParams, backend: str,
                               programs: tuple | None,
                               tier_cfg: tuple | None,
                               edge_cap: int, verdicts: bool = False):
    """The step jit (plain or fused) with the edge-snapshot passes
    attached as extra outputs — still exactly one launch. Two optional
    passes ride here, in output order after the base step outputs:
    the [sync] cadence tier pass (``tier_cfg`` a (n_tiers, near, far)
    tuple; None skips it) and the fused-delivery edge-verdict pass
    (``verdicts=True``). Keyed by ``edge_cap`` (the padded edge-array
    size) ON PURPOSE: edge capacities grow in power-of-two tiers, and a
    fresh lru instance per capacity makes the growth compile a WARM
    trace on a new SentinelJit instead of a steady-state retrace on a
    hot one (telemetry/sentinel.py)."""
    if programs is None:
        if backend == "jnp":
            base = functools.partial(_step_packed_jnp, params)
        else:
            base = functools.partial(
                _step_pallas, params, backend == "pallas_interpret")
    elif backend == "jnp":
        base = functools.partial(_step_packed_fused_jnp, params, programs)
    else:
        base = functools.partial(
            _step_packed_fused_pallas, params,
            backend == "pallas_interpret", programs)
    # Offset of the CURRENT epoch's (pos, ..., radius) within the args
    # after the previous epoch's four: the pallas step additionally
    # carries 7 carried-grid artifacts first.
    off = 0 if backend == "jnp" else 7

    def aoi_step_tiered(subj, wat, ppos, pact, pspc, prad, *rest):
        outs = base(ppos, pact, pspc, prad, *rest)
        if tier_cfg is not None:
            n_tiers, near_ratio, far_ratio = tier_cfg
            outs = outs + (_tier_pass(
                rest[off], ppos, rest[off + 3], subj, wat,
                n_tiers, near_ratio, far_ratio),)
        if verdicts:
            outs = outs + (_edge_verdicts(params, outs[2], subj, wat),)
        return outs

    label = ("aoi_step_tiered_" if tier_cfg is not None
             else "aoi_step_verdict_") + backend
    return sentinel.SentinelJit(label, jax.jit(aoi_step_tiered))


def tier_edge_capacity(n_edges: int) -> int:
    """Padded edge-array size for ``n_edges`` live edges: power-of-two
    tiers from 256 so the tiered jit recompiles only on capacity growth
    (a handful of times over a process's life), never per edge churn."""
    cap = 256
    while cap < n_edges:
        cap *= 2
    return cap


# --- jit wrappers ------------------------------------------------------------


def _named(name: str, fn):
    """``fn`` (a ``functools.partial``) as a function called ``name``: the
    jit's program is then ``jit_<name>`` in the profiler's trace and the
    compile cache, not ``jit__unknown``."""

    def named(*args, **kwargs):
        return fn(*args, **kwargs)

    named.__name__ = named.__qualname__ = name
    return named


@functools.lru_cache(maxsize=None)
def _jitted_step_packed(params: NeighborParams, backend: str):
    if backend == "jnp":
        fn = functools.partial(_step_packed_jnp, params)
    else:
        fn = functools.partial(
            _step_pallas, params, backend == "pallas_interpret"
        )
    # NOTHING is donated. The previous-position arg used to be, but no
    # output of either step shares float32[N,2] layout, so XLA could never
    # alias it — every jit just warned "Some donated buffers were not
    # usable" (the multichip dryrun log flagged it). The carried grid
    # artifacts (pallas args 4-10) must stay undonated regardless: the
    # still-pending previous step's paging context references those exact
    # buffers; likewise the previous meta arrays (act/space/radius), which
    # with ``meta_dirty=False`` are the SAME device buffers as the current
    # epoch's meta.
    return sentinel.SentinelJit(f"aoi_step_{backend}",
                                jax.jit(_named("aoi_step", fn)))


@functools.lru_cache(maxsize=None)
def _jitted_drain_ids(params: NeighborParams):
    return sentinel.SentinelJit("aoi_drain_ids", jax.jit(_named(
        "aoi_drain_ids", functools.partial(
            _drain_ids, n=params.capacity, max_events=params.max_events
        )
    )))


@functools.lru_cache(maxsize=None)
def _jitted_drain_bits(params: NeighborParams):
    return sentinel.SentinelJit(
        "aoi_drain_bits", jax.jit(_named(
            "aoi_drain_bits", functools.partial(_drain_bits, params))))


# --- host-facing engine ------------------------------------------------------


_async_copy_supported: dict[str, bool] = {}


def start_host_copy(arr: jax.Array) -> None:
    """Begin the device→host copy of a packed result, if the platform can.

    Capability is probed once per platform (ADVICE r2: do not classify
    JaxRuntimeError by message substring — wording drifts across jaxlib
    versions). If the probe call raises, async copies are disabled for that
    platform and the copy simply happens synchronously in ``collect()``,
    where any real device-side error surfaces on the blocking read.
    """
    try:
        platform = arr.devices().pop().platform
    except Exception:
        platform = "unknown"
    if not _async_copy_supported.get(platform, True):
        return
    try:
        arr.copy_to_host_async()
    except (NotImplementedError, jax.errors.JaxRuntimeError):
        _async_copy_supported[platform] = False


class PendingStep:
    """An in-flight tick: dispatched to the device, result not yet fetched.

    The device-to-host copy of the packed result starts immediately
    (``copy_to_host_async``); ``collect()`` blocks only on whatever is still
    outstanding. Dispatching tick t+1 before collecting tick t hides the
    fetch RTT behind compute — diffs arrive one tick late, which is the
    engine's documented delivery model anyway (batched.py docstring).
    """

    __slots__ = ("_engine", "_pager", "_out", "_collected", "fused",
                 "tiers", "verdicts", "edge_log")

    def __init__(self, engine: "NeighborEngine", pager, out) -> None:
        self._engine = engine
        self._pager = pager  # pager(which, remaining, start_flat) -> pairs
        self._out = out
        self._collected = False
        # Fused-tick payload, set by the dispatching caller when the step
        # carried entity logic: (programs, sel slot-space snapshot,
        # row→slot perm or None, device output arrays). Consumed exactly
        # once by BatchAOIService._consume_fused before the next dispatch.
        self.fused = None
        # Sync-tier payload ([sync]; set when the step carried the tier
        # pass): (edge_version snapshot, edge count, device tier array).
        # Consumed by BatchAOIService._consume_tiers before the next
        # dispatch; discarded there if the edge table churned meanwhile.
        self.tiers = None
        # Fused-delivery payload: device edge-verdict uint8[2E] array (or
        # None) and the edge delta log that was accumulating when this
        # step's snapshot was taken (aoi/batched.py _deliver_fused).
        self.verdicts = None
        self.edge_log = None
        start_host_copy(out)

    def is_ready(self) -> bool:
        """True when collect() will not block on device compute (the packed
        result is finished; the host copy may still be a memcpy away).
        Callers on a latency-critical thread — the single-threaded game loop
        — poll this to frame-skip instead of stalling (batched.py)."""
        try:
            return bool(self._out.is_ready())
        except AttributeError:  # older jax array types
            return True

    def wait_device(self) -> None:
        """Block until the device step has finished computing the packed
        result (collect() after this times only the host copy + unpack).
        Latency instrumentation seam: the BASELINE p99 diff-latency budget
        is measured from step completion to events-on-host (bench.py)."""
        jax.block_until_ready(self._out)

    def collect(self) -> tuple[np.ndarray, np.ndarray, int]:
        """Fetch (enter_pairs, leave_pairs, dropped); one blocking read.
        Host phases: ``wait`` for the device, ``readback`` of the packed
        result, ``page`` per pager call (telemetry.phases.engine_span)."""
        assert not self._collected, "PendingStep already collected"
        self._collected = True
        eng = self._engine
        p = eng.params
        e = p.max_events
        with engine_span("wait"):
            self._out.block_until_ready()
        with engine_span("readback"):
            out = np.asarray(self._out)  # THE round trip
            n_e, n_l = int(out[0, 0]), int(out[0, 1])
            dropped = int(out[1, 0])
            enter_last, leave_last = int(out[2, 0]), int(out[2, 1])
            enters = out[3:3 + min(n_e, e)]
            leaves = out[3 + e:3 + e + min(n_l, e)]
        # Storm paging (rare): the pallas drain pages by event RANK (resume
        # at e), the jnp drain by flat matrix index (resume after the last
        # drained position).
        rank_paging = eng.backend != "jnp"
        if n_e > e:
            with engine_span("page"):
                more = self._pager(
                    "enter", n_e - e, e if rank_paging else enter_last + 1)
            enters = np.concatenate([enters, more])
        if n_l > e:
            with engine_span("page"):
                more = self._pager(
                    "leave", n_l - e, e if rank_paging else leave_last + 1)
            leaves = np.concatenate([leaves, more])
        eng.last_grid_dropped = dropped
        if dropped:
            from goworld_tpu.utils import gwlog

            gwlog.warnf(
                "AOI grid overflow: %d active entities exceeded cell_capacity"
                "=%d and are invisible this tick; raise cell_capacity, or "
                "raise [aoi] grid/cell_size — the torus covers "
                "grid*cell_size (%.0f) world units, and a wider map FOLDS "
                "distant cells onto shared buckets",
                dropped,
                p.cell_capacity,
                p.grid_x * p.cell_size,
            )
        return enters, leaves, dropped


class NeighborEngine:
    """Stateful wrapper around the jitted step function.

    Usage (one engine per game process; all spaces batched together):

        eng = NeighborEngine(NeighborParams(capacity=1024))
        eng.reset()
        enters, leaves, dropped = eng.step(pos, active, space, radius)

    ``enters`` / ``leaves`` are numpy ``[E, 2]`` arrays of (slot, other_slot)
    pairs — the batched equivalent of the reference's OnEnterAOI/OnLeaveAOI
    callback invocations (Entity.go:227-246).

    ``backend``: "auto" picks the Pallas kernel on TPU and the jnp reference
    path elsewhere; "pallas_interpret" runs the kernel through the Pallas
    interpreter (slow — oracle tests only); "jnp" / "pallas" force a path.
    """

    def __init__(self, params: NeighborParams, backend: str = "auto"):
        if backend == "auto":
            backend = "pallas" if jax.default_backend() == "tpu" else "jnp"
        if backend not in ("jnp", "pallas", "pallas_interpret"):
            raise ValueError(f"unknown backend {backend!r}")
        if backend != "jnp" and params.cell_capacity > LANES:
            raise ValueError(
                f"pallas path supports cell_capacity <= {LANES}, "
                f"got {params.cell_capacity}"
            )
        self.params = params
        self.backend = backend
        self._jit_step = _jitted_step_packed(params, backend)
        if backend == "jnp":
            self._jit_drain = _jitted_drain_ids(params)
        else:
            self._jit_drain = _jitted_drain_bits(params)
        self._state: tuple | None = None
        self.last_grid_dropped = 0

    def reset(self) -> None:
        """Clear device state: the next step sees an all-inactive previous
        tick and emits the full enter storm (freeze/restore re-entry)."""
        n = self.params.capacity
        self._state = (
            jnp.zeros((n, 2), jnp.float32),
            jnp.zeros((n,), jnp.bool_),
            jnp.zeros((n,), jnp.int32),
            jnp.zeros((n,), jnp.float32),
        )
        if self.backend != "jnp":
            # Carried grid artifacts of the (all-inactive) previous tick:
            # sentinel table, -1 slots, all-dropped dst — exactly what
            # _build_table returns for active=False everywhere; bins and
            # order are irrelevant then.
            table_size = self.params.num_buckets * LANES
            self._state = self._state + (
                jnp.zeros((n,), jnp.int32),  # pcx
                jnp.zeros((n,), jnp.int32),  # pcz
                jnp.zeros((n,), jnp.int32),  # psm
                jnp.full((table_size,), n, jnp.int32),  # ptable
                jnp.full((n,), -1, jnp.int32),  # pslot
                jnp.arange(n, dtype=jnp.int32),  # porder
                jnp.full((n,), table_size, jnp.int32),  # pdst
            )

    def carried_epoch(self) -> tuple:
        """The last dispatched (pos, active, space, radius) as numpy in
        SLOT space — the tier-growth reseed contract every engine speaks
        (the spatial engine's device state is row-permuted, so callers
        must not peek at ``_state`` directly)."""
        assert self._state is not None, "call reset() first"
        return tuple(np.asarray(a) for a in self._state[0:4])

    def _page(self, ctx, remaining: int, start_flat: int) -> np.ndarray:
        chunks = []
        start = jnp.int32(start_flat)
        rank_paging = self.backend != "jnp"
        while remaining > 0:
            pairs, aux = self._jit_drain(*ctx, start_flat=start)
            take = min(self.params.max_events, remaining)
            chunks.append(np.asarray(pairs[:take]))
            remaining -= take
            if remaining > 0:
                start = start + take if rank_paging else aux[take - 1] + 1
        return np.concatenate(chunks)

    # The batched service may hand this engine a fused-logic payload
    # (aoi/batched.py _build_logic); sharded variants opt in separately.
    supports_fused_logic = True
    # The batched service may additionally ride the [sync] cadence tier
    # pass on the step launch (step_async tiers=); engines without it
    # fall back to the host classification in entity/slabs.py.
    supports_tier_pass = True

    def step_async(
        self,
        pos: np.ndarray,
        active: np.ndarray,
        space: np.ndarray,
        radius: np.ndarray,
        meta_dirty: bool = True,
        logic: tuple | None = None,
        tiers: tuple | None = None,
    ) -> PendingStep:
        """Dispatch one tick without blocking; collect() fetches the events.

        State advances immediately, so back-to-back step_async calls
        pipeline: tick t+1 computes while tick t's packed result is in
        flight to the host.

        ``meta_dirty=False`` asserts that active/space/radius are unchanged
        since the previous step: the device-resident copies are reused and
        only positions are uploaded (~half the per-tick host→device bytes;
        spawn/despawn/space/radius changes are rare relative to movement).

        ``logic = (programs, sel, y, yaw, dt, cols)`` fuses the per-class
        entity-logic programs into the SAME launch (see the fused-logic
        section above): the AOI diff is computed exactly as without logic,
        and the programs' outputs over the dispatched epoch ride back on
        ``pending.fused`` for the caller to write back before the next
        dispatch. ``sel`` is int32[capacity] (program index + 1, 0 = none),
        ``cols`` the flat per-program column arrays.
        """
        assert self._state is not None, "call reset() first"
        fused_out = None
        tier_out = None
        tier_meta = None
        verdict_out = None
        extra: tuple = ()
        programs: tuple | None = None
        with engine_span("upload"):
            check_radius(self.params, radius, active)
            if self.backend != "jnp":
                check_space_ids(space, active)
            # jnp.array (not asarray): the arrays become next tick's
            # PREVIOUS state, so they must not alias the caller's numpy
            # buffers — on the CPU backend a zero-copy view would silently
            # mutate history when game code updates positions in place.
            if meta_dirty:
                meta = (
                    jnp.array(active, jnp.bool_),
                    jnp.array(space, jnp.int32),
                    jnp.array(radius, jnp.float32),
                )
            else:
                meta = self._state[1:4]
            cur = (jnp.array(pos, jnp.float32),) + meta
            if logic is not None:
                programs, sel, y, yaw, dt, cols = logic
                programs = tuple(programs)
                extra = (
                    jnp.array(y, jnp.float32),
                    jnp.array(yaw, jnp.float32),
                    jnp.array(sel, jnp.int32),
                    jnp.float32(dt),
                ) + tuple(jnp.array(c) for c in cols)
        with engine_span("launch"):
            if tiers is not None:
                # ``tiers = (edge_version, n_edges, subj_pad, wat_pad,
                # (n_tiers, near_ratio, far_ratio)[, want_verdicts])`` —
                # the [sync] cadence tier pass and/or the fused-delivery
                # edge verdict pass ride the SAME launch as the step (+ any
                # fused logic); the outputs are the step outputs plus one
                # uint8 vector per requested pass. A 5-tuple is the legacy
                # tiers-only payload; the 6-tuple may set the tier config
                # to None for a verdicts-only launch.
                if len(tiers) == 5:
                    t_ver, t_n, subj_pad, wat_pad, tcfg = tiers
                    want_verdicts = False
                else:
                    (t_ver, t_n, subj_pad, wat_pad, tcfg,
                     want_verdicts) = tiers
                tier_meta = (t_ver, t_n)
                jit_tiered = _jitted_step_packed_tiered(
                    self.params, self.backend, programs,
                    tuple(tcfg) if tcfg is not None else None,
                    len(subj_pad), want_verdicts,
                )
                outs = jit_tiered(
                    jnp.array(subj_pad, jnp.int32),
                    jnp.array(wat_pad, jnp.int32),
                    *self._state, *cur, *extra,
                )
                if want_verdicts:
                    verdict_out = outs[-1]
                    outs = outs[:-1]
                if tcfg is not None:
                    tier_out = outs[-1]
                    outs = outs[:-1]
            elif logic is not None:
                jit_fused = _jitted_step_packed_fused(
                    self.params, self.backend, programs
                )
                outs = jit_fused(*self._state, *cur, *extra)
            else:
                outs = self._jit_step(*self._state, *cur)
        if self.backend == "jnp":
            if logic is not None:
                enter_ids, leave_ids, out, fused_out = outs
            else:
                enter_ids, leave_ids, out = outs
            next_state = cur
        else:
            if logic is not None:
                enter_ctx, leave_ctx, out, next_grid, fused_out = outs
            else:
                enter_ctx, leave_ctx, out, next_grid = outs
            next_state = cur + next_grid

        if self.backend == "jnp":
            def pager(which, remaining, start):
                ids = enter_ids if which == "enter" else leave_ids
                return self._page((ids,), remaining, start)
        else:
            def pager(which, remaining, start):
                ctx = enter_ctx if which == "enter" else leave_ctx
                return self._page(ctx, remaining, start)

        self._state = next_state
        pending = PendingStep(self, pager, out)
        if fused_out is not None:
            for arr in fused_out:
                start_host_copy(arr)
            pending.fused = (tuple(logic[0]), np.asarray(logic[1]),
                             None, fused_out)
        if tier_out is not None:
            start_host_copy(tier_out)
            pending.tiers = tier_meta + (tier_out,)
        if verdict_out is not None:
            start_host_copy(verdict_out)
            pending.verdicts = verdict_out
        return pending

    def warmup_fused(self, programs: tuple, col_dtypes: tuple) -> None:
        """Compile the fused step jit for ``programs`` WITHOUT touching
        engine state: an all-zero dummy call at full capacity populates
        the lru jit cache so the first real fused dispatch (or the first
        one after a freeze→restore respawn) pays no XLA trace inside the
        game loop. ``col_dtypes`` must match the flat per-program column
        dtypes of the real calls."""
        n = self.params.capacity
        zeros = (
            jnp.zeros((n, 2), jnp.float32),
            jnp.zeros((n,), jnp.bool_),
            jnp.zeros((n,), jnp.int32),
            jnp.zeros((n,), jnp.float32),
        )
        state: tuple = zeros
        if self.backend != "jnp":
            table_size = self.params.num_buckets * LANES
            state = state + (
                jnp.zeros((n,), jnp.int32),
                jnp.zeros((n,), jnp.int32),
                jnp.zeros((n,), jnp.int32),
                jnp.full((table_size,), n, jnp.int32),
                jnp.full((n,), -1, jnp.int32),
                jnp.arange(n, dtype=jnp.int32),
                jnp.full((n,), table_size, jnp.int32),
            )
        extra = (
            jnp.zeros((n,), jnp.float32),  # y
            jnp.zeros((n,), jnp.float32),  # yaw
            jnp.zeros((n,), jnp.int32),  # sel
            jnp.float32(0.0),  # dt
        ) + tuple(jnp.zeros((n,), np.dtype(d)) for d in col_dtypes)
        jit_fused = _jitted_step_packed_fused(
            self.params, self.backend, tuple(programs)
        )
        jax.block_until_ready(jit_fused(*state, *zeros, *extra)[2])

    def warmup_tiered(self, programs: tuple | None, col_dtypes: tuple,
                      tier_cfg: tuple | None, edge_cap: int,
                      verdicts: bool = False) -> None:
        """Compile the tiered step jit (plain or fused variant) WITHOUT
        touching engine state — the warmup_fused analog for the [sync]
        tier pass. The batched service never dispatches an un-compiled
        tiered variant from the game loop (a ~seconds XLA trace there
        froze RPCs, seen live); this populates the lru cache off-thread
        or at boot."""
        n = self.params.capacity
        zeros = (
            jnp.zeros((n, 2), jnp.float32),
            jnp.zeros((n,), jnp.bool_),
            jnp.zeros((n,), jnp.int32),
            jnp.zeros((n,), jnp.float32),
        )
        state: tuple = zeros
        if self.backend != "jnp":
            table_size = self.params.num_buckets * LANES
            state = state + (
                jnp.zeros((n,), jnp.int32),
                jnp.zeros((n,), jnp.int32),
                jnp.zeros((n,), jnp.int32),
                jnp.full((table_size,), n, jnp.int32),
                jnp.full((n,), -1, jnp.int32),
                jnp.arange(n, dtype=jnp.int32),
                jnp.full((n,), table_size, jnp.int32),
            )
        extra: tuple = ()
        if programs:
            extra = (
                jnp.zeros((n,), jnp.float32),
                jnp.zeros((n,), jnp.float32),
                jnp.zeros((n,), jnp.int32),
                jnp.float32(0.0),
            ) + tuple(jnp.zeros((n,), np.dtype(d)) for d in col_dtypes)
        pads = jnp.full((edge_cap,), n, jnp.int32)
        jit_tiered = _jitted_step_packed_tiered(
            self.params, self.backend,
            tuple(programs) if programs else None,
            tuple(tier_cfg) if tier_cfg is not None else None,
            edge_cap, verdicts,
        )
        jax.block_until_ready(
            jit_tiered(pads, pads, *state, *zeros, *extra)[2])

    def fused_trace_count(self, programs: tuple) -> int:
        """Compiled-trace count of the fused step jit for ``programs`` —
        the one-launch regression gate asserts this stays at 1 across
        steady-state ticks (and across a restore after warmup_fused)."""
        jit_fused = _jitted_step_packed_fused(
            self.params, self.backend, tuple(programs)
        )
        try:
            return int(jit_fused._cache_size())
        except Exception:  # pragma: no cover - private-API drift
            return -1

    def step(
        self,
        pos: np.ndarray,
        active: np.ndarray,
        space: np.ndarray,
        radius: np.ndarray,
    ) -> tuple[np.ndarray, np.ndarray, int]:
        """Run one tick; returns (enter_pairs, leave_pairs, dropped) on host.

        One upload batch + ONE blocking readback (the packed result); event
        counts are still unbounded — a mass spawn's "enter storm" pages extra
        chunks beyond the inline max_events.
        """
        return self.step_async(pos, active, space, radius).collect()


def check_space_ids(space: np.ndarray, active: np.ndarray) -> None:
    """The Pallas path carries space ids as f32 cell features; ids >= 2^24
    lose integer precision and distinct spaces could silently compare equal
    (cross-space enter events — ADVICE r2). Reject them loudly."""
    s = np.asarray(space)
    a = np.asarray(active)
    if a.any() and int(s[a].max()) >= (1 << 24):
        raise ValueError(
            f"space id {int(s[a].max())} not exactly representable as f32 "
            f"(>= 2^24); the pallas backend requires space ids < {1 << 24}"
        )


def check_radius(params: NeighborParams, radius: np.ndarray, active: np.ndarray) -> None:
    """The 3x3 cell gather only covers AOI distance <= cell_size: a larger
    radius would silently miss true neighbors, so reject it loudly."""
    r = np.asarray(radius)
    a = np.asarray(active)
    if a.any() and float(r[a].max()) > params.cell_size:
        raise ValueError(
            f"AOI radius {float(r[a].max())} exceeds cell_size "
            f"{params.cell_size}; enlarge cell_size (it must be >= "
            f"the maximum AOI distance)"
        )
