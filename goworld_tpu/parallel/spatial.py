"""Spatially sharded AOI: grid-column strips with halo exchange.

The entity-sharded engine (parallel/mesh.py) all-gathers EVERY feature
array every tick so each device can rebuild the whole world's grid — an
O(N) replicated broadcast plus a replicated N-key sort per device. This
engine shards the *grid* instead: the torus's columns are split into D
contiguous strips, each device owns the entity rows whose cell lies in its
strip, and per tick the only cross-device traffic is a ``ppermute`` of the
boundary-strip rows (cells within one interaction radius of a seam,
covering BOTH epochs so enter/leave diffs at the seam stay exact) to the
two ring neighbors. Communication drops from O(N) to O(boundary), and the
per-tick table build sorts only a strip's rows instead of all N.

Host-side layout (the part jax never sees):

- Entity→shard assignment is recomputed from the slab's ``xz`` columns
  each dispatch with ONE CELL of hysteresis: a row migrates only after its
  cell is a full column past the seam, so seam-straddlers don't thrash.
  The ownership invariant at every dispatch is
  ``cx ∈ [strip_lo - 1, strip_hi]`` (one column of slack each side).
- Strip boundaries come from observed column density — an
  equal-population split re-planned at a slow cadence (and immediately
  when a strip overflows its row budget) — the AoiZora-style
  density-aware placement seed (PAPERS.md).
- Row permutation: device rows ``[d*chunk, (d+1)*chunk)`` hold the slots
  assigned to shard d: those placed in either epoch, then inactive fill,
  in no fixed order. A migrating slot swaps rows with a free row of its
  new strip, and only the swapped rows of the PREVIOUS epoch are written
  on the device, from the host mirror, so the device diff never sees a
  migration as a despawn+spawn — event streams are migration-transparent.
  The layout is rebuilt whole (both epochs re-uploaded) only on the first
  dispatch after ``reset``, after an adopted re-plan, and when a strip
  has too few free rows for the slots moving in.

Exactness contract (same event sets as the single-device engine):

- Each query's 3×3 cell neighborhood, in both epochs, is fully populated
  on its owner: neighbors exchange the rows whose current OR previous
  cell lies within 3 columns of the seam, and strips are kept ≥ 4
  columns wide so one ring hop suffices.
- Cell-capacity drops break ties by SLOT id (ops/neighbor.sorted_ranks_by),
  so a seam cell's surviving set is identical on every shard holding a
  copy — and identical to the single-device engine's.
- Ticks the strip invariants cannot cover — a teleport whose previous
  cell escapes the halo, a halo-budget overflow, a strip whose population
  exceeds its row budget even after a re-plan — fall back to the exact
  all-gather program (parallel/mesh._sharded_step) for that tick, counted
  on ``aoi_shard_fallback_total{reason}``.

Same host interface as the other engines: ``step_async`` returns a
pending with ONE blocking packed readback in ``collect()``, storm paging
beyond the inline budget, and the ``meta_dirty=False`` upload elision
(void here on a dispatch that rebuilds the row layout).

Inline budget: EACH chip keeps ``params.max_events`` events a side
inline (its step, in-kernel drain, storm pager and the exact fallback
alike), not ``max_events / D`` as the entity-sharded tiers do
(parallel/mesh.py, parallel/multihost.py). A seamless world grows with
its chips, so a chip's share of the events does not shrink as chips are
added, and a window that did would overflow and repage every tick.

Two device backends share the halo layout (ISSUE 15):

- **jnp** — strip-local candidate-matrix math (the original tier).
- **pallas / pallas_interpret** — the strip-local KERNEL slab: each
  device scatters its own+ghost rows into a
  ``[space_slots, gz+2, strip_cols+4, F, LANES]`` dense cell layout and
  launches the dual-mask event kernel there, so the kernel grid, the
  table build/sort, and the event drain are all strip-local — the
  all-gather + replicated grid rebuild of mesh._sharded_step_pallas
  never happens on this path (see the "Pallas strip tier" section
  below). Fallback ticks run the exact jnp all-gather program on either
  backend.

Both backends take the seam-free single-pass fast tick: a replicated
guard (per-shard scalars pmax/psum-reduced — ops/neighbor._fast_guard's
eligibility) lets steady-state ticks compute the leave diff on the
CURRENT grid — one combined pass / one dual-output kernel launch —
halving the per-tick candidate math; guard outcomes ride the packed
header as ``last_fast_tick`` / ``aoi_spatial_fast_ticks_total``.

Strip→device placement is topology-aware (AoiZora, PAPERS.md): strips
are ring-ordered by construction, so ``plan_placement`` orders the mesh
devices along a coordinate snake and ring-adjacent strips land on
interconnect-adjacent chips; rigs without device coords keep ring order.
"""

from __future__ import annotations

import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from goworld_tpu import telemetry
from goworld_tpu.ops.neighbor import (
    LANES,
    _PACK,
    NeighborParams,
    _apply_fused_logic,
    _bins,
    _compiled_event_kernel,
    _drain_bits,
    _drain_ids,
    _gather_cands,
    _pair_valid,
    _scatter_feats,
    bins_reference,
    check_radius,
    check_space_ids,
    sorted_ranks_by,
    untile_pairs,
)
from goworld_tpu.telemetry import sentinel
from goworld_tpu.telemetry.phases import engine_span
from goworld_tpu.parallel.mesh import (
    SHARD_AXIS,
    ShardedPendingStep,
    _jitted_sharded_drain,
    _jitted_sharded_step,
    _jitted_sharded_step_fused,
)

# Seam-free single-pass ticks (ISSUE 15): steady-state ticks whose
# replicated guard held, so the leave diff rode the CURRENT grid — one
# combined pass (jnp) / one dual-output kernel launch (pallas) instead of
# two grid passes. Module-scope registration (gwlint R5).
_M_FAST_TICKS = telemetry.counter(
    "aoi_spatial_fast_ticks_total",
    "Spatial-engine ticks served by the seam-free single-pass fast path "
    "(replicated displacement guard held; leave diff rode the current "
    "grid).",
)
# Topology-aware strip→device placement (AoiZora, PAPERS.md): total
# interconnect distance (manhattan over device coords) of the strip ring,
# for the adopted placement vs the naive mesh order it replaced.
_M_RING_DISTANCE = telemetry.gauge(
    "aoi_strip_ring_distance",
    "Sum of interconnect (manhattan coord) distances between ring-adjacent "
    "strip devices, per placement order.",
    ("order",),
)

# Halo feature-block bytes per exchanged row: f32 (px, pz, x, z) + i32
# (pspc, spc, slot) + bool (pact, act). Radius does NOT travel: the pair
# predicate only reads the QUERY side's radius, and queries never leave
# their owner.
HALO_ROW_BYTES = 4 * 4 + 3 * 4 + 2 * 1

# Minimum strip width (columns). 3 is the correctness floor (a 3-column
# halo band must not reach past the adjacent strip); 4 adds one column of
# margin so the band arithmetic never wraps into the same strip twice.
MIN_STRIP_COLS = 4


def _build_table_spatial(p: NeighborParams, bucket, active, slots, chunk):
    """Strip-local table build over the combined (own + ghost) rows.

    Differs from ops/neighbor._build_table in two load-bearing ways: table
    values are COMBINED-ROW indices (sentinel n_rows), and cell-capacity
    ties break by SLOT id — every shard holding a copy of a seam cell
    must drop the same members the single-device engine would.
    Returns (table, in_table bool[n_rows], own_dropped)."""
    n_rows = bucket.shape[0]
    m = p.cell_capacity
    key = jnp.where(active, bucket, p.num_buckets)
    order, sorted_key, rank = sorted_ranks_by(key, slots, n_rows)
    ok = (sorted_key < p.num_buckets) & (rank < m)
    table_size = p.num_buckets * m
    dst = jnp.where(ok, sorted_key * m + rank, table_size)
    table = jnp.full((table_size,), n_rows, dtype=jnp.int32)
    table = table.at[dst].set(order.astype(jnp.int32), mode="drop")
    in_table = jnp.zeros((n_rows,), bool).at[order].set(ok)
    dropped_sorted = (sorted_key < p.num_buckets) & ~ok
    own_dropped = jnp.sum(dropped_sorted & (order < chunk)).astype(jnp.int32)
    return table, in_table, own_dropped


def _exchange_halo(
    p: NeighborParams, n_dev: int,
    ppos_l, pact_l, pspc_l, prad_l,
    pos_l, act_l, spc_l, rad_l,
    slot_l, send_lo_idx, send_hi_idx,
):
    """The halo ``ppermute``: pack both seam bands, exchange with the two
    ring neighbors, and return the combined own+ghost feature arrays
    ([chunk + 2h] rows, own rows first). Shared by the jnp and Pallas
    spatial step bodies — the exchanged bytes are identical on both tiers
    (radius does not travel; ghost queries are never extracted, so their
    radius rows may be zero)."""
    with jax.named_scope("aoi.halo"):
        n = p.capacity
        chunk = pos_l.shape[0]

        def pack_band(idx):
            safe = jnp.minimum(idx, chunk - 1)
            pad = idx >= chunk
            f32b = jnp.stack(
                [ppos_l[safe, 0], ppos_l[safe, 1],
                 pos_l[safe, 0], pos_l[safe, 1]],
                axis=1,
            )
            i32b = jnp.stack(
                [pspc_l[safe], spc_l[safe], jnp.where(pad, n, slot_l[safe])],
                axis=1,
            )
            boolb = jnp.stack([pact_l[safe] & ~pad, act_l[safe] & ~pad],
                              axis=1)
            return f32b, i32b, boolb

        fwd = [(i, (i + 1) % n_dev) for i in range(n_dev)]
        bwd = [(i, (i - 1) % n_dev) for i in range(n_dev)]

        def exchange(blocks, perm):
            return tuple(
                jax.lax.ppermute(b, SHARD_AXIS, perm=perm) for b in blocks
            )

        # from_left = my predecessor's high-seam band; from_right = my
        # successor's low-seam band.
        from_left = exchange(pack_band(send_hi_idx), fwd)
        from_right = exchange(pack_band(send_lo_idx), bwd)

        def unpack(blocks):
            f32b, i32b, boolb = blocks
            return (
                f32b[:, 0:2], f32b[:, 2:4],  # ppos, pos
                i32b[:, 0], i32b[:, 1], i32b[:, 2],  # pspc, spc, slot
                boolb[:, 0], boolb[:, 1],  # pact, act
            )

        gl_ppos, gl_pos, gl_pspc, gl_spc, gl_slot, gl_pact, gl_act = unpack(
            from_left
        )
        gr_ppos, gr_pos, gr_pspc, gr_spc, gr_slot, gr_pact, gr_act = unpack(
            from_right
        )
        h = gl_pos.shape[0]
        zeros_h = jnp.zeros((h,), jnp.float32)
        return (
            jnp.concatenate([pos_l, gl_pos, gr_pos], axis=0),
            jnp.concatenate([ppos_l, gl_ppos, gr_ppos], axis=0),
            jnp.concatenate([act_l, gl_act, gr_act]),
            jnp.concatenate([pact_l, gl_pact, gr_pact]),
            jnp.concatenate([spc_l, gl_spc, gr_spc]),
            jnp.concatenate([pspc_l, gl_pspc, gr_pspc]),
            jnp.concatenate([slot_l, gl_slot, gr_slot]),
            jnp.concatenate([rad_l, zeros_h, zeros_h]),
            jnp.concatenate([prad_l, zeros_h, zeros_h]),
        )


def _fast_guard_strip(p: NeighborParams, ppos_l, pact_l, pspc_l, prad_l,
                      pos_l, act_l, spc_l, dropped_total):
    """The seam-free single-pass guard, replicated across strips: the same
    eligibility as ops/neighbor._fast_guard (no deactivation, no space
    change, zero capacity drops, displacement small enough that every pair
    valid in EITHER epoch sits inside the CURRENT grid's 3x3 halo), with
    the per-shard scalars reduced over the mesh so the ``cond`` resolves
    identically on every shard. Own rows partition the slot space, so the
    local reductions cover every entity exactly once."""
    both = pact_l & act_l
    deact = jnp.any(pact_l & ~act_l).astype(jnp.int32)
    spchg = jnp.any(both & (pspc_l != spc_l)).astype(jnp.int32)
    disp2 = jnp.max(
        jnp.where(both, jnp.sum((pos_l - ppos_l) ** 2, axis=1), 0.0)
    )
    prad_max = jnp.max(jnp.where(pact_l, prad_l, 0.0))
    deact_g = jax.lax.pmax(deact, SHARD_AXIS) > 0
    spchg_g = jax.lax.pmax(spchg, SHARD_AXIS) > 0
    disp_g = jnp.sqrt(jax.lax.pmax(disp2, SHARD_AXIS))
    prad_g = jax.lax.pmax(prad_max, SHARD_AXIS)
    return (
        (~deact_g)
        & (~spchg_g)
        & (dropped_total == 0)
        & (2.0 * disp_g + prad_g <= p.cell_size)
    )


def _spatial_step_impl(
    p: NeighborParams,
    events_inline: int,
    halo_cap: int,
    n_dev: int,
    ppos_l, pact_l, pspc_l, prad_l,
    pos_l, act_l, spc_l, rad_l,
    slot_l,
    send_lo_idx,
    send_hi_idx,
):
    n = p.capacity
    chunk = pos_l.shape[0]
    h = halo_cap
    n_all = chunk + 2 * h

    (pos_all, ppos_all, act_all, pact_all, spc_all, pspc_all, slot_all,
     _, _) = _exchange_halo(
        p, n_dev, ppos_l, pact_l, pspc_l, prad_l,
        pos_l, act_l, spc_l, rad_l, slot_l, send_lo_idx, send_hi_idx,
    )

    cxc, czc, smc = _bins(p, pos_all, spc_all)
    cxp, czp, smp = _bins(p, ppos_all, pspc_all)
    buc_c = (smc * p.grid_z + czc) * p.grid_x + cxc
    buc_p = (smp * p.grid_z + czp) * p.grid_x + cxp
    # Strip-local sorts over chunk + 2h keys — the replicated N-key sorts
    # of the all-gather formulation are what this engine deletes.
    table_c, av_c, own_drop = _build_table_spatial(
        p, buc_c, act_all, slot_all, chunk
    )
    table_p, av_p, _ = _build_table_spatial(
        p, buc_p, pact_all, slot_all, chunk
    )
    dropped_total = jax.lax.psum(own_drop, SHARD_AXIS).astype(jnp.int32)

    q_iota = jnp.arange(chunk, dtype=jnp.int32)

    def emask(cand, q_pos, q_av, q_spc, q_rad, pos_a, av_a, spc_a):
        safe = jnp.minimum(cand, n_all - 1)
        not_self = (cand < n_all) & (cand != q_iota[:, None])
        return _pair_valid(
            q_av[:, None],
            q_spc[:, None],
            (q_rad * q_rad)[:, None],
            q_pos[:, 0][:, None],
            q_pos[:, 1][:, None],
            av_a[safe],
            spc_a[safe],
            pos_a[:, 0][safe],
            pos_a[:, 1][safe],
            not_self,
        )

    # Enter pass: candidates from the current grid, own rows as queries.
    cand_c = _gather_cands(p, table_c, cxc[:chunk], czc[:chunk], smc[:chunk])
    vc = emask(cand_c, pos_l, av_c[:chunk], spc_l, rad_l,
               pos_all, av_c, spc_all)
    vp_on_c = emask(cand_c, ppos_l, av_p[:chunk], pspc_l, prad_l,
                    ppos_all, av_p, pspc_all)
    enter_mask = vc & ~vp_on_c

    # Leave pass: seam-free single-pass fast path (ISSUE 15) when the
    # replicated guard holds — the leave mask is vp_on_c & ~vc over the
    # already-gathered current candidates, skipping the previous grid's
    # candidate gather and both epoch-mask passes (the engine's dominant
    # per-tick FLOPs; both table SORTS stay, av_p feeds vp_on_c). Other
    # ticks pay the full previous-grid pass.
    fast = _fast_guard_strip(
        p, ppos_l, pact_l, pspc_l, prad_l, pos_l, act_l, spc_l,
        dropped_total,
    )

    def fast_fn():
        return vp_on_c & ~vc, cand_c

    def slow_fn():
        cand_p = _gather_cands(
            p, table_p, cxp[:chunk], czp[:chunk], smp[:chunk]
        )
        vp = emask(cand_p, ppos_l, av_p[:chunk], pspc_l, prad_l,
                   ppos_all, av_p, pspc_all)
        vc_on_p = emask(cand_p, pos_l, av_c[:chunk], spc_l, rad_l,
                        pos_all, av_c, spc_all)
        return vp & ~vc_on_p, cand_p

    leave_mask, cand_l = jax.lax.cond(fast, fast_fn, slow_fn)

    def slot_of(cand):
        return slot_all[jnp.minimum(cand, n_all - 1)]

    enter_ids = jnp.where(enter_mask, slot_of(cand_c), n)
    leave_ids = jnp.where(leave_mask, slot_of(cand_l), n)
    n_enters = jnp.sum(enter_mask).astype(jnp.int32)
    n_leaves = jnp.sum(leave_mask).astype(jnp.int32)

    ep, ei = _drain_ids(enter_ids, n, events_inline, jnp.int32(0))
    lp, li = _drain_ids(leave_ids, n, events_inline, jnp.int32(0))

    def slotize(pairs):
        ent = pairs[:, 0]
        ent = jnp.where(
            ent < chunk, slot_l[jnp.minimum(ent, chunk - 1)], n
        )
        return jnp.stack([ent, pairs[:, 1]], axis=1)

    header = jnp.stack(
        [
            jnp.stack([n_enters, n_leaves]),
            jnp.stack([dropped_total, fast.astype(jnp.int32)]),
            jnp.stack([ei[events_inline - 1], li[events_inline - 1]]),
        ]
    ).astype(jnp.int32)
    # Replicated per-shard counts: same storm-paging convergence contract
    # as parallel/mesh._sharded_step (ShardedPendingStep reads them).
    counts_all = jax.lax.all_gather(header[0], SHARD_AXIS)  # [D, 2]
    out = jnp.concatenate(
        [header, counts_all, slotize(ep), slotize(lp)], axis=0
    )
    return enter_ids, leave_ids, out


def _spatial_drain(
    p: NeighborParams, events_inline: int, chunk: int,
    ids_l: jax.Array,  # [chunk, 9M] this shard's SLOT-id event matrix
    slot_l: jax.Array,  # [chunk] row → slot
    start_l: jax.Array,  # [1] resume cursor (local flat index)
):
    n = p.capacity
    pairs, idx = _drain_ids(ids_l, n, events_inline, start_l[0])
    ent = pairs[:, 0]
    ent = jnp.where(ent < chunk, slot_l[jnp.minimum(ent, chunk - 1)], n)
    pairs = jnp.stack([ent, pairs[:, 1]], axis=1)
    return pairs, idx[None]


def _spatial_step_fused_impl(
    p: NeighborParams,
    events_inline: int,
    halo_cap: int,
    n_dev: int,
    programs,
    ppos_l, pact_l, pspc_l, prad_l,
    pos_l, act_l, spc_l, rad_l,
    slot_l,
    send_lo_idx,
    send_hi_idx,
    y_l, yaw_l, sel_l, dt_l, *cols_l,
):
    """The spatial halo-exchange step plus fused entity logic on this
    shard's LOCAL rows. The logic is elementwise per row — it never
    crosses a seam, needs no halo, and leaves every layout invariant of
    the spatial step untouched (the diff runs on the dispatched epoch
    exactly as unfused). Logic inputs/outputs are in ROW-permuted layout:
    the host uploads sel/y/yaw/columns through the same ``perm`` as the
    positions, and writes the outputs back through the dispatch-time perm
    snapshot (a strip migration or re-plan between dispatches therefore
    CANNOT misroute or reset a column — the satellite contract pinned in
    tests/test_spatial.py)."""
    enter_ids, leave_ids, out = _spatial_step_impl(
        p, events_inline, halo_cap, n_dev,
        ppos_l, pact_l, pspc_l, prad_l,
        pos_l, act_l, spc_l, rad_l,
        slot_l, send_lo_idx, send_hi_idx,
    )
    new_pos, new_y, new_yaw, new_cols = _apply_fused_logic(
        programs, pos_l, y_l, yaw_l, sel_l, dt_l[0], cols_l
    )
    return enter_ids, leave_ids, out, (new_pos, new_y, new_yaw) + new_cols


@functools.lru_cache(maxsize=None)
def _jitted_spatial_step_fused(
    params: NeighborParams, mesh: Mesh, events_inline: int, halo_cap: int,
    programs: tuple, n_cols: int,
):
    body = functools.partial(
        _spatial_step_fused_impl, params, events_inline, halo_cap,
        mesh.devices.size, programs,
    )
    spec = P(SHARD_AXIS)
    mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec,) * (15 + n_cols),
        out_specs=(spec, spec, spec, (spec,) * (3 + n_cols)),
    )
    return sentinel.SentinelJit("spatial_step_fused", jax.jit(mapped))


@functools.lru_cache(maxsize=None)
def _jitted_spatial_step(
    params: NeighborParams, mesh: Mesh, events_inline: int, halo_cap: int
):
    body = functools.partial(
        _spatial_step_impl, params, events_inline, halo_cap,
        mesh.devices.size,
    )
    spec = P(SHARD_AXIS)
    mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec,) * 11,
        out_specs=(spec, spec, spec),
    )
    return sentinel.SentinelJit("spatial_step", jax.jit(mapped))


@functools.lru_cache(maxsize=None)
def _jitted_spatial_drain(
    params: NeighborParams, mesh: Mesh, events_inline: int, chunk: int
):
    body = functools.partial(_spatial_drain, params, events_inline, chunk)
    spec = P(SHARD_AXIS)
    mapped = jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=(spec, spec),
    )
    return sentinel.SentinelJit("spatial_drain", jax.jit(mapped))


# Rows one launch of the incremental relayout writes; a tick that moves
# more loops over launches of this one shape, so no move count compiles.
ROW_MOVE_BATCH = 256


def _row_moves(chunk, ppos, pact, pspc, prad, perm, ipay, fpay):
    """Overwrite rows of the sharded previous epoch and row→slot map.
    ``ipay`` int32 [K, 4] = (global row, slot, active, space), ``fpay``
    f32 [K, 3] = (x, z, radius); each shard keeps the rows of its block
    and drops the rest (padding rows are ``capacity``, in no block)."""
    local = ipay[:, 0] - jax.lax.axis_index(SHARD_AXIS) * chunk
    local = jnp.where((local >= 0) & (local < chunk), local, chunk)

    def put(a, v):
        return a.at[local].set(v.astype(a.dtype), mode="drop")

    return (put(ppos, fpay[:, :2]), put(pact, ipay[:, 2] != 0),
            put(pspc, ipay[:, 3]), put(prad, fpay[:, 2]),
            put(perm, ipay[:, 1]))


@functools.lru_cache(maxsize=None)
def _jitted_row_moves(mesh: Mesh, chunk: int):
    spec = P(SHARD_AXIS)
    mapped = jax.shard_map(
        functools.partial(_row_moves, chunk), mesh=mesh,
        in_specs=(spec,) * 5 + (P(), P()), out_specs=(spec,) * 5,
    )
    return sentinel.SentinelJit("spatial_row_moves", jax.jit(mapped))


# --- Pallas strip tier (ISSUE 15) --------------------------------------------
#
# The kernel-tier analog of the jnp halo exchange above: each device
# builds a STRIP-LOCAL dense cell slab over its own+ghost rows and feeds
# the existing dual-mask event kernel (ops/neighbor._event_kernel) a
# [space_slots, gz+2, cols_cap+4, F, LANES] layout instead of a slice of
# a replicated full-torus grid — the kernel grid, the table build/sort,
# and the event drain are all strip-local, and the only cross-device
# traffic is the same seam-band ppermute the jnp tier moves. Column
# geometry per shard (w = this strip's width, all offsets mod grid_x):
#
#   world column:  lo-2  lo-1  lo ... hi-1   hi   hi+1
#   local column:    0     1    2 ...  w+1   w+2   w+3      (lx)
#   role:          ghost  QUERY ...... QUERY QUERY ghost
#
# Own rows may sit one column outside the strip (the hysteresis slack),
# so query columns span [lo-1, hi] and candidate columns [lo-2, hi+1] —
# exactly the 3-column seam bands the halo exchange already ships. The
# slab's x extent is the STATIC cols_cap + 4 (cols_cap caps strip width;
# plan_strips enforces it), z keeps the torus wrap; columns past this
# strip's dynamic width are NaN cells the kernel skims through. Ghost
# rows appear as un-extracted queries; far ghost columns (a ghost's other
# epoch far from the seam) fall outside every own query's 3x3 block, and
# any pair they could carry is > cell_size apart — excluded exactly.


def _build_table_strip(
    p: NeighborParams, bucket, active, slots, num_buckets, chunk
):
    """Strip-local LANES-stride table for the kernel slab. Like
    _build_table_spatial, capacity ties break by SLOT id (seam cells exist
    as copies on two shards — the drop set must be identical everywhere
    and identical to the single-device engine's). Table values are SLOT
    ids (sentinel N) so the bit drain emits pairs directly; ``tpos`` is
    each combined row's flat table position (-1 = dropped/absent), whose
    % LANES is the row's kernel lane. Returns
    (table, tpos, own_dropped, order, dst)."""
    n_rows = bucket.shape[0]
    cap = min(p.cell_capacity, LANES)
    key = jnp.where(active, bucket, num_buckets)
    order, sorted_key, rank = sorted_ranks_by(key, slots, n_rows)
    ok = (sorted_key < num_buckets) & (rank < cap)
    table_size = num_buckets * LANES
    dst = jnp.where(ok, sorted_key * LANES + rank, table_size)
    table = jnp.full((table_size,), p.capacity, dtype=jnp.int32)
    table = table.at[dst].set(slots[order].astype(jnp.int32), mode="drop")
    tpos = jnp.zeros((n_rows,), jnp.int32).at[order].set(
        jnp.where(ok, dst, -1).astype(jnp.int32)
    )
    dropped_sorted = (sorted_key < num_buckets) & ~ok
    own_dropped = jnp.sum(dropped_sorted & (order < chunk)).astype(jnp.int32)
    return table, tpos, own_dropped, order, dst


def _scatter_slotown(p: NeighborParams, dst, order, slot_all, chunk: int,
                     gx_ext: int):
    """Dense slot/own plane for the in-kernel drain (ISSUE 19 leg b):
    the cells-slab geometry with two i32 planes per lane in place of the
    F float features — plane 0 the tabled lane's SLOT id (sentinel
    ``capacity``), plane 1 its OWN flag (row < chunk: ghost rows must not
    emit events; their owner shard emits them). Same one-scatter build and
    z-wrap halo ring as _scatter_feats; x ghost columns are physical."""
    n_rows = slot_all.shape[0]
    table_size = p.space_slots * p.grid_z * gx_ext * LANES
    own = (jnp.arange(n_rows, dtype=jnp.int32) < chunk).astype(jnp.int32)
    vals = jnp.stack([slot_all.astype(jnp.int32), own], axis=1)  # [N, 2]
    flat = jnp.full((table_size, 2), p.capacity, jnp.int32).at[:, 1].set(0)
    flat = flat.at[dst].set(vals[order], mode="drop")
    plane = flat.reshape(p.space_slots, p.grid_z, gx_ext, LANES, 2)
    plane = plane.transpose(0, 1, 2, 4, 3)  # [S, gz, gxe, 2, LANES]
    return jnp.pad(
        plane, ((0, 0), (1, 1), (0, 0), (0, 0), (0, 0)), mode="wrap"
    )


def _spatial_step_pallas_impl(
    p: NeighborParams,
    events_inline: int,
    halo_cap: int,
    n_dev: int,
    interpret: bool,
    cols_cap: int,
    drain_inline: int,
    ppos_l, pact_l, pspc_l, prad_l,
    pos_l, act_l, spc_l, rad_l,
    slot_l,
    send_lo_idx,
    send_hi_idx,
    strip_lo,  # [1] i32: this shard's first owned column
):
    """Per-shard strip+halo Pallas body (see the section comment). Returns
    (enter drain ctx x4, table_c, leave drain ctx x4, table_l, out) —
    the same 11-output contract as parallel/mesh._sharded_step_pallas,
    with drain contexts in strip-local coordinates."""
    n = p.capacity
    chunk = pos_l.shape[0]
    h = halo_cap
    n_all = chunk + 2 * h
    gz = p.grid_z
    gxe = cols_cap + 4  # slab x extent: query cols + 2 ghost cols per side
    qcols = cols_cap + 2  # kernel grid columns (strip + hysteresis slack)
    nb_local = p.space_slots * gz * gxe
    w_words = 9 * LANES // _PACK
    kernel = _compiled_event_kernel(
        p, interpret, rows=gz, cols=qcols, drain_inline=drain_inline
    )
    kernel_dual = _compiled_event_kernel(
        p, interpret, rows=gz, cols=qcols, dual=True,
        drain_inline=drain_inline,
    )

    (pos_all, ppos_all, act_all, pact_all, spc_all, pspc_all, slot_all,
     rad_all, prad_all) = _exchange_halo(
        p, n_dev, ppos_l, pact_l, pspc_l, prad_l,
        pos_l, act_l, spc_l, rad_l, slot_l, send_lo_idx, send_hi_idx,
    )

    cxc, czc, smc = _bins(p, pos_all, spc_all)
    cxp, czp, smp = _bins(p, ppos_all, pspc_all)
    base = strip_lo[0] - 2
    lxc = jnp.mod(cxc - base, p.grid_x)
    lxp = jnp.mod(cxp - base, p.grid_x)
    # Rows outside the slab's column span (a ghost's OTHER epoch far from
    # the seam) are absent from that epoch's strip table — NaN-poisoned
    # like a capacity drop, which is exact: any pair they could carry with
    # an own query is > cell_size apart in that epoch.
    in_c = lxc < gxe
    in_p = lxp < gxe
    buc_c = jnp.where(in_c, (smc * gz + czc) * gxe + lxc, nb_local)
    buc_p = jnp.where(in_p, (smp * gz + czp) * gxe + lxp, nb_local)
    # Strip-local LANES-stride sorts over chunk + 2h keys — the replicated
    # N-row sort + full-grid scatter of the all-gather kernel tier
    # (parallel/mesh._sharded_step_pallas) are what this path deletes.
    table_c, tpos_c, own_drop, order_c, dst_c = _build_table_strip(
        p, buc_c, act_all & in_c, slot_all, nb_local, chunk
    )
    table_p, tpos_p, _, order_p, dst_p = _build_table_strip(
        p, buc_p, pact_all & in_p, slot_all, nb_local, chunk
    )
    dropped_total = jax.lax.psum(own_drop, SHARD_AXIS).astype(jnp.int32)

    # Each epoch's x row poisoned by its OWN table validity
    # (ops/neighbor._step_pallas — fresh spawns must not be suppressed by
    # stale previous positions).
    xs_c = jnp.where(tpos_c >= 0, pos_all[:, 0], jnp.nan)
    xs_p = jnp.where(tpos_p >= 0, ppos_all[:, 0], jnp.nan)
    cur_feats = (xs_c, pos_all[:, 1], spc_all, rad_all)
    prev_feats = (xs_p, ppos_all[:, 1], pspc_all, prad_all)
    cells_c = _scatter_feats(p, dst_c, order_c, cur_feats, prev_feats,
                             gx_ext=gxe)

    def extract(packed_cells, lx, cz, sm, tpos):
        """Packed event words of the OWN rows binned in this slab."""
        lane = tpos[:chunk] % LANES
        ocol = lx[:chunk] - 1  # kernel output col: slab col minus ghost col
        flat = packed_cells.reshape(-1, w_words)
        oflat = ((sm[:chunk] * gz + cz[:chunk]) * qcols + ocol) * LANES + lane
        mine = (tpos[:chunk] >= 0) & (ocol >= 0) & (ocol < qcols)
        safe = jnp.clip(oflat, 0, flat.shape[0] - 1)
        return jnp.where(mine[:, None], flat[safe], 0)  # i32[chunk, W]

    # Seam-free single-pass fast tick (ISSUE 15): when the replicated
    # guard holds, ONE dual-output kernel launch on the current slab
    # yields both masks; other ticks pay the second scatter+kernel pass on
    # the previous slab. Both strip tables always build (xs poisoning and
    # drain contexts need them) — the kernel pass is what halves.
    fast = _fast_guard_strip(
        p, ppos_l, pact_l, pspc_l, prad_l, pos_l, act_l, spc_l,
        dropped_total,
    )

    if drain_inline:
        # In-kernel drain (ISSUE 19 leg b): the launch itself emits the
        # compacted (query slot, other slot) pairs — the XLA rank-select
        # below never runs on these ticks. Both branches slice their pairs
        # block to the [2, drain_inline] enter/leave regions so the cond
        # unifies; emission is already slot-valued and own-masked.
        so_c = _scatter_slotown(p, dst_c, order_c, slot_all, chunk, gxe)

        def fast_fn():
            pk2, tiles = kernel_dual(cells_c, so_c)
            prs = untile_pairs(tiles, 2 * drain_inline)
            return (pk2[..., :w_words], pk2[..., w_words:],
                    lxc, czc, smc, tpos_c, table_c,
                    prs[:, :drain_inline], prs[:, drain_inline:])

        def slow_fn():
            pk_e, prs_e = kernel(cells_c, so_c)
            cells_p = _scatter_feats(p, dst_p, order_p, prev_feats,
                                     cur_feats, gx_ext=gxe)
            so_p = _scatter_slotown(p, dst_p, order_p, slot_all, chunk, gxe)
            # Epoch symmetry: the prev-grid launch's "enter" mask
            # (valid_prev ∧ ¬valid_cur) IS the leave set.
            pk_l, prs_l = kernel(cells_p, so_p)
            return (pk_e, pk_l, lxp, czp, smp, tpos_p, table_p,
                    untile_pairs(prs_e, drain_inline),
                    untile_pairs(prs_l, drain_inline))

        (pk_e, pk_l, l_lx, l_cz, l_sm, l_tpos, l_table, prs_e, prs_l
         ) = jax.lax.cond(fast, fast_fn, slow_fn)
    else:
        def fast_fn():
            pk2 = kernel_dual(cells_c)  # [S, gz, qcols, LANES, 2W]
            return (pk2[..., :w_words], pk2[..., w_words:],
                    lxc, czc, smc, tpos_c, table_c)

        def slow_fn():
            pk_e = kernel(cells_c)
            cells_p = _scatter_feats(p, dst_p, order_p, prev_feats,
                                     cur_feats, gx_ext=gxe)
            pk_l = kernel(cells_p)
            return (pk_e, pk_l, lxp, czp, smp, tpos_p, table_p)

        pk_e, pk_l, l_lx, l_cz, l_sm, l_tpos, l_table = jax.lax.cond(
            fast, fast_fn, slow_fn
        )
        prs_e = prs_l = None
    packed_e = extract(pk_e, lxc, czc, smc, tpos_c)
    packed_l = extract(pk_l, l_lx, l_cz, l_sm, l_tpos)
    n_enters = jnp.sum(jax.lax.population_count(packed_e)).astype(jnp.int32)
    n_leaves = jnp.sum(jax.lax.population_count(packed_l)).astype(jnp.int32)

    if drain_inline:
        ep = jnp.transpose(prs_e)  # [events_inline, 2], already slot ids
        lp = jnp.transpose(prs_l)
    else:
        ep, _ = _drain_bits(p, packed_e, lxc[:chunk], czc[:chunk],
                            smc[:chunk], table_c, jnp.int32(0),
                            max_events=events_inline, gx_ext=gxe,
                            wrap_x=False)
        lp, _ = _drain_bits(p, packed_l, l_lx[:chunk], l_cz[:chunk],
                            l_sm[:chunk], l_table, jnp.int32(0),
                            max_events=events_inline, gx_ext=gxe,
                            wrap_x=False)

    def slotize(pairs):
        if drain_inline:
            return pairs  # kernel pairs are slot-valued already
        ent = pairs[:, 0]
        ent = jnp.where(ent < chunk, slot_l[jnp.minimum(ent, chunk - 1)], n)
        return jnp.stack([ent, pairs[:, 1]], axis=1)

    zero = jnp.int32(0)
    header = jnp.stack(
        [
            jnp.stack([n_enters, n_leaves]),
            jnp.stack([dropped_total, fast.astype(jnp.int32)]),
            jnp.stack([zero, zero]),  # rank paging resumes at events_inline
        ]
    ).astype(jnp.int32)
    # Replicated per-shard counts — see _spatial_step_impl.
    counts_all = jax.lax.all_gather(header[0], SHARD_AXIS)  # [D, 2]
    out = jnp.concatenate(
        [header, counts_all, slotize(ep), slotize(lp)], axis=0
    )
    enter_ctx = (packed_e, lxc[:chunk], czc[:chunk], smc[:chunk], table_c)
    leave_ctx = (packed_l, l_lx[:chunk], l_cz[:chunk], l_sm[:chunk], l_table)
    return enter_ctx + leave_ctx + (out,)


def _spatial_drain_bits(
    p: NeighborParams, events_inline: int, cols_cap: int,
    packed_l,  # [chunk, W] this shard's own-row packed event words
    lx_l, cz_l, sm_l,  # [chunk] strip-local bin coords of the pass's grid
    table_l,  # [nb_local * LANES] slot-id table of the pass's grid
    slot_l,  # [chunk] row → slot (dispatch-time perm snapshot)
    start_l,  # [1] resume EVENT RANK
):
    """Pallas-strip storm paging: rank-select past the inline budget, own
    rows mapped to slots through the dispatch-time perm snapshot."""
    n = p.capacity
    chunk = packed_l.shape[0]
    pairs, total = _drain_bits(
        p, packed_l, lx_l, cz_l, sm_l, table_l, start_l[0],
        max_events=events_inline, gx_ext=cols_cap + 4, wrap_x=False,
    )
    ent = pairs[:, 0]
    ent = jnp.where(ent < chunk, slot_l[jnp.minimum(ent, chunk - 1)], n)
    pairs = jnp.stack([ent, pairs[:, 1]], axis=1)
    return pairs, total[None]


def _spatial_step_pallas_fused_impl(
    p: NeighborParams,
    events_inline: int,
    halo_cap: int,
    n_dev: int,
    interpret: bool,
    cols_cap: int,
    drain_inline: int,
    programs,
    ppos_l, pact_l, pspc_l, prad_l,
    pos_l, act_l, spc_l, rad_l,
    slot_l,
    send_lo_idx,
    send_hi_idx,
    strip_lo,
    y_l, yaw_l, sel_l, dt_l, *cols_l,
):
    """The Pallas strip step plus fused entity logic on this shard's LOCAL
    rows — identical logic contract to _spatial_step_fused_impl (row-
    permuted inputs, perm-snapshot writeback)."""
    res = _spatial_step_pallas_impl(
        p, events_inline, halo_cap, n_dev, interpret, cols_cap,
        drain_inline,
        ppos_l, pact_l, pspc_l, prad_l,
        pos_l, act_l, spc_l, rad_l,
        slot_l, send_lo_idx, send_hi_idx, strip_lo,
    )
    new_pos, new_y, new_yaw, new_cols = _apply_fused_logic(
        programs, pos_l, y_l, yaw_l, sel_l, dt_l[0], cols_l
    )
    return res + ((new_pos, new_y, new_yaw) + new_cols,)


@functools.lru_cache(maxsize=None)
def _jitted_spatial_step_pallas(
    params: NeighborParams, mesh: Mesh, events_inline: int, halo_cap: int,
    interpret: bool, cols_cap: int, drain_inline: int = 0,
):
    assert drain_inline in (0, events_inline)
    body = functools.partial(
        _spatial_step_pallas_impl, params, events_inline, halo_cap,
        mesh.devices.size, interpret, cols_cap, drain_inline,
    )
    spec = P(SHARD_AXIS)
    mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec,) * 12,
        out_specs=(spec,) * 11,
        # pallas_call's out_shape carries no varying-mesh-axes annotation;
        # skip the vma check (outputs are explicitly per-shard here) —
        # same reasoning as parallel/mesh._jitted_sharded_step_pallas.
        check_vma=False,
    )
    return sentinel.SentinelJit("spatial_step_pallas", jax.jit(mapped))


@functools.lru_cache(maxsize=None)
def _jitted_spatial_step_pallas_fused(
    params: NeighborParams, mesh: Mesh, events_inline: int, halo_cap: int,
    interpret: bool, cols_cap: int, programs: tuple, n_cols: int,
    drain_inline: int = 0,
):
    assert drain_inline in (0, events_inline)
    body = functools.partial(
        _spatial_step_pallas_fused_impl, params, events_inline, halo_cap,
        mesh.devices.size, interpret, cols_cap, drain_inline, programs,
    )
    spec = P(SHARD_AXIS)
    mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec,) * (16 + n_cols),
        out_specs=(spec,) * 11 + ((spec,) * (3 + n_cols),),
        check_vma=False,
    )
    return sentinel.SentinelJit("spatial_step_pallas_fused", jax.jit(mapped))


@functools.lru_cache(maxsize=None)
def _jitted_spatial_drain_bits(
    params: NeighborParams, mesh: Mesh, events_inline: int, cols_cap: int
):
    body = functools.partial(
        _spatial_drain_bits, params, events_inline, cols_cap
    )
    spec = P(SHARD_AXIS)
    mapped = jax.shard_map(
        body, mesh=mesh, in_specs=(spec,) * 7, out_specs=(spec, spec),
    )
    return sentinel.SentinelJit("spatial_drain_bits", jax.jit(mapped))


def plan_strips(
    col_pop: np.ndarray, n_dev: int, min_cols: int = MIN_STRIP_COLS,
    max_cols: int | None = None,
) -> np.ndarray:
    """Equal-population strip boundaries from an observed column histogram.

    Returns int32[D+1] with boundaries[0] == 0 and boundaries[D] == grid_x.
    Each strip gets ≥ min_cols columns (the halo-correctness floor); the
    split otherwise walks the population cumsum so every strip carries
    ~1/D of the entities — hot columns get narrow strips, empty space gets
    wide ones (the AoiZora-style density-aware placement seed).

    ``max_cols`` caps every strip's width (the Pallas tier's static slab
    extent, cols_cap): sparse regions then spread over several capped
    strips instead of one wide one. Requires n_dev * max_cols >= grid_x.
    """
    gx = len(col_pop)
    if gx < n_dev * min_cols:
        raise ValueError(
            f"grid_x {gx} < {n_dev} shards * {min_cols} min columns"
        )
    if max_cols is not None and gx > n_dev * max_cols:
        raise ValueError(
            f"grid_x {gx} > {n_dev} shards * {max_cols} max columns"
        )
    cum = np.concatenate([[0], np.cumsum(col_pop, dtype=np.int64)])
    total = cum[-1]
    bounds = np.zeros(n_dev + 1, np.int32)
    bounds[n_dev] = gx
    for d in range(1, n_dev):
        target = total * d // n_dev
        b = int(np.searchsorted(cum, target, side="left"))
        # Clamp so every strip (including the ones still to come) keeps
        # its minimum width — and, under a width cap, so no strip placed
        # OR remaining can exceed it.
        b = max(b, int(bounds[d - 1]) + min_cols)
        b = min(b, gx - (n_dev - d) * min_cols)
        if max_cols is not None:
            b = min(b, int(bounds[d - 1]) + max_cols)
            b = max(b, gx - (n_dev - d) * max_cols)
        bounds[d] = b
    return bounds


def ring_link_distance(coords: list, order: np.ndarray) -> int:
    """Total interconnect distance of the strip ring under a device order:
    sum of manhattan distances between consecutive (and wrap-around)
    devices' mesh coordinates — the quantity every halo ``ppermute`` pays
    per tick, which topology-aware placement minimizes."""
    k = len(order)
    total = 0
    for i in range(k):
        a = coords[int(order[i])]
        b = coords[int(order[(i + 1) % k])]
        total += sum(abs(int(x) - int(y)) for x, y in zip(a, b))
    return total


def plan_placement(devices: list) -> np.ndarray:
    """Topology-aware strip→device placement (AoiZora, PAPERS.md): an
    index permutation ``order`` such that ``devices[order[i]]`` hosts
    strip i, chosen so ring-adjacent strips land on interconnect-adjacent
    chips. Devices exposing mesh ``coords`` (TPU) are walked in a
    boustrophedon (snake) over (z, y, x) — adjacent steps on a full grid
    are single-hop — with same-chip cores kept consecutive; the snake is
    adopted only when it strictly beats the given order's ring distance.
    Devices without coords (CPU/GPU rigs) fall back to ring order
    (identity)."""
    k = len(devices)
    ident = np.arange(k, dtype=np.int64)
    coords = [getattr(d, "coords", None) for d in devices]
    if k < 2 or any(c is None for c in coords):
        return ident
    coords = [tuple(int(v) for v in c) + (0, 0, 0) for c in coords]
    coords = [c[:3] for c in coords]
    ys = sorted({c[1] for c in coords})
    yi = {v: i for i, v in enumerate(ys)}

    def key(i: int):
        x, y, z = coords[i]
        core = int(getattr(devices[i], "core_on_chip", 0) or 0)
        yr = yi[y] if z % 2 == 0 else len(ys) - 1 - yi[y]
        xr = x if (z + yi[y]) % 2 == 0 else -x
        return (z, yr, xr, core)

    snake = np.asarray(sorted(range(k), key=key), dtype=np.int64)
    if ring_link_distance(coords, snake) < ring_link_distance(coords, ident):
        return snake
    return ident


def strip_cols_for(grid_x: int, n_dev: int,
                   strip_cols: int | None = None) -> int:
    """The Pallas tier's static kernel-slab width cap, checked: by default
    2x the uniform strip, clamped to planner feasibility on both sides."""
    ceil_w = -(-grid_x // n_dev)
    if strip_cols is None:
        strip_cols = min(grid_x - (n_dev - 1) * MIN_STRIP_COLS, 2 * ceil_w)
    strip_cols = int(strip_cols)
    if strip_cols < ceil_w:
        raise ValueError(
            f"strip_cols {strip_cols} < ceil(grid_x/{n_dev}) = {ceil_w}: "
            f"{n_dev} capped strips cannot cover {grid_x} columns"
        )
    if strip_cols + 4 > grid_x:
        raise ValueError(
            f"strip_cols {strip_cols} + 4 ghost columns exceeds grid_x "
            f"{grid_x}; lower strip_cols (the strip slab must not wrap "
            f"onto itself)"
        )
    return strip_cols


def default_halo_cap(params: NeighborParams, n_dev: int) -> int:
    """Rows each seam band may ship: ~6 band columns of the uniform-density
    column population, doubled for clustering, clamped to the chunk (an
    overflow past this budget falls back for the tick, it never breaks)."""
    est = 12 * params.capacity // params.grid_x
    return max(64, min(params.capacity // n_dev, ((est + 7) // 8) * 8))


class SpatialShardedNeighborEngine:
    """Grid-strip sharded AOI engine (see module docstring).

    Interface parity with ShardedNeighborEngine: ``reset`` /
    ``step_async`` / ``step``, one packed readback per tick, paging past
    each chip's inline budget of ``params.max_events``. Extra
    observability attributes: ``last_mode`` ("spatial" |
    "fallback:<reason>"), ``last_fast_tick`` (the seam-free single-pass
    guard held on the last collected tick), ``shard_population`` (np int64[D] active rows per shard at the last
    dispatch), ``halo_bytes_per_tick`` (structural ppermute payload), and
    the telemetry counters wired in ``__init__``.

    ``backend``: "auto" = the strip-local Pallas kernel slab on TPU, the
    jnp candidate math elsewhere; "pallas" / "pallas_interpret" / "jnp"
    force a path. Both backends move the SAME halo bands — the Pallas
    tier additionally keeps the kernel grid, table sort, and event drain
    strip-local (``strip_cols`` caps a strip's width, the kernel slab's
    static extent). ``placement``: "topology" reorders the mesh so
    ring-adjacent strips land on interconnect-adjacent devices
    (plan_placement; identity on rigs without device coords), "ring"
    keeps the given mesh order.
    """

    def __init__(
        self,
        params: NeighborParams,
        mesh: Mesh,
        halo_cap: int | None = None,
        replan_interval: int = 64,
        prewarm_fallback: bool = True,
        backend: str = "auto",
        strip_cols: int | None = None,
        placement: str = "topology",
        inkernel_drain: bool = True,
    ) -> None:
        if backend == "auto":
            backend = "pallas" if jax.default_backend() == "tpu" else "jnp"
        if backend not in ("jnp", "pallas", "pallas_interpret"):
            raise ValueError(f"unknown backend {backend!r}")
        if placement not in ("topology", "ring"):
            raise ValueError(
                f"placement must be topology|ring, got {placement!r}"
            )
        n_dev = int(mesh.devices.size)
        if n_dev < 2:
            raise ValueError("spatial sharding needs >= 2 devices")
        if params.capacity % (8 * n_dev) != 0:
            raise ValueError(
                f"capacity {params.capacity} must be a multiple of 8*{n_dev}"
            )
        if params.grid_x < MIN_STRIP_COLS * n_dev:
            raise ValueError(
                f"grid_x {params.grid_x} < {MIN_STRIP_COLS}*{n_dev} "
                f"(each strip needs >= {MIN_STRIP_COLS} columns for the "
                f"halo contract); raise [aoi] grid or lower mesh_shards"
            )
        if backend != "jnp" and params.cell_capacity > LANES:
            raise ValueError(
                f"pallas path supports cell_capacity <= {LANES}, "
                f"got {params.cell_capacity}"
            )
        # Topology-aware strip→device placement (tentpole a): strip i
        # always lives at mesh position i, so placing strips IS ordering
        # the mesh's devices. Re-plans move strip boundaries, never strip
        # order, so the adjacency the placement buys survives them.
        self.placement = placement
        devs = list(mesh.devices.reshape(-1))
        self.placement_order = plan_placement(devs)
        if placement == "topology" and not np.array_equal(
            self.placement_order, np.arange(n_dev)
        ):
            mesh = Mesh(
                np.asarray([devs[i] for i in self.placement_order]),
                (SHARD_AXIS,),
            )
        coords = [getattr(d, "coords", None) for d in devs]
        if all(c is not None for c in coords):
            _M_RING_DISTANCE.labels("ring").set(
                ring_link_distance(coords, np.arange(n_dev)))
            _M_RING_DISTANCE.labels("placed").set(
                ring_link_distance(
                    coords,
                    self.placement_order if placement == "topology"
                    else np.arange(n_dev)))
        self.params = params
        self.mesh = mesh
        self.backend = backend
        self.n_devices = n_dev
        self.chunk = params.capacity // n_dev
        # Per chip, not divided by D (module docstring).
        self.events_inline = params.max_events
        if backend != "jnp":
            self._max_cols: int | None = strip_cols_for(
                params.grid_x, n_dev, strip_cols)
        else:
            self._max_cols = None
        self.strip_cols = self._max_cols
        if halo_cap is None:
            halo_cap = default_halo_cap(params, n_dev)
        self.halo_cap = int(halo_cap)
        self.replan_interval = int(replan_interval)
        self.halo_bytes_per_tick = (
            n_dev * 2 * self.halo_cap * HALO_ROW_BYTES
        )
        # What the all-gather formulation moves instead: every OTHER
        # shard's rows, both epochs (pos 8B + act 1B + spc 4B + rad 4B
        # each), received by each of the D devices. The Pallas kernel
        # tier's all-gather formulation (mesh._sharded_step_pallas) moves
        # the same eight feature arrays, so one equivalent serves both.
        self.allgather_bytes_per_tick = (
            n_dev * (params.capacity - self.chunk) * 34
        )
        # In-kernel drain ([aoi] pallas_inkernel_drain, ISSUE 19 leg b):
        # steady strip ticks emit their compacted event pairs from the
        # kernel launch itself; the XLA rank-select stays compiled in as
        # the storm-paging program (a tick whose events overflow the
        # inline budget repages WHOLLY through it — kernel emission is
        # cell-major, so its partial window cannot be rank-resumed).
        self.inkernel_drain = bool(inkernel_drain)
        self.drain_inline = (
            self.events_inline if (backend != "jnp" and inkernel_drain)
            else 0
        )
        if backend == "jnp":
            self._jit_step = _jitted_spatial_step(
                params, mesh, self.events_inline, self.halo_cap
            )
            self._jit_drain = _jitted_spatial_drain(
                params, mesh, self.events_inline, self.chunk
            )
        else:
            self._jit_step = _jitted_spatial_step_pallas(
                params, mesh, self.events_inline, self.halo_cap,
                backend == "pallas_interpret", self.strip_cols,
                self.drain_inline,
            )
            self._jit_drain = _jitted_spatial_drain_bits(
                params, mesh, self.events_inline, self.strip_cols
            )
        # Exact all-gather program for ticks the strip invariants cannot
        # cover (teleports past the halo, halo overflow, strip overflow).
        # BOTH backends fall back to the jnp all-gather program: fallback
        # ticks are rare by construction, and one exact program keeps the
        # oracle surface single (the kernel tier's honesty note, README).
        self._jit_fallback = _jitted_sharded_step(
            params, mesh, self.events_inline
        )
        self._jit_fallback_drain = _jitted_sharded_drain(
            params, mesh, self.events_inline, self.chunk
        )
        self._jit_row_moves = _jitted_row_moves(mesh, self.chunk)
        self._flat_end = self.chunk * 9 * params.cell_capacity
        self._sharding = NamedSharding(mesh, P(SHARD_AXIS))
        self._replicated = NamedSharding(mesh, P())
        self._state: tuple | None = None
        self.last_grid_dropped = 0
        self.last_mode = "spatial"
        self.last_fast_tick = False
        self.total_fast_ticks = 0
        self.shard_population = np.zeros(n_dev, np.int64)
        self.total_migrations = 0
        self.total_fallbacks = 0
        self.total_replans = 0
        # Dispatches by how they brought the row layout up to date
        # ("incremental": swaps of the moved slots only; "rebuild": the
        # whole layout and both epochs' upload), and the slots the
        # incremental swaps moved to another strip's rows.
        self.total_relayouts = {"incremental": 0, "rebuild": 0}
        self.total_row_moves = 0
        from goworld_tpu import telemetry

        telemetry.gauge(
            "aoi_shard_count",
            "Device shards of the spatially sharded AOI engine.",
        ).set(n_dev)
        self._m_shard_entities = telemetry.gauge(
            "aoi_shard_entities",
            "Active entity rows owned by each AOI grid-strip shard at the "
            "last dispatch.",
            ("shard",),
        )
        self._m_halo_bytes = telemetry.counter(
            "aoi_halo_bytes_total",
            "Bytes ppermuted between shards for AOI halo exchange "
            "(structural: halo_cap rows x 2 directions x D shards per "
            "spatial tick).",
        )
        self._m_allgather_bytes = telemetry.counter(
            "aoi_allgather_bytes_total",
            "Bytes the exact all-gather fallback program moves between "
            "shards (every other shard's rows, both epochs) on ticks the "
            "strip invariants cannot cover.",
        )
        # The structural comms story as live gauges (previously only a
        # bench headline): what one spatial tick moves vs what the
        # all-gather formulation would move — their ratio is THE point of
        # the spatial engine, now watchable on /metrics and /cluster.
        telemetry.gauge(
            "aoi_halo_bytes_per_tick",
            "Structural ppermute payload of one spatial tick "
            "(halo_cap rows x 2 directions x D shards).",
        ).set(self.halo_bytes_per_tick)
        telemetry.gauge(
            "aoi_allgather_equiv_bytes_per_tick",
            "What the all-gather formulation would move per tick at this "
            "tier (every other shard's rows, both epochs, on D devices).",
        ).set(self.allgather_bytes_per_tick)
        self._m_migrations = telemetry.counter(
            "aoi_shard_migrations_total",
            "Entities reassigned to a different AOI grid-strip shard "
            "(hysteresis: one full cell past the seam).",
        )
        relayouts = telemetry.counter(
            "aoi_shard_relayouts_total",
            "Dispatches that changed the AOI strip engine's row layout: "
            "kind=incremental swapped only the moved slots' rows, "
            "kind=rebuild rebuilt the layout and re-uploaded both epochs.",
            ("kind",),
        )
        self._m_relayouts = {k: relayouts.labels(k)
                             for k in self.total_relayouts}
        self._m_fallback = telemetry.counter(
            "aoi_shard_fallback_total",
            "Ticks the spatial engine ran the exact all-gather program "
            "instead of the halo exchange.",
            ("reason",),
        )
        self._m_replans = telemetry.counter(
            "aoi_shard_replans_total",
            "Density-driven strip re-plans adopted (equal-population "
            "boundary moves).",
        )
        # Per-seam observed halo payload (ROADMAP item 5): the wire moves
        # the structural halo_cap envelope, but the OCCUPIED rows of each
        # band are what a comms regression shows up in — counted per
        # directed seam link into the shared aoi_link_bytes_total family
        # (children prebuilt; _build_bands records the occupancy).
        from goworld_tpu.parallel.mesh import _M_LINK_BYTES

        self._halo_link_children = tuple(
            (_M_LINK_BYTES.labels("halo", f"{s}->{(s - 1) % n_dev}"),
             _M_LINK_BYTES.labels("halo", f"{s}->{(s + 1) % n_dev}"))
            for s in range(n_dev))
        self._last_band_counts: np.ndarray | None = None
        if prewarm_fallback:
            # The fallback program compiles lazily on its (rare) first
            # tick otherwise — a synchronous XLA compile inside the game
            # loop. Best-effort daemon warmup, same pattern as
            # BatchAOIService._prewarm_next_tier.
            threading.Thread(
                target=self._prewarm_fallback, name="aoi-spatial-fallback",
                daemon=True,
            ).start()

    # --- host-side shard layout ---------------------------------------------

    def _prewarm_fallback(self) -> None:
        try:
            n = self.params.capacity
            put = lambda x: jax.device_put(x, self._sharding)  # noqa: E731
            z = (
                put(np.zeros((n, 2), np.float32)),
                put(np.zeros((n,), bool)),
                put(np.zeros((n,), np.int32)),
                put(np.zeros((n,), np.float32)),
            )
            jax.block_until_ready(self._jit_fallback(*z, *z)[2])
        except Exception:  # pragma: no cover - prewarm is best-effort
            pass

    def reset(self) -> None:
        n = self.params.capacity
        gx = self.params.grid_x
        d = self.n_devices
        self.boundaries = np.array(
            [round(i * gx / d) for i in range(d)] + [gx], np.int32
        )
        self._rebuild_col_owner()
        self.perm = np.arange(n, dtype=np.int32)
        self.row_of = np.arange(n, dtype=np.int32)
        self.assign = (self.perm // self.chunk).astype(np.int32)
        zeros = (
            np.zeros((n, 2), np.float32),
            np.zeros((n,), bool),
            np.zeros((n,), np.int32),
            np.zeros((n,), np.float32),
        )
        self._host_prev = zeros
        self._prev_cx = bins_reference(self.params, zeros[0], zeros[2])[0]
        self._dispatches = 0
        # Layout debt: the first dispatch builds the layout whole; after
        # that, ``_moved`` collects the slots whose ``assign`` changed
        # since the rows last matched it.
        self._relayout_full = True
        self._moved: list[np.ndarray] = []
        put = lambda x: jax.device_put(x, self._sharding)  # noqa: E731
        self._state = tuple(put(a) for a in zeros)
        self._perm_dev = put(self.perm)
        # Compile the row-move program now (a no-op write: every row is
        # padding), not on the first migration inside the game loop.
        self._move_rows(np.empty(0, np.int32), np.empty(0, np.int32))

    def _rebuild_col_owner(self) -> None:
        gx = self.params.grid_x
        owner = np.empty(gx, np.int32)
        for d in range(self.n_devices):
            owner[self.boundaries[d]:self.boundaries[d + 1]] = d
        self._col_owner = owner
        # Hysteresis band columns, one per side of each strip.
        self._band_lo = (self.boundaries[:-1] - 1) % gx
        self._band_hi = self.boundaries[1:] % gx
        # Per-shard strip origin for the Pallas slab's local-column map;
        # a dynamic [D] input, so boundary moves never retrace the jit.
        self._strip_lo_dev = jax.device_put(
            np.ascontiguousarray(self.boundaries[:-1], dtype=np.int32),
            self._sharding,
        )

    def carried_epoch(self) -> tuple:
        """The last dispatched world in SLOT space (what the tier-growth
        reseed needs — the device state is row-permuted here)."""
        return tuple(np.array(a) for a in self._host_prev)

    def _in_strip_or_band(self, cx: np.ndarray, shard: np.ndarray):
        """Hysteresis keep-test: column inside the shard's strip, or in
        its one-column slack band on either side."""
        return (
            (self._col_owner[cx] == shard)
            | (cx == self._band_lo[shard])
            | (cx == self._band_hi[shard])
        )

    def _rehome_prev_only(self, prev_act, cur_act) -> int:
        """Re-home rows active ONLY in the previous epoch onto the strip
        owning their PREVIOUS cell (see step_async — keeps adopted
        re-plans from stranding a despawned row's prev cell outside its
        band). Returns the number of rows moved."""
        prev_only = np.flatnonzero(prev_act & ~cur_act)
        if not len(prev_only):
            return 0
        keep = self._in_strip_or_band(
            self._prev_cx[prev_only], self.assign[prev_only]
        )
        movers = prev_only[~keep]
        if len(movers):
            self.assign[movers] = self._col_owner[self._prev_cx[movers]]
            self._moved.append(movers)
        return int(len(movers))

    def _replan(self, cx: np.ndarray, active: np.ndarray) -> bool:
        """Re-split strips from the observed column density; adopt only
        when the split meaningfully improves the worst strip load."""
        pop = np.bincount(cx[active], minlength=self.params.grid_x)
        new = plan_strips(pop, self.n_devices, max_cols=self._max_cols)
        if np.array_equal(new, self.boundaries):
            return False
        cum = np.concatenate([[0], np.cumsum(pop, dtype=np.int64)])

        def worst(bounds):
            loads = cum[bounds[1:]] - cum[bounds[:-1]]
            return int(loads.max()) if len(loads) else 0

        if worst(new) > 0.9 * worst(self.boundaries):
            return False
        self.boundaries = new
        self._rebuild_col_owner()
        # Boundaries moved: rows may change strip by the thousand, so the
        # next relayout rebuilds the layout whole.
        self._relayout_full = True
        self.total_replans += 1
        self._m_replans.inc()
        return True

    def _rebuild_perm(self, placed: np.ndarray) -> None:
        """Row layout from the current assignment: shard d's rows hold its
        PLACED slots (active in either epoch — a freshly-despawned slot
        must stay on the strip its previous-epoch pairs live on, or its
        neighbors' leave events would never find it) in slot order, then
        free fill (deterministic).

        Runs only where many rows move at once: the first dispatch after
        ``reset``, the dispatch after an adopted re-plan, and a dispatch
        whose moved slots find too few free rows in their new strip
        (``_swap_rows``). Every other migration swaps rows in place."""
        n = self.params.capacity
        d = self.n_devices
        chunk = self.chunk
        perm = np.empty(n, np.int32)
        inactive = np.flatnonzero(~placed).astype(np.int32)
        cursor = 0
        for s in range(d):
            mine = np.flatnonzero(placed & (self.assign == s)).astype(
                np.int32
            )
            k = len(mine)
            assert k <= chunk, "strip overflow must fall back before here"
            perm[s * chunk:s * chunk + k] = mine
            fill = chunk - k
            pad = inactive[cursor:cursor + fill]
            perm[s * chunk + k:(s + 1) * chunk] = pad
            # Inactive slots inherit the shard of the row that parks them
            # (keeps the keep-test well-defined when they activate).
            self.assign[pad] = s
            cursor += fill
        self.perm = perm
        self.row_of = np.empty(n, np.int32)
        self.row_of[perm] = np.arange(n, dtype=np.int32)

    def _free_rows(self, s: int, need: int, placed: np.ndarray):
        """``need`` rows of shard s's block whose slot is placed in
        neither epoch, searched from the block's end (where a rebuild
        parks the free fill) over a window that grows until it holds
        enough; None when the whole block has fewer."""
        lo, hi = s * self.chunk, (s + 1) * self.chunk
        width = max(64, 4 * need)
        while True:
            start = max(lo, hi - width)
            free = start + np.flatnonzero(~placed[self.perm[start:hi]])
            if len(free) >= need:
                return free[len(free) - need:].astype(np.int32)
            if start == lo:
                return None
            width *= 4

    def _swap_rows(self, placed: np.ndarray):
        """Incremental relayout: each slot in ``_moved`` that is placed
        and now assigned to another strip swaps rows with a free row of
        that strip; the free row's slot, placed in neither epoch, parks
        in the vacated row. Updates ``perm``, ``row_of`` and ``assign``
        for those rows only and returns (rows, slots) to write on the
        device, or None (layout untouched) when a strip lacks the free
        rows, and the caller rebuilds."""
        cand = np.unique(np.concatenate(self._moved))
        block = self.row_of[cand] // self.chunk
        off = self.assign[cand] != block
        cand, block = cand[off], block[off]
        idle = ~placed[cand]
        movers = cand[~idle]
        dest = self.assign[movers]
        picked = []
        for s in np.unique(dest):
            mine = movers[dest == s]
            rows = self._free_rows(int(s), len(mine), placed)
            if rows is None:
                return None
            picked.append((mine, rows))
        # An unplaced slot holds no epoch's values: it stays parked in
        # its row and takes that row's strip.
        self.assign[cand[idle]] = block[idle]
        if not picked:
            return np.empty(0, np.int32), np.empty(0, np.int32)
        movers = np.concatenate([m for m, _ in picked])
        dst = np.concatenate([r for _, r in picked])
        src = self.row_of[movers]
        parked = self.perm[dst]
        self.perm[dst] = movers
        self.perm[src] = parked
        self.row_of[movers] = dst
        self.row_of[parked] = src
        self.assign[parked] = src // self.chunk
        return (np.concatenate([dst, src]),
                np.concatenate([movers, parked]))

    def _move_rows(self, rows: np.ndarray, slots: np.ndarray) -> None:
        """Write ``rows`` of the device's previous epoch with those
        slots' values from the host mirror, and of the row→slot map with
        the slots: fixed-shape launches of ``ROW_MOVE_BATCH`` rows."""
        hp = self._host_prev
        cap = self.params.capacity
        k = ROW_MOVE_BATCH
        for b in range(0, max(len(rows), 1), k):
            r, sl = rows[b:b + k], slots[b:b + k]
            ipay = np.zeros((k, 4), np.int32)
            ipay[:, 0] = cap
            ipay[:len(r), 0] = r
            ipay[:len(r), 1] = sl
            ipay[:len(r), 2] = hp[1][sl]
            ipay[:len(r), 3] = hp[2][sl]
            fpay = np.zeros((k, 3), np.float32)
            fpay[:len(r), :2] = hp[0][sl]
            fpay[:len(r), 2] = hp[3][sl]
            out = self._jit_row_moves(
                *self._state, self._perm_dev,
                jax.device_put(ipay, self._replicated),
                jax.device_put(fpay, self._replicated),
            )
            self._state = tuple(out[:4])
            self._perm_dev = out[4]

    def _relayout(self, placed: np.ndarray):
        """Bring the host's row layout up to the current ``assign``.
        Returns (kind, swapped): kind "incremental" with the (rows,
        slots) ``_move_rows`` writes on the device, "rebuild" when the
        whole layout was rebuilt (both epochs go up again), or None
        when no placed slot changed strip."""
        if not self._relayout_full and self._moved:
            swapped = self._swap_rows(placed)
            if swapped is not None:
                self._moved = []
                if not len(swapped[0]):
                    return None, None
                self.total_row_moves += len(swapped[0]) // 2
                return self._count_relayout("incremental"), swapped
        if not (self._relayout_full or self._moved):
            return None, None
        self._rebuild_perm(placed)
        self._relayout_full = False
        self._moved = []
        return self._count_relayout("rebuild"), None

    def _count_relayout(self, kind: str) -> str:
        self.total_relayouts[kind] += 1
        self._m_relayouts[kind].inc()
        return kind

    # --- dispatch -----------------------------------------------------------

    # Fused entity logic is supported: per-row elementwise programs ride
    # the spatial launch in row-permuted layout (see _spatial_step_fused).
    supports_fused_logic = True

    def step_async(
        self,
        pos: np.ndarray,
        active: np.ndarray,
        space: np.ndarray,
        radius: np.ndarray,
        meta_dirty: bool = True,
        logic: tuple | None = None,
    ):
        assert self._state is not None, "call reset() first"
        with engine_span("upload"):
            check_radius(self.params, radius, active)
            if self.backend != "jnp":
                check_space_ids(space, active)
            p = self.params
            # Copies, not views: these become the host prev mirror and
            # must not alias caller buffers (same contract as the other
            # engines).
            cur = (
                np.array(pos, np.float32),
                np.array(active, bool),
                np.array(space, np.int32),
                np.array(radius, np.float32),
            )
        with engine_span("plan"):
            cur_pos, cur_act, cur_spc, _ = cur
            cx = bins_reference(p, cur_pos, cur_spc)[0]
            self._dispatches += 1

            from goworld_tpu.telemetry import tracing

            halo_span = tracing.child_scope("tick.halo")
            t0 = time.monotonic()

            migrations = 0
            prev_act = self._host_prev[1]
            # Slow-cadence density re-plan.
            if self.replan_interval and (
                self._dispatches % self.replan_interval == 0
            ):
                self._replan(cx, cur_act)
            # Hysteresis migration: move a row only when its cell is a
            # full column past the seam.
            act_idx = np.flatnonzero(cur_act)
            keep = self._in_strip_or_band(cx[act_idx], self.assign[act_idx])
            movers = act_idx[~keep]
            if len(movers):
                self.assign[movers] = self._col_owner[cx[movers]]
                migrations += len(movers)
                self._moved.append(movers)
            # Prev-epoch-only rows (freshly despawned) re-home by their
            # PREVIOUS column: their only remaining job is hosting their
            # prev-epoch pairs, so an adopted re-plan that moved
            # boundaries several columns must carry them to the new owner
            # of that cell — otherwise the stranded prev cell trips the
            # teleport guard and the tick pays the exact all-gather
            # fallback for no reason.
            migrations += self._rehome_prev_only(prev_act, cur_act)

            fallback_reason = None
            # Row placement covers slots live in EITHER epoch: a slot that
            # just despawned still owns a row on its strip this tick so its
            # neighbors' leave events resolve there.
            placed_idx = np.flatnonzero(cur_act | prev_act)
            counts = np.bincount(
                self.assign[placed_idx], minlength=self.n_devices
            ).astype(np.int64)
            if counts.max(initial=0) > self.chunk:
                # A strip outgrew its row budget: re-plan NOW; if one
                # column is hotter than a whole shard's budget even alone,
                # spatial sharding cannot represent it — exact fallback.
                if self._replan(cx, cur_act):
                    # Boundary move: reassign by owner column (hysteresis
                    # slack resets), counting only rows that actually
                    # changed shard.
                    new_assign = self._col_owner[cx[act_idx]]
                    migrations += int(
                        (new_assign != self.assign[act_idx]).sum())
                    self.assign[act_idx] = new_assign
                    migrations += self._rehome_prev_only(prev_act, cur_act)
                    counts = np.bincount(
                        self.assign[placed_idx], minlength=self.n_devices
                    ).astype(np.int64)
                if counts.max(initial=0) > self.chunk:
                    fallback_reason = "strip_overflow"
            self.shard_population = counts

            if fallback_reason is None:
                # Teleport guard: every row active in the PREVIOUS epoch
                # must have its previous cell inside its (current) shard's
                # slack band, or its leave pass would reach past the halo.
                pa_idx = np.flatnonzero(prev_act)
                ok = self._in_strip_or_band(
                    self._prev_cx[pa_idx], self.assign[pa_idx]
                )
                if not ok.all():
                    fallback_reason = "teleport"

            relayout = swapped = None
            if fallback_reason != "strip_overflow":
                # Bands are expressed as LOCAL row indices, so the layout
                # must follow ``assign`` before they are selected. (The
                # layout debt is persistent state: a strip-overflow
                # fallback tick defers it — chunk cannot hold the strip —
                # without losing it.)
                relayout, swapped = self._relayout(cur_act | prev_act)
            send_lo = send_hi = None
            if fallback_reason is None:
                send_lo, send_hi, overflow = self._build_bands(
                    cx, cur_act, prev_act
                )
                if overflow:
                    fallback_reason = "halo_overflow"
            if migrations:
                self.total_migrations += migrations
                self._m_migrations.inc(migrations)
            for d in range(self.n_devices):
                self._m_shard_entities.labels(str(d)).set(int(counts[d]))
            if halo_span is not None:
                halo_span.args["migrations"] = migrations
                halo_span.args["mode"] = fallback_reason or "spatial"
                tracing.record_span(
                    halo_span.name, t0, time.monotonic() - t0,
                    halo_span.ctx.trace_id, halo_span.ctx.span_id,
                    halo_span.parent_id, halo_span.args,
                )

        with engine_span("upload"):
            put = lambda x: jax.device_put(x, self._sharding)  # noqa: E731
            perm = self.perm
            # The previous epoch must live in the new layout, or the
            # device diff would read a migration as despawn+spawn. A
            # swap writes only the moved rows (and, with them, the reused
            # meta below); a rebuild gathers and uploads the whole epoch.
            if relayout == "incremental":
                self._move_rows(*swapped)
            elif relayout == "rebuild":
                self._state = tuple(put(a[perm]) for a in self._host_prev)
                # A copy: ``_swap_rows`` edits ``perm`` in place later, and
                # a put may read (on the CPU, alias) the host buffer after
                # it returns, while this tick is still in flight.
                self._perm_dev = put(perm.copy())
            if meta_dirty or relayout == "rebuild":
                meta = (
                    put(cur[1][perm]), put(cur[2][perm]), put(cur[3][perm])
                )
            else:
                meta = self._state[1:4]
            cur_dev = (put(cur[0][perm]),) + meta

            fused_out = None
            logic_dev: tuple = ()
            if logic is not None:
                # Row-permuted upload of the fused-logic inputs: the
                # programs run per LOCAL row, so sel/y/yaw/columns travel
                # through the same perm as the positions; dt rides as a
                # [D] sharded array (one scalar per shard body).
                programs, sel, y, yaw, dt, cols = logic
                logic_dev = (
                    put(np.asarray(y, np.float32)[perm]),
                    put(np.asarray(yaw, np.float32)[perm]),
                    put(np.asarray(sel, np.int32)[perm]),
                    put(np.full(self.n_devices, dt, np.float32)),
                ) + tuple(put(np.asarray(c)[perm]) for c in cols)

        with engine_span("launch"):
            if fallback_reason is None:
                if self.backend != "jnp":
                    band_args = (
                        self._perm_dev, put(send_lo), put(send_hi),
                        self._strip_lo_dev,
                    )
                    if logic is not None:
                        jit_fused = _jitted_spatial_step_pallas_fused(
                            self.params, self.mesh, self.events_inline,
                            self.halo_cap,
                            self.backend == "pallas_interpret",
                            self.strip_cols, tuple(logic[0]),
                            len(logic[5]), self.drain_inline,
                        )
                        res = jit_fused(
                            *self._state, *cur_dev, *band_args, *logic_dev,
                        )
                        fused_out = res[11]
                    else:
                        res = self._jit_step(*self._state, *cur_dev,
                                             *band_args)
                    enter_ctx = (("pallas",) + tuple(res[0:5])
                                 + (self._perm_dev,))
                    leave_ctx = (("pallas",) + tuple(res[5:10])
                                 + (self._perm_dev,))
                    out = res[10]
                else:
                    if logic is not None:
                        jit_fused = _jitted_spatial_step_fused(
                            self.params, self.mesh, self.events_inline,
                            self.halo_cap, tuple(logic[0]), len(logic[5]),
                        )
                        enter_ids, leave_ids, out, fused_out = jit_fused(
                            *self._state, *cur_dev, self._perm_dev,
                            put(send_lo), put(send_hi), *logic_dev,
                        )
                    else:
                        enter_ids, leave_ids, out = self._jit_step(
                            *self._state, *cur_dev, self._perm_dev,
                            put(send_lo), put(send_hi),
                        )
                    enter_ctx = ("spatial", enter_ids, self._perm_dev)
                    leave_ctx = ("spatial", leave_ids, self._perm_dev)
                self.last_mode = "spatial"
                self._m_halo_bytes.inc(self.halo_bytes_per_tick)
                if self._last_band_counts is not None:
                    for s in range(self.n_devices):
                        lo_n, hi_n = self._last_band_counts[s]
                        if lo_n:
                            self._halo_link_children[s][0].inc(
                                int(lo_n) * HALO_ROW_BYTES)
                        if hi_n:
                            self._halo_link_children[s][1].inc(
                                int(hi_n) * HALO_ROW_BYTES)
                pending = ShardedPendingStep(self, enter_ctx, leave_ctx,
                                             out)
                # The strip-local bit drain pages by event RANK; everything
                # else (jnp ids, the jnp all-gather fallback) by flat
                # index.
                pending.rank_paging = self.backend != "jnp"
                # In-kernel drain pairs are cell-major: an overflowing
                # shard's inline window is order-incompatible with rank
                # resume, so collect() discards it and repages that shard
                # from rank 0.
                pending.full_repage = self.drain_inline > 0
            else:
                if logic is not None:
                    jit_fused = _jitted_sharded_step_fused(
                        self.params, self.mesh, self.events_inline,
                        tuple(logic[0]), len(logic[5]),
                    )
                    enter_ids, leave_ids, out, fused_out = jit_fused(
                        *self._state, *cur_dev, *logic_dev,
                    )
                else:
                    enter_ids, leave_ids, out = self._jit_fallback(
                        *self._state, *cur_dev
                    )
                enter_ctx = ("fallback", enter_ids)
                leave_ctx = ("fallback", leave_ids)
                self.last_mode = f"fallback:{fallback_reason}"
                self.total_fallbacks += 1
                self._m_fallback.labels(fallback_reason).inc()
                self._m_allgather_bytes.inc(self.allgather_bytes_per_tick)
                pending = _FallbackPendingStep(
                    self, enter_ctx, leave_ctx, out, perm.copy()
                )
                # The fallback is the jnp all-gather program on EITHER
                # backend: its cursors are flat matrix indices.
                pending.rank_paging = False

        if fused_out is not None:
            from goworld_tpu.ops.neighbor import start_host_copy

            for arr in fused_out:
                start_host_copy(arr)
            # Outputs are in ROW space: the perm SNAPSHOT maps row→slot at
            # writeback time, immune to later migrations/re-plans.
            pending.fused = (tuple(logic[0]), np.asarray(logic[1]),
                             perm.copy(), fused_out)

        self._state = cur_dev
        self._host_prev = cur
        self._prev_cx = cx
        return pending

    def warmup_fused(self, programs: tuple, col_dtypes: tuple) -> None:
        """Compile BOTH fused programs (spatial + exact fallback) for this
        program set without touching engine state — the spatial analog of
        NeighborEngine.warmup_fused (restore-path prewarm)."""
        n = self.params.capacity
        d = self.n_devices
        gx = self.params.grid_x
        put = lambda x: jax.device_put(x, self._sharding)  # noqa: E731
        zeros = (
            put(np.zeros((n, 2), np.float32)),
            put(np.zeros((n,), bool)),
            put(np.zeros((n,), np.int32)),
            put(np.zeros((n,), np.float32)),
        )
        logic_dev = (
            put(np.zeros(n, np.float32)),
            put(np.zeros(n, np.float32)),
            put(np.zeros(n, np.int32)),
            put(np.zeros(d, np.float32)),
        ) + tuple(put(np.zeros(n, np.dtype(dt))) for dt in col_dtypes)
        ncols = len(col_dtypes)
        perm = put(np.arange(n, dtype=np.int32))
        empty_band = put(np.full(d * self.halo_cap, self.chunk, np.int32))
        if self.backend != "jnp":
            strip_lo = put(np.asarray(
                [round(i * gx / d) for i in range(d)], np.int32))
            jit_sp = _jitted_spatial_step_pallas_fused(
                self.params, self.mesh, self.events_inline, self.halo_cap,
                self.backend == "pallas_interpret", self.strip_cols,
                tuple(programs), ncols, self.drain_inline,
            )
            jax.block_until_ready(
                jit_sp(*zeros, *zeros, perm, empty_band, empty_band,
                       strip_lo, *logic_dev)[10])
        else:
            jit_sp = _jitted_spatial_step_fused(
                self.params, self.mesh, self.events_inline, self.halo_cap,
                tuple(programs), ncols,
            )
            jax.block_until_ready(
                jit_sp(*zeros, *zeros, perm, empty_band, empty_band,
                       *logic_dev)[2])
        jit_fb = _jitted_sharded_step_fused(
            self.params, self.mesh, self.events_inline,
            tuple(programs), ncols,
        )
        jax.block_until_ready(jit_fb(*zeros, *zeros, *logic_dev)[2])

    def fused_trace_count(self, programs: tuple) -> int:
        """Trace count of the fused SPATIAL jit for ``programs`` (the
        no-fresh-trace restore gate; the fallback jit is warmed alongside
        but not counted here)."""
        if self.backend != "jnp":
            jit_sp: object = _jitted_spatial_step_pallas_fused(
                self.params, self.mesh, self.events_inline, self.halo_cap,
                self.backend == "pallas_interpret", self.strip_cols,
                tuple(programs), self._warmed_ncols(programs),
            )
        else:
            jit_sp = _jitted_spatial_step_fused(
                self.params, self.mesh, self.events_inline, self.halo_cap,
                tuple(programs), self._warmed_ncols(programs),
            )
        try:
            return int(jit_sp._cache_size())
        except Exception:  # pragma: no cover - private-API drift
            return -1

    def _note_step_flags(self, flags: int) -> None:
        """Header-flag hook (ShardedPendingStep.collect): bit 0 = the
        seam-free single-pass guard held for the collected tick."""
        self.last_fast_tick = bool(flags & 1)
        if flags & 1:
            self.total_fast_ticks += 1
            _M_FAST_TICKS.inc()

    @staticmethod
    def _warmed_ncols(programs: tuple) -> int:
        return sum(len(p.columns) for p in programs)

    def _build_bands(self, cx, cur_act, prev_act):
        """Per-shard send-index arrays for both seams (flattened
        [D*halo_cap], sentinel chunk) from current AND previous columns."""
        gx = self.params.grid_x
        d = self.n_devices
        h = self.halo_cap
        rel = np.flatnonzero(cur_act | prev_act)
        sh = self.assign[rel]
        lo = self.boundaries[sh]
        hi = self.boundaries[sh + 1]
        c = cx[rel]
        pc = self._prev_cx[rel]

        def in_lo_band(col, act_mask):
            return act_mask & (((col - (lo - 1)) % gx) < 3)

        def in_hi_band(col, act_mask):
            return act_mask & (((col - (hi - 2)) % gx) < 3)

        ca = cur_act[rel]
        pa = prev_act[rel]
        low = in_lo_band(c, ca) | in_lo_band(pc, pa)
        high = in_hi_band(c, ca) | in_hi_band(pc, pa)
        if d == 2:
            # Ring of two: both bands land on the same peer — one copy.
            high &= ~low
        send_lo = np.full(d * h, self.chunk, np.int32)
        send_hi = np.full(d * h, self.chunk, np.int32)
        counts = np.zeros((d, 2), np.int64)
        for s in range(d):
            for i, (mask, buf) in enumerate(((low, send_lo),
                                             (high, send_hi))):
                slots = rel[mask & (sh == s)]
                if len(slots) > h:
                    self._last_band_counts = None
                    return None, None, True
                rows = np.sort(self.row_of[slots] - s * self.chunk)
                buf[s * h:s * h + len(rows)] = rows
                counts[s, i] = len(rows)
        self._last_band_counts = counts
        return send_lo, send_hi, False

    def _page(self, ctx: tuple, deficit: np.ndarray, starts: np.ndarray):
        """Per-shard chunked drain for events beyond the inline budget;
        ctx[0] picks the program: "spatial" = jnp id-matrix drain (flat-
        index paging), "pallas" = strip-local bit drain (event-RANK
        paging), anything else = the jnp all-gather fallback drain."""
        mode = ctx[0]
        chunks: list[np.ndarray] = []
        starts = starts.copy()
        deficit = deficit.copy()
        rank_paging = mode == "pallas"
        while deficit.any():
            st = jax.device_put(
                np.asarray(starts, np.int32), self._sharding
            )
            if mode == "pallas":
                pairs, aux = self._jit_drain(*ctx[1:6], ctx[6], st)
            elif mode == "spatial":
                pairs, aux = self._jit_drain(ctx[1], ctx[2], st)
            else:
                pairs, aux = self._jit_fallback_drain(ctx[1], st)
            pairs = np.asarray(pairs)
            aux = np.asarray(aux)
            e = self.events_inline
            for d in range(self.n_devices):
                take = int(min(e, deficit[d]))
                if take <= 0:
                    continue
                chunks.append(pairs[d * e:d * e + take])
                deficit[d] -= take
                if rank_paging:
                    starts[d] += take
                elif deficit[d] > 0:
                    starts[d] = aux[d, take - 1] + 1
                else:
                    starts[d] = self._flat_end
        return chunks

    def step(self, pos, active, space, radius):
        return self.step_async(pos, active, space, radius).collect()


class _FallbackPendingStep(ShardedPendingStep):
    """A fallback tick's pending step: the all-gather program speaks ROW
    ids — map the collected pairs back to entity slots through the row
    permutation snapshotted at dispatch (the live perm may rotate under a
    pipelined consumer before collect())."""

    __slots__ = ("_perm",)

    def __init__(self, engine, enter_ctx, leave_ctx, out, perm) -> None:
        super().__init__(engine, enter_ctx, leave_ctx, out)
        self._perm = perm

    def collect(self):
        enters, leaves, dropped = super().collect()
        if len(enters):
            enters = self._perm[enters]
        if len(leaves):
            leaves = self._perm[leaves]
        return enters, leaves, dropped
