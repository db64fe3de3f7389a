"""Entity-sharded AOI over a device mesh.

The reference scales by sharding entities/spaces across game processes, with
no cross-process AOI at all (SURVEY.md §5.7: AOI is strictly per-Space,
per-game). The TPU-native design goes further: entity slots are sharded over
a mesh axis; each tick, **positions are all-gathered over ICI** so every
device sees the whole world, then each device computes the enter/leave event
diffs only for the entity rows it owns (the same event-native two-grid
pairwise formulation as ops/neighbor.py — exact sets, no truncation). This
is the "sequence parallelism" of this domain (BASELINE.json config 5: 1M
entities, 8 game processes → v5e-16 pod).

Communication per tick = one all-gather of the per-entity feature arrays
(~1 MB at 100k entities) — rides ICI, far below its bandwidth. Grid builds
are replicated per device (cheap: one sort of N keys each); the O(N·9M)
candidate math — the actual FLOPs — is perfectly sharded on query rows.

Host interface parity with the single-device engine (round-2 upgrade):
``step_async`` dispatches without blocking and ``collect()`` performs
exactly ONE blocking device→host read — every shard packs its header +
inline event pairs into one stacked ``[D * (3 + 2E), 2]`` buffer. Event
storms beyond the inline budget page through per-shard chunked drains.
The inline budget is divided: each shard keeps ``E = max_events / D``,
so the tier's total stays ``max_events`` (the world does not grow with
the chips here; the spatial tier, parallel/spatial.py, keeps
``max_events`` on each chip instead).

Collectives are XLA's (all_gather inside shard_map); there is no NCCL/MPI
analog to port — the reference's TCP star stays the control plane
(SURVEY.md §5.8).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from goworld_tpu.telemetry import sentinel
from goworld_tpu.telemetry.phases import engine_span
from goworld_tpu.ops.neighbor import (
    LANES,
    _PACK,
    NeighborParams,
    _apply_fused_logic,
    _bins,
    _build_table,
    _fast_guard,
    _compiled_event_kernel,
    _drain_bits,
    _drain_ids,
    _epoch_mask,
    _gather_cands,
    _scatter_feats,
    check_radius,
    check_space_ids,
    start_host_copy,
)

SHARD_AXIS = "shard"

from goworld_tpu import telemetry  # noqa: E402  (after SHARD_AXIS constant)

# Transfer accounting for the all-gather tiers (ISSUE 15 satellite): what
# one entity-sharded tick structurally moves between devices — every
# other shard's rows, both epochs — live beside the spatial tier's halo
# gauges so the comms story is comparable on /metrics, /cluster and
# gwtop. Module-scope registration (gwlint R5); same family the spatial
# engine's fallback ticks account into.
_M_ALLGATHER_EQUIV = telemetry.gauge(
    "aoi_allgather_equiv_bytes_per_tick",
    "What the all-gather formulation moves per tick at this tier (every "
    "other shard's rows, both epochs, on D devices).",
)
_M_ALLGATHER_TOTAL = telemetry.counter(
    "aoi_allgather_bytes_total",
    "Bytes moved between shards by all-gather AOI ticks (the entity-"
    "sharded tier every tick; the spatial tier only on exact-fallback "
    "ticks).",
)
# Per-link transfer accounting (ROADMAP item 5): what each receiving
# device/host/seam pulls per tick, attributable after the fact through
# the history frames every process records. tier: ici-allgather (entity-
# sharded within a host), dcn-allgather (multihost cross-host slice),
# halo (the spatial tier's seam ppermute — OBSERVED band occupancy, not
# the structural halo_cap envelope).
_M_LINK_BYTES = telemetry.counter(
    "aoi_link_bytes_total",
    "Per-link device-comms bytes by tier (ici-allgather / dcn-allgather "
    "/ halo) and link (receiving device, host slice, or strip seam).",
    ("tier", "link"),
)


def make_mesh(n_devices: int | None = None, devices: list | None = None) -> Mesh:
    """Build a 1-D mesh over the entity-shard axis.

    Prefers explicitly passed devices; otherwise takes the first n of
    jax.devices(), and too few is an error. For CPU-hosted multi-device
    testing, set ``--xla_force_host_platform_device_count``
    (tests/conftest.py does).
    """
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            if len(devices) < n_devices:
                raise ValueError(
                    f"need {n_devices} devices, have {len(devices)} "
                    f"{devices[0].platform}"
                )
            devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (SHARD_AXIS,))


def _sharded_step(
    p: NeighborParams,
    events_inline: int,  # per-shard inline event budget E
    ppos_l, pact_l, pspc_l, prad_l,  # this shard's previous-tick rows
    pos_l, act_l, spc_l, rad_l,  # this shard's current-tick rows
):
    """Per-shard body run under shard_map. Returns
    (enter_ids [chunk, 9M], leave_ids [chunk, 9M], out [3+2E, 2])."""
    n = p.capacity
    m = p.cell_capacity
    chunk = pos_l.shape[0]
    shard = jax.lax.axis_index(SHARD_AXIS)
    q_ids = shard * chunk + jnp.arange(chunk, dtype=jnp.int32)

    # ICI all-gather: full world view of both epochs on every device.
    gather = lambda x: jax.lax.all_gather(x, SHARD_AXIS, tiled=True)  # noqa: E731
    pos, act, spc, rad = gather(pos_l), gather(act_l), gather(spc_l), gather(rad_l)
    ppos, pact, pspc, prad = (
        gather(ppos_l), gather(pact_l), gather(pspc_l), gather(prad_l),
    )

    cxc, czc, smc = _bins(p, pos, spc)
    cxp, czp, smp = _bins(p, ppos, pspc)
    buc_c = (smc * p.grid_z + czc) * p.grid_x + cxc
    buc_p = (smp * p.grid_z + czp) * p.grid_x + cxp
    # Replicated table builds (one N-key sort each); identical on all shards.
    table_c, slot_c, dropped_c, _, _, _ = _build_table(p, buc_c, act, m)
    table_p, slot_p, _, _, _, _ = _build_table(p, buc_p, pact, m)
    av_c = slot_c >= 0
    av_p = slot_p >= 0

    lo = shard * chunk
    sl = lambda x: jax.lax.dynamic_slice_in_dim(x, lo, chunk)  # noqa: E731
    sl2 = lambda x: jax.lax.dynamic_slice_in_dim(x, lo, chunk, axis=0)  # noqa: E731

    # Enter pass: candidates from the current grid, this shard's queries.
    cand_c = _gather_cands(p, table_c, sl(cxc), sl(czc), sl(smc))
    vc = _epoch_mask(p, cand_c, q_ids, sl2(pos), sl(av_c), sl(spc), sl(rad),
                     pos, av_c, spc)
    vp_on_c = _epoch_mask(p, cand_c, q_ids, sl2(ppos), sl(av_p), sl(pspc),
                          sl(prad), ppos, av_p, pspc)
    enter_mask = vc & ~vp_on_c

    # Leave pass: single-pass fast path when the displacement guard holds
    # (ops/neighbor._step_jnp — the guard's inputs are replicated after the
    # all-gather, so the cond resolves identically on every shard).
    fast = _fast_guard(p, ppos, pact, pspc, prad, pos, act, spc, dropped_c)

    def fast_fn():
        return vp_on_c & ~vc, cand_c

    def slow_fn():
        cand_p = _gather_cands(p, table_p, sl(cxp), sl(czp), sl(smp))
        vp = _epoch_mask(p, cand_p, q_ids, sl2(ppos), sl(av_p), sl(pspc),
                         sl(prad), ppos, av_p, pspc)
        vc_on_p = _epoch_mask(p, cand_p, q_ids, sl2(pos), sl(av_c), sl(spc),
                              sl(rad), pos, av_c, spc)
        return vp & ~vc_on_p, cand_p

    leave_mask, cand_l = jax.lax.cond(fast, fast_fn, slow_fn)

    enter_ids = jnp.where(enter_mask, cand_c, n)
    leave_ids = jnp.where(leave_mask, cand_l, n)
    n_enters = jnp.sum(enter_mask).astype(jnp.int32)
    n_leaves = jnp.sum(leave_mask).astype(jnp.int32)

    def globalize(pairs):
        ent = pairs[:, 0]
        ent = jnp.where(ent < chunk, ent + lo, n)
        return jnp.stack([ent, pairs[:, 1]], axis=1)

    ep, ei = _drain_ids(enter_ids, n, events_inline, jnp.int32(0))
    lp, li = _drain_ids(leave_ids, n, events_inline, jnp.int32(0))
    header = jnp.stack(
        [
            jnp.stack([n_enters, n_leaves]),
            jnp.stack([dropped_c, jnp.int32(0)]),
            jnp.stack([ei[events_inline - 1], li[events_inline - 1]]),
        ]
    ).astype(jnp.int32)
    # EVERY shard's counts, replicated into each block: a multi-controller
    # host (parallel/multihost.py) can only read its own shards, but storm
    # paging must dispatch the SAME number of global drain calls on every
    # process — the replicated counts are what make the loops converge.
    counts_all = jax.lax.all_gather(header[0], SHARD_AXIS)  # [D, 2]
    out = jnp.concatenate(
        [header, counts_all, globalize(ep), globalize(lp)], axis=0
    )
    return enter_ids, leave_ids, out


def _sharded_step_pallas(
    p: NeighborParams,
    events_inline: int,
    interpret: bool,
    n_dev: int,
    ppos_l, pact_l, pspc_l, prad_l,
    pos_l, act_l, spc_l, rad_l,
):
    """Per-shard body running the dense-cell Pallas kernel on a SLAB of the
    grid (VERDICT r2 #3: pod = single-chip kernel × N, not oracle × N).

    Inputs stay entity-row sharded (the host's natural layout) and are
    all-gathered over ICI; the *work* is sharded over grid rows: each device
    scatters the replicated cell layout, slices its ``grid_z / D`` rows
    (plus torus halo), launches the kernel there, and drains events for the
    entities binned in its slab — every event is emitted exactly once
    because each entity lives in exactly one cell per pass.
    """
    n = p.capacity
    # n_dev rides in statically from the jit builder: jax.lax.axis_size
    # does not exist on this image's jax (0.4.37), and the mesh size is a
    # compile-time constant here anyway (rows must be static).
    rows = p.grid_z // n_dev
    shard = jax.lax.axis_index(SHARD_AXIS)
    lo = shard * rows
    w_words = 9 * LANES // _PACK
    kernel = _compiled_event_kernel(p, interpret, rows)
    kernel_dual = _compiled_event_kernel(p, interpret, rows, dual=True)

    gather = lambda x: jax.lax.all_gather(x, SHARD_AXIS, tiled=True)  # noqa: E731
    pos, act, spc, rad = gather(pos_l), gather(act_l), gather(spc_l), gather(rad_l)
    ppos, pact, pspc, prad = (
        gather(ppos_l), gather(pact_l), gather(pspc_l), gather(prad_l),
    )

    # Build both epochs' grids ONCE; each pass then shares them (the enter
    # pass's candidate grid is the leave pass's B-visibility grid and vice
    # versa — building per pass would do 4 argsorts where 2 suffice).
    def one_grid(xpos, xact, xspc):
        cx, cz, sm = _bins(p, xpos, xspc)
        buc = (sm * p.grid_z + cz) * p.grid_x + cx
        table, slot, dropped, order, dst, _ = _build_table(
            p, buc, xact, LANES)
        return cx, cz, sm, table, slot, dropped, order, dst

    cxc, czc, smc, table_c, slot_c, dropped_c, order_c, dst_c = one_grid(
        pos, act, spc
    )
    cxp, czp, smp, table_p, slot_p, _, order_p, dst_p = one_grid(
        ppos, pact, pspc
    )
    # x rows poisoned by each epoch's own slot validity (ops/neighbor:
    # _step_pallas) — NaN replaces the av occupancy rows of round 2.
    xs_c = jnp.where(slot_c >= 0, pos[:, 0], jnp.nan)
    xs_p = jnp.where(slot_p >= 0, ppos[:, 0], jnp.nan)
    cur_feats = (xs_c, pos[:, 1], spc, rad)
    prev_feats = (xs_p, ppos[:, 1], pspc, prad)

    cells_c = _scatter_feats(p, dst_c, order_c, cur_feats, prev_feats)
    slab_c = jax.lax.dynamic_slice_in_dim(cells_c, lo, rows + 2, axis=1)

    # Single-launch fast path (ops/neighbor._step_pallas): the guard's
    # inputs are replicated after the all-gather, so the cond resolves
    # identically on every shard. Fast ticks run ONE dual-output kernel on
    # the current grid's slab; other ticks pay the second feats+kernel pass
    # on the previous grid.
    fast = _fast_guard(p, ppos, pact, pspc, prad, pos, act, spc, dropped_c)

    def fast_fn():
        pk2 = kernel_dual(slab_c)  # [S, rows, gx, LANES, 2W]
        return (pk2[..., :w_words], pk2[..., w_words:],
                cxc, czc, smc, table_c, slot_c)

    def slow_fn():
        pk_e = kernel(slab_c)
        cells_p = _scatter_feats(p, dst_p, order_p, prev_feats, cur_feats)
        slab_p = jax.lax.dynamic_slice_in_dim(cells_p, lo, rows + 2, axis=1)
        pk_l = kernel(slab_p)
        return (pk_e, pk_l, cxp, czp, smp, table_p, slot_p)

    pk_e, pk_l, lcx, lcz, lsm, ltable, lslot = jax.lax.cond(
        fast, fast_fn, slow_fn
    )

    def extract(packed_cells, cx, cz, sm, slot):
        """Per-entity packed words for entities binned in THIS slab."""
        lane = slot % LANES
        local_bucket = (sm * rows + (cz - lo)) * p.grid_x + cx
        local_flat = local_bucket * LANES + lane
        mine = (slot >= 0) & (cz >= lo) & (cz < lo + rows)
        flat = packed_cells.reshape(-1, w_words)
        safe = jnp.clip(local_flat, 0, flat.shape[0] - 1)
        pe = jnp.where(mine[:, None], flat[safe], 0)  # i32[N, W]
        return pe, jnp.sum(jax.lax.population_count(pe)).astype(jnp.int32)

    packed_e, n_enters = extract(pk_e, cxc, czc, smc, slot_c)
    packed_l, n_leaves = extract(pk_l, lcx, lcz, lsm, lslot)

    ep, _ = _drain_bits(p, packed_e, cxc, czc, smc, table_c, jnp.int32(0),
                        max_events=events_inline)
    lp, _ = _drain_bits(p, packed_l, lcx, lcz, lsm, ltable, jnp.int32(0),
                        max_events=events_inline)
    zero = jnp.int32(0)
    header = jnp.stack(
        [
            jnp.stack([n_enters, n_leaves]),
            jnp.stack([dropped_c, zero]),
            jnp.stack([zero, zero]),  # rank paging resumes at events_inline
        ]
    ).astype(jnp.int32)
    # Replicated per-shard counts — see _sharded_step (multihost paging).
    counts_all = jax.lax.all_gather(header[0], SHARD_AXIS)  # [D, 2]
    out = jnp.concatenate([header, counts_all, ep, lp], axis=0)
    enter_ctx = (packed_e, cxc, czc, smc, table_c)
    leave_ctx = (packed_l, lcx, lcz, lsm, ltable)
    return enter_ctx + leave_ctx + (out,)


def _sharded_drain_bits(
    p: NeighborParams, events_inline: int,
    packed_l, cx_l, cz_l, sm_l, table_l,  # per-shard drain context
    start_l: jax.Array,  # [1] resume RANK
):
    """Pallas-path storm paging: rows are global entity ids already."""
    pairs, total = _drain_bits(
        p, packed_l, cx_l, cz_l, sm_l, table_l, start_l[0],
        max_events=events_inline,
    )
    return pairs, total[None]


def _sharded_drain(
    p: NeighborParams, events_inline: int, chunk: int,
    ids_l: jax.Array,  # [chunk, 9M] this shard's event-id matrix
    start_l: jax.Array,  # [1] this shard's resume cursor (local flat index)
):
    n = p.capacity
    shard = jax.lax.axis_index(SHARD_AXIS)
    pairs, idx = _drain_ids(ids_l, n, events_inline, start_l[0])
    ent = jnp.where(pairs[:, 0] < chunk, pairs[:, 0] + shard * chunk, n)
    pairs = jnp.stack([ent, pairs[:, 1]], axis=1)
    return pairs, idx[None]


def _sharded_step_fused(
    p: NeighborParams, events_inline: int, programs,
    ppos_l, pact_l, pspc_l, prad_l,
    pos_l, act_l, spc_l, rad_l,
    y_l, yaw_l, sel_l, dt_l, *cols_l,
):
    """The all-gather step plus fused entity logic on this shard's LOCAL
    rows (elementwise — no extra comms). Used by the spatial engine's
    exact-fallback ticks so a teleport/overflow tick still advances the
    fused programs; outputs are in ROW space, mapped back through the
    dispatch-time perm snapshot by the caller."""
    enter_ids, leave_ids, out = _sharded_step(
        p, events_inline,
        ppos_l, pact_l, pspc_l, prad_l,
        pos_l, act_l, spc_l, rad_l,
    )
    new_pos, new_y, new_yaw, new_cols = _apply_fused_logic(
        programs, pos_l, y_l, yaw_l, sel_l, dt_l[0], cols_l
    )
    return enter_ids, leave_ids, out, (new_pos, new_y, new_yaw) + new_cols


@functools.lru_cache(maxsize=None)
def _jitted_sharded_step_fused(
    params: NeighborParams, mesh: Mesh, events_inline: int,
    programs: tuple, n_cols: int,
):
    body = functools.partial(
        _sharded_step_fused, params, events_inline, programs
    )
    spec = P(SHARD_AXIS)
    mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec,) * (12 + n_cols),
        out_specs=(spec, spec, spec, (spec,) * (3 + n_cols)),
    )
    return sentinel.SentinelJit("sharded_step_fused", jax.jit(mapped))


@functools.lru_cache(maxsize=None)
def _jitted_sharded_step(params: NeighborParams, mesh: Mesh, events_inline: int):
    body = functools.partial(_sharded_step, params, events_inline)
    spec = P(SHARD_AXIS)
    mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec,) * 8,
        out_specs=(spec, spec, spec),
    )
    # No donation: no output shares the previous-position buffer's
    # float32 layout, so XLA could never reuse it — donating only produced
    # the "Some donated buffers were not usable" dryrun warning. (The
    # previous meta buffers must not be donated regardless: with
    # meta_dirty=False they are passed as both epochs' meta.)
    return sentinel.SentinelJit("sharded_step", jax.jit(mapped))


@functools.lru_cache(maxsize=None)
def _jitted_sharded_step_pallas(
    params: NeighborParams, mesh: Mesh, events_inline: int, interpret: bool
):
    body = functools.partial(
        _sharded_step_pallas, params, events_inline, interpret,
        mesh.devices.size,
    )
    spec = P(SHARD_AXIS)
    mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec,) * 8,
        out_specs=(spec,) * 11,
        # pallas_call's out_shape carries no varying-mesh-axes annotation;
        # skip the vma check (outputs are explicitly per-shard here anyway).
        check_vma=False,
    )
    # No donation — same unusable-layout reasoning as _jitted_sharded_step.
    return sentinel.SentinelJit("sharded_step_pallas", jax.jit(mapped))


@functools.lru_cache(maxsize=None)
def _jitted_sharded_drain(
    params: NeighborParams, mesh: Mesh, events_inline: int, chunk: int
):
    body = functools.partial(_sharded_drain, params, events_inline, chunk)
    spec = P(SHARD_AXIS)
    mapped = jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec), out_specs=(spec, spec)
    )
    return sentinel.SentinelJit("sharded_drain", jax.jit(mapped))


@functools.lru_cache(maxsize=None)
def _jitted_sharded_drain_bits(
    params: NeighborParams, mesh: Mesh, events_inline: int
):
    body = functools.partial(_sharded_drain_bits, params, events_inline)
    spec = P(SHARD_AXIS)
    mapped = jax.shard_map(
        body, mesh=mesh, in_specs=(spec,) * 6, out_specs=(spec, spec)
    )
    return sentinel.SentinelJit("sharded_drain_bits", jax.jit(mapped))


class ShardedPendingStep:
    """In-flight sharded tick; ``collect()`` = ONE blocking host read of the
    stacked per-shard packed buffers, then (rare) storm paging."""

    __slots__ = ("_engine", "_enter_ctx", "_leave_ctx", "_out", "_collected",
                 "fused", "rank_paging", "full_repage")

    def __init__(self, engine, enter_ctx, leave_ctx, out) -> None:
        self._engine = engine
        self._enter_ctx = enter_ctx  # per-backend paging payload tuple
        self._leave_ctx = leave_ctx
        self._out = out
        self._collected = False
        # Fused-tick payload (same contract as PendingStep.fused): set by
        # the dispatching engine when the launch carried entity logic.
        self.fused = None
        # Paging cursor semantics of THIS tick's program: rank-based
        # (pallas bit drains) vs flat-index (jnp id drains). Engine-level
        # default; the spatial engine overrides per dispatch — its
        # pallas-backend SPATIAL ticks page by rank while its jnp
        # all-gather FALLBACK ticks page by flat index.
        self.rank_paging = engine.backend != "jnp"
        # In-kernel-drain ticks (parallel/spatial.py, ISSUE 19 leg b) emit
        # inline pairs in cell-major order: a shard whose events overflow
        # the inline budget cannot resume that window by rank — collect()
        # then discards the shard's inline rows and repages it from rank 0
        # through the XLA drain.
        self.full_repage = False
        start_host_copy(out)

    def is_ready(self) -> bool:
        """Non-blocking readiness probe (parity with PendingStep)."""
        try:
            return bool(self._out.is_ready())
        except AttributeError:
            return True

    def wait_device(self) -> None:
        """Block until the sharded step finishes computing (parity with
        PendingStep.wait_device — the aoi.drain latency seam)."""
        jax.block_until_ready(self._out)

    def collect(self) -> tuple[np.ndarray, np.ndarray, int]:
        assert not self._collected, "ShardedPendingStep already collected"
        self._collected = True
        eng = self._engine
        e = eng.events_inline
        nd = eng.n_devices
        # Block layout: 3 header rows, nd replicated-counts rows
        # (multihost paging convergence), e enter pairs, e leave pairs.
        block = 3 + nd + 2 * e
        with engine_span("wait"):
            self._out.block_until_ready()
        with engine_span("readback"):
            out = np.asarray(self._out)  # THE round trip
        enters, leaves = [], []
        enter_deficit = np.zeros(nd, np.int64)
        leave_deficit = np.zeros(nd, np.int64)
        enter_starts = np.zeros(nd, np.int32)
        leave_starts = np.zeros(nd, np.int32)
        dropped = 0
        rank_paging = self.rank_paging
        full_repage = self.full_repage
        for d in range(nd):
            o = out[d * block:(d + 1) * block]
            n_e, n_l = int(o[0, 0]), int(o[0, 1])
            dropped = int(o[1, 0])  # replicated diagnostic, same on all
            if full_repage and n_e > e:
                enter_deficit[d] = n_e  # whole shard through the XLA drain
                enter_starts[d] = 0
            else:
                enters.append(o[3 + nd:3 + nd + min(n_e, e)])
                enter_deficit[d] = max(0, n_e - e)
                enter_starts[d] = e if rank_paging else int(o[2, 0]) + 1
            if full_repage and n_l > e:
                leave_deficit[d] = n_l
                leave_starts[d] = 0
            else:
                leaves.append(o[3 + nd + e:3 + nd + e + min(n_l, e)])
                leave_deficit[d] = max(0, n_l - e)
                leave_starts[d] = e if rank_paging else int(o[2, 1]) + 1
        if enter_deficit.any():
            with engine_span("page"):
                enters += eng._page(self._enter_ctx, enter_deficit,
                                    enter_starts)
        if leave_deficit.any():
            with engine_span("page"):
                leaves += eng._page(self._leave_ctx, leave_deficit,
                                    leave_starts)
        eng.last_grid_dropped = dropped
        # Header flags (out[1, 1], replicated): the spatial engines report
        # the seam-free fast-tick bit there; other programs write 0.
        note = getattr(eng, "_note_step_flags", None)
        if note is not None:
            note(int(out[1, 1]))
        return (
            np.concatenate(enters) if enters else np.empty((0, 2), np.int32),
            np.concatenate(leaves) if leaves else np.empty((0, 2), np.int32),
            dropped,
        )


class ShardedNeighborEngine:
    """Multi-device AOI engine: same semantics and event stream as the
    single-device engine, with entity rows sharded over a mesh
    (slot i lives on device i // (N / D)).

    ``backend``: "auto" = the Pallas slab kernel on TPU, the jnp candidate
    math elsewhere; "pallas" / "pallas_interpret" / "jnp" force a path. The
    Pallas path shards the KERNEL GRID (``grid_z / D`` rows per device)
    while inputs stay row-sharded — pod = single-chip kernel × N.
    """

    def __init__(self, params: NeighborParams, mesh: Mesh,
                 backend: str = "auto"):
        if backend == "auto":
            backend = "pallas" if jax.default_backend() == "tpu" else "jnp"
        if backend not in ("jnp", "pallas", "pallas_interpret"):
            raise ValueError(f"unknown backend {backend!r}")
        n_dev = mesh.devices.size
        if params.capacity % (8 * n_dev) != 0:
            raise ValueError(
                f"capacity {params.capacity} must be a multiple of 8*{n_dev}"
            )
        if params.max_events % n_dev != 0:
            raise ValueError(
                f"max_events {params.max_events} must be divisible by {n_dev}"
            )
        if backend != "jnp" and params.cell_capacity > LANES:
            raise ValueError(
                f"pallas path supports cell_capacity <= {LANES}, "
                f"got {params.cell_capacity}"
            )
        if backend != "jnp" and params.grid_z % n_dev != 0:
            raise ValueError(
                f"pallas path needs grid_z {params.grid_z} divisible by "
                f"{n_dev} (one slab of rows per device)"
            )
        self.params = params
        self.mesh = mesh
        self.backend = backend
        self.n_devices = n_dev
        self.chunk = params.capacity // n_dev
        # Inline budget per shard; total inline capacity stays max_events.
        self.events_inline = params.max_events // n_dev
        # Structural comms of one tick: every other shard's rows, both
        # epochs (pos 8B + act 1B + spc 4B + rad 4B each), on D devices.
        self.allgather_bytes_per_tick = (
            n_dev * (params.capacity - self.chunk) * 34
        )
        _M_ALLGATHER_EQUIV.set(self.allgather_bytes_per_tick)
        # Per-link split of the same structural total: each device pulls
        # every OTHER shard's rows (children prebuilt — label lookups
        # stay out of the tick).
        self._link_bytes = (params.capacity - self.chunk) * 34
        self._link_children = tuple(
            _M_LINK_BYTES.labels("ici-allgather", f"dev{d}")
            for d in range(n_dev))
        if backend == "jnp":
            self._jit_step = _jitted_sharded_step(
                params, mesh, self.events_inline
            )
            self._jit_drain = _jitted_sharded_drain(
                params, mesh, self.events_inline, self.chunk
            )
            self._flat_end = self.chunk * 9 * params.cell_capacity
        else:
            self._jit_step = _jitted_sharded_step_pallas(
                params, mesh, self.events_inline, backend == "pallas_interpret"
            )
            self._jit_drain = _jitted_sharded_drain_bits(
                params, mesh, self.events_inline
            )
            self._flat_end = params.capacity * 9 * LANES
        self._sharding = NamedSharding(mesh, P(SHARD_AXIS))
        self._state: tuple | None = None
        self.last_grid_dropped = 0

    def reset(self) -> None:
        n = self.params.capacity
        put = lambda x: jax.device_put(x, self._sharding)  # noqa: E731
        # device_put from NUMPY, never from an intermediate jax array: a jax
        # array can carry a sharding over the same device set in a different
        # order, which trips jax's different-device-order reshard path
        # (dispatch.py _different_device_order_reshard asserts NamedSharding).
        self._state = (
            put(np.zeros((n, 2), np.float32)),
            put(np.zeros((n,), bool)),
            put(np.zeros((n,), np.int32)),
            put(np.zeros((n,), np.float32)),
        )

    def carried_epoch(self) -> tuple:
        """Last dispatched world in slot space (rows == slots here);
        see NeighborEngine.carried_epoch."""
        assert self._state is not None, "call reset() first"
        return tuple(np.asarray(a) for a in self._state[0:4])

    def _page(
        self, ctx: tuple, deficit: np.ndarray, starts: np.ndarray
    ) -> list[np.ndarray]:
        """Per-shard chunked drain for events beyond the inline budget."""
        chunks: list[np.ndarray] = []
        starts = starts.copy()
        deficit = deficit.copy()
        rank_paging = self.backend != "jnp"
        while deficit.any():
            pairs, aux = self._jit_drain(
                *ctx, jax.device_put(np.asarray(starts, np.int32), self._sharding)
            )
            pairs = np.asarray(pairs)
            aux = np.asarray(aux)
            e = self.events_inline
            for d in range(self.n_devices):
                take = int(min(e, deficit[d]))
                if take <= 0:
                    continue
                chunks.append(pairs[d * e:d * e + take])
                deficit[d] -= take
                if deficit[d] > 0:
                    starts[d] = (
                        starts[d] + take if rank_paging else aux[d, take - 1] + 1
                    )
                else:
                    starts[d] = self._flat_end
        return chunks

    def step_async(
        self,
        pos: np.ndarray,
        active: np.ndarray,
        space: np.ndarray,
        radius: np.ndarray,
        meta_dirty: bool = True,
    ) -> ShardedPendingStep:
        """Dispatch one tick without blocking (parity with NeighborEngine,
        including the ``meta_dirty=False`` upload-elision contract)."""
        assert self._state is not None, "call reset() first"
        put = lambda x: jax.device_put(x, self._sharding)  # noqa: E731
        with engine_span("upload"):
            check_radius(self.params, radius, active)
            if self.backend != "jnp":
                check_space_ids(space, active)
            # np.array (copying, not asarray): state must not alias caller
            # buffers — see NeighborEngine.step_async. Numpy (not jnp)
            # inputs by design: see reset().
            if meta_dirty:
                meta = (
                    put(np.array(active, bool)),
                    put(np.array(space, np.int32)),
                    put(np.array(radius, np.float32)),
                )
            else:
                meta = self._state[1:4]
            cur = (put(np.array(pos, np.float32)),) + meta
        with engine_span("launch"):
            res = self._jit_step(*self._state, *cur)
        if self.backend == "jnp":
            enter_ids, leave_ids, out = res
            enter_ctx: tuple = (enter_ids,)
            leave_ctx: tuple = (leave_ids,)
        else:
            enter_ctx, leave_ctx, out = res[0:5], res[5:10], res[10]
        self._state = cur
        _M_ALLGATHER_TOTAL.inc(self.allgather_bytes_per_tick)
        for child in self._link_children:
            child.inc(self._link_bytes)
        return ShardedPendingStep(self, enter_ctx, leave_ctx, out)

    def step(
        self,
        pos: np.ndarray,
        active: np.ndarray,
        space: np.ndarray,
        radius: np.ndarray,
    ) -> tuple[np.ndarray, np.ndarray, int]:
        """Run one tick; returns host (enter_pairs, leave_pairs, dropped)."""
        return self.step_async(pos, active, space, radius).collect()
