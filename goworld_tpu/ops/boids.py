"""Fused boids/flocking kernel — AOI neighbor query + kNN steering in one
Pallas launch (BASELINE.json config 4: 50k agents, fused kernel).

Where the generic engine (ops/neighbor.py) must *materialize* neighbor sets
for the host, steering behaviors only need neighbor *reductions* — so the
whole pipeline fuses on-chip: no [N, 9M] candidate intermediates ever reach
HBM, and nothing but the integrated positions/velocities leaves the device.

Layout strategy (chosen for TPU, not translated from anything): entities are
binned into grid cells of side ``cell_size`` (= interaction radius) and
packed into a DENSE per-cell layout ``[gz, gx, feature, lane]`` with
``lane`` = cell capacity = 128 (one full TPU lane dim). After a wrap-pad of
the spatial dims, every cell's 3x3 neighborhood is a contiguous [3, 3]
block — the kernel DMAs it HBM→VMEM and does all pairwise math in VMEM:

    per program (one cell):  q = center cell [F, 128]
                             c = 3x3 block   [3, 3, F, 128] → [F, 1152]
                             pairwise [128, 1152] masks/forces on the VPU

Forces are the classic triple (Reynolds 1987, public-domain math):
separation (inverse-square repulsion inside ``sep_frac * radius``),
alignment (match mean neighbor velocity), cohesion (steer to mean neighbor
position). Integration is symplectic Euler with speed clamping, world
wrapped to the grid torus.

The reference has no analog of this subsystem (its AOI stops at interest
sets, SURVEY.md §2.9); this is the TPU-native extension the baseline asks
for. CPU tests run the same kernel under ``interpret=True``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from goworld_tpu.ops.neighbor import sorted_ranks

LANES = 128  # cell capacity = one TPU lane dimension
_F = 8  # padded feature count (x, z, vx, vz, valid, 3 spare) — f32 sublane


@dataclasses.dataclass(frozen=True)
class BoidsParams:
    capacity: int = 65536  # max agents (N)
    cell_size: float = 100.0  # grid cell side; must be >= radius
    grid_x: int = 64
    grid_z: int = 64
    # Interaction radius; 0.0 = cell_size. Decoupled so SUPERCELLS can pack
    # more agents per 128-lane cell at a fixed radius (low lane occupancy
    # wastes pair math on empty lanes — the same tuning axis the neighbor
    # bench sweeps as cell_size).
    radius: float = 0.0
    sep_frac: float = 0.3  # separation acts inside sep_frac * radius
    w_sep: float = 1.5
    w_align: float = 1.0
    w_coh: float = 1.0
    max_speed: float = 8.0
    max_accel: float = 2.0
    dt: float = 1.0

    def __post_init__(self) -> None:
        if self.radius > self.cell_size:
            # The 3x3 halo only covers one cell ring: a larger radius
            # would silently miss true neighbors.
            raise ValueError(
                f"radius {self.radius} exceeds cell_size {self.cell_size}"
            )

    @property
    def r_eff(self) -> float:
        return self.radius or self.cell_size

    @property
    def world_x(self) -> float:
        return self.grid_x * self.cell_size

    @property
    def world_z(self) -> float:
        return self.grid_z * self.cell_size


def _build_cells(p: BoidsParams, pos, vel, active):
    """Pack entities into the dense per-cell layout.

    Returns (cells f32[gz+2, gx+2, F, LANES] wrap-padded, slot i32[N]) where
    ``slot`` is each entity's flat (cell, lane) address in the UNpadded grid
    (-1 when dropped because its cell overflowed LANES entities).
    """
    n = p.capacity
    cx = jnp.floor(pos[:, 0] / p.cell_size).astype(jnp.int32) % p.grid_x
    cz = jnp.floor(pos[:, 1] / p.cell_size).astype(jnp.int32) % p.grid_z
    bucket = cz * p.grid_x + cx
    num_buckets = p.grid_x * p.grid_z

    key = jnp.where(active, bucket, num_buckets)
    order, sorted_key, rank = sorted_ranks(key, n, num_buckets)
    ok = (sorted_key < num_buckets) & (rank < LANES)

    flat_size = num_buckets * LANES
    dst = jnp.where(ok, sorted_key * LANES + rank, flat_size)  # drop → OOB

    # One scatter builds the slot→entity table; features then GATHER through
    # it (TPU gathers are far cheaper than five scatters — the same change
    # as ops/neighbor._scatter_feats).
    table = jnp.full((flat_size,), n, dtype=jnp.int32)
    table = table.at[dst].set(order.astype(jnp.int32), mode="drop")
    safe = jnp.minimum(table, n - 1)
    present = table < n

    def gather(values, gate: bool = False):
        out = values[safe]
        return jnp.where(present, out, 0.0) if gate else out

    feats = jnp.stack(
        [
            gather(pos[:, 0]),
            gather(pos[:, 1]),
            gather(vel[:, 0]),
            gather(vel[:, 1]),
            gather(jnp.ones((n,), jnp.float32) * active, gate=True),
        ]
    )  # [5, num_buckets*LANES]
    feats = jnp.pad(feats, ((0, _F - 5), (0, 0)))
    cells = feats.reshape(_F, p.grid_z, p.grid_x, LANES).transpose(1, 2, 0, 3)
    # Torus halo: one wrapped ring around the spatial dims.
    cells = jnp.pad(cells, ((1, 1), (1, 1), (0, 0), (0, 0)), mode="wrap")

    # Entity → (cell, lane) address for reading results back.
    slot_sorted = jnp.where(ok, dst, -1).astype(jnp.int32)
    slot = jnp.zeros((n,), jnp.int32).at[order].set(slot_sorted)
    return cells, slot


def _boids_kernel(p: BoidsParams, cells_hbm, out_ref, scratch, sem):
    """One program per grid cell: DMA the 3x3 halo block, steer its agents.

    The halo DMA is double-buffered across grid steps (prefetch cell k+1
    during cell k's math) — the same latency fix measured on the neighbor
    kernel (ops/neighbor.py::_event_kernel)."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    gx = pl.num_programs(1)
    lin = i * gx + j
    total = pl.num_programs(0) * gx
    slot = jax.lax.rem(lin, 2)
    nslot = jax.lax.rem(lin + 1, 2)

    def halo_copy(idx_lin, buf):
        return pltpu.make_async_copy(
            cells_hbm.at[pl.ds(idx_lin // gx, 3),
                         pl.ds(jax.lax.rem(idx_lin, gx), 3)],
            scratch.at[buf],
            sem.at[buf],
        )

    @pl.when(lin == 0)
    def _():
        halo_copy(lin, slot).start()

    @pl.when(lin + 1 < total)
    def _():
        halo_copy(lin + 1, nslot).start()

    halo_copy(lin, slot).wait()
    c = scratch[slot]  # [3, 3, F, LANES]
    # Candidates: all 9 cells, feature-major [F, 9*LANES].
    cand = c.transpose(2, 0, 1, 3).reshape(_F, 9 * LANES)
    q = c[1, 1]  # center cell [F, LANES]

    qx, qz, qvx, qvz, qok = q[0], q[1], q[2], q[3], q[4]
    cx, cz, cvx, cvz, cok = cand[0], cand[1], cand[2], cand[3], cand[4]

    dx = cx[None, :] - qx[:, None]  # [LANES, 9*LANES]
    dz = cz[None, :] - qz[:, None]
    # Torus-shortest displacement (halo only covers one wrap; entities near
    # the seam read their neighbors via the pad, but distances still need
    # the minimal image for correctness at the world scale).
    wx, wz = p.world_x, p.world_z
    dx = dx - wx * jnp.round(dx / wx)
    dz = dz - wz * jnp.round(dz / wz)
    d2 = dx * dx + dz * dz

    r2 = jnp.float32(p.r_eff * p.r_eff)
    # Self-pairs: the center cell occupies candidate block 4 (row-major 3x3).
    lane = jax.lax.broadcasted_iota(jnp.int32, (LANES, 9 * LANES), 0)
    cidx = jax.lax.broadcasted_iota(jnp.int32, (LANES, 9 * LANES), 1)
    is_self = cidx == 4 * LANES + lane
    valid = (
        (qok[:, None] > 0.0)
        & (cok[None, :] > 0.0)
        & (d2 <= r2)
        & ~is_self
    )
    vf = valid.astype(jnp.float32)
    count = jnp.sum(vf, axis=1)  # [LANES]
    has_n = count > 0.0
    inv_count = jnp.where(has_n, 1.0 / jnp.maximum(count, 1.0), 0.0)

    # Separation: inverse-square push away inside the close radius.
    sep_r2 = jnp.float32((p.r_eff * p.sep_frac) ** 2)
    close = vf * (d2 < sep_r2).astype(jnp.float32)
    inv_d2 = close / (d2 + 1e-6)
    sep_x = -jnp.sum(dx * inv_d2, axis=1)
    sep_z = -jnp.sum(dz * inv_d2, axis=1)

    # Alignment: match the mean neighbor velocity.
    align_x = (jnp.sum(cvx[None, :] * vf, axis=1) * inv_count - qvx) * has_n
    align_z = (jnp.sum(cvz[None, :] * vf, axis=1) * inv_count - qvz) * has_n

    # Cohesion: steer toward the neighborhood centroid (minimal-image mean).
    coh_x = jnp.sum(dx * vf, axis=1) * inv_count
    coh_z = jnp.sum(dz * vf, axis=1) * inv_count

    ax = p.w_sep * sep_x + p.w_align * align_x + p.w_coh * coh_x
    az = p.w_sep * sep_z + p.w_align * align_z + p.w_coh * coh_z

    # Clamp acceleration magnitude.
    a2 = ax * ax + az * az
    scale = jnp.minimum(1.0, p.max_accel * jax.lax.rsqrt(a2 + 1e-12))
    out_ref[0, 0, 0] = ax * scale
    out_ref[0, 0, 1] = az * scale


@functools.lru_cache(maxsize=None)
def _compiled_accel(p: BoidsParams, interpret: bool):
    kernel = functools.partial(_boids_kernel, p)
    call = pl.pallas_call(
        kernel,
        grid=(p.grid_z, p.grid_x),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(
            (1, 1, 2, LANES), lambda i, j: (i, j, 0, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((p.grid_z, p.grid_x, 2, LANES), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((2, 3, 3, _F, LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )
    return jax.jit(call)


def _step(p: BoidsParams, interpret: bool, pos, vel, active):
    cells, slot = _build_cells(p, pos, vel, active)
    accel_cells = _compiled_accel(p, interpret)(cells)  # [gz, gx, 2, LANES]
    flat = accel_cells.transpose(0, 1, 3, 2).reshape(-1, 2)  # [(gz*gx*L), 2]
    ok = slot >= 0
    safe = jnp.maximum(slot, 0)
    accel = jnp.where(ok[:, None], flat[safe], 0.0)
    dropped = jnp.sum(active & ~ok).astype(jnp.int32)

    vel2 = vel + accel * p.dt
    speed2 = jnp.sum(vel2 * vel2, axis=1, keepdims=True)
    clamp = jnp.minimum(1.0, p.max_speed * jax.lax.rsqrt(speed2 + 1e-12))
    vel2 = vel2 * clamp
    pos2 = pos + vel2 * p.dt
    pos2 = jnp.mod(pos2, jnp.array([p.world_x, p.world_z], jnp.float32))
    return pos2, vel2, accel, dropped


@functools.lru_cache(maxsize=None)
def _jitted_step(p: BoidsParams, interpret: bool):
    return jax.jit(functools.partial(_step, p, interpret))


class BoidsEngine:
    """Stateless-per-tick flocking stepper (positions in, positions out)."""

    # Check the overflow counter once per this many ticks. The checked scalar
    # is a full interval old, so int()-ing it never stalls the pipeline.
    DROP_CHECK_INTERVAL = 64

    def __init__(self, params: BoidsParams, interpret: bool):
        self.params = params
        self._step_fn = _jitted_step(params, interpret)
        # Device scalar: active agents whose cell overflowed LANES this tick
        # (they get zero steering — densest clusters are exactly where this
        # bites, so surface it instead of silently zeroing).
        self.last_dropped = None
        self._tick = 0
        self._stale_dropped = None

    def step(self, pos, vel, active):
        """One tick; accepts/returns numpy or jax arrays [N,2],[N,2],[N]."""
        pos2, vel2, accel, dropped = self._step_fn(
            jnp.asarray(pos, jnp.float32),
            jnp.asarray(vel, jnp.float32),
            jnp.asarray(active, jnp.bool_),
        )
        self.last_dropped = dropped  # device scalar; int() it to inspect
        self._tick += 1
        if self._tick % self.DROP_CHECK_INTERVAL == 0:
            if self._stale_dropped is not None:
                n_dropped = int(self._stale_dropped)
                if n_dropped:
                    from goworld_tpu.utils import gwlog

                    gwlog.warnf(
                        "boids cell overflow: %d active agents exceeded "
                        "LANES=%d occupants in their grid cell (zero steering, "
                        "invisible to neighbors); enlarge grid or cell_size",
                        n_dropped,
                        LANES,
                    )
            self._stale_dropped = dropped
        return pos2, vel2, accel


def reference_accel(p: BoidsParams, pos, vel, active):
    """O(N^2) numpy oracle with identical force semantics (for tests)."""
    pos = np.asarray(pos, np.float64)
    vel = np.asarray(vel, np.float64)
    n = len(pos)
    accel = np.zeros((n, 2))
    wx, wz = p.world_x, p.world_z
    for i in range(n):
        if not active[i]:
            continue
        d = pos - pos[i]
        d[:, 0] -= wx * np.round(d[:, 0] / wx)
        d[:, 1] -= wz * np.round(d[:, 1] / wz)
        d2 = np.sum(d * d, axis=1)
        mask = active & (d2 <= p.r_eff**2)
        mask[i] = False
        if not mask.any():
            continue
        close = mask & (d2 < (p.r_eff * p.sep_frac) ** 2)
        inv = np.where(close, 1.0 / (d2 + 1e-6), 0.0)
        sep = -np.sum(d * inv[:, None], axis=0)
        align = vel[mask].mean(axis=0) - vel[i]
        coh = d[mask].mean(axis=0)
        a = p.w_sep * sep + p.w_align * align + p.w_coh * coh
        accel[i] = a * min(1.0, p.max_accel / np.sqrt(np.sum(a * a) + 1e-12))
    return accel
