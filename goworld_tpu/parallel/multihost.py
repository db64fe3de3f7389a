"""Multi-HOST (multi-process) sharded AOI — the DCN tier of the scaling
story.

Single-host scaling shards entity rows over one process's devices
(parallel/mesh.py — the ICI tier). This module runs the SAME shard_map
step across multiple jax processes (multi-controller SPMD): each host
contributes its local devices to one global mesh, owns the entity rows
sharded onto them, uploads only its local slab, and reads back only the
events of entities it owns. The all-gather inside the step then rides ICI
within a host and DCN between hosts — exactly how a v5e multi-host pod
runs, and the data-plane analog of the reference's one-process-per-game
TCP fabric (SURVEY.md §5.8: NCCL/MPI's slot is XLA collectives).

Multi-controller rules this module encodes:

- Global arrays are built with ``jax.make_array_from_process_local_data``
  (a process cannot device_put onto non-addressable devices).
- EVERY process must dispatch every global computation. Storm paging
  loops are therefore driven by the REPLICATED per-shard counts that the
  step all-gathers into each output block (mesh.py) — all processes see
  every shard's deficit and dispatch the same number of drain calls,
  each keeping only its own shards' pairs.
- ``collect()`` reads only addressable shards: a host receives exactly
  the events of the entity rows it owns (its games'), which is the
  delivery each game process wants anyway.

Bootstrap: call :func:`init_multihost` (a thin jax.distributed wrapper)
before any jax use, then build the engine on every process with the same
params. Tested by spawning real OS processes over the Gloo CPU backend
(tests/test_multihost.py) — the localhost analog of a multi-host pod,
mirroring how the reference CI tests its multi-process cluster.

The jitted step/drain builders (``jax.shard_map``) are shared with
parallel/mesh.py, and so is its inline-budget rule: each shard keeps
``max_events / D`` events a side inline. The spatially sharded
engine (parallel/spatial.py) is single-controller only for now: its
host-side strip planner assumes one process owns the whole slot space.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from goworld_tpu.ops.neighbor import (
    NeighborParams,
    check_radius,
    check_space_ids,
)
from goworld_tpu.parallel.mesh import (
    SHARD_AXIS,
    _M_ALLGATHER_EQUIV,
    _M_ALLGATHER_TOTAL,
    _M_LINK_BYTES,
    _jitted_sharded_drain,
    _jitted_sharded_drain_bits,
    _jitted_sharded_step,
    _jitted_sharded_step_pallas,
    make_mesh,
    start_host_copy,
)


def init_multihost(
    coordinator_address: str, num_processes: int, process_id: int
) -> None:
    """Join the multi-controller runtime (call before ANY jax use).

    On CPU test rigs combine with ``--xla_force_host_platform_device_count``
    for several local devices per process; on TPU pods the plugin provides
    the topology and this reduces to jax.distributed.initialize.
    """
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


class MultiHostPendingStep:
    """In-flight multi-host tick: collect() reads only LOCAL shards."""

    __slots__ = ("_engine", "_enter_ctx", "_leave_ctx", "_out", "_collected")

    def __init__(self, engine, enter_ctx, leave_ctx, out) -> None:
        self._engine = engine
        self._enter_ctx = enter_ctx  # per-backend paging payload tuple
        self._leave_ctx = leave_ctx
        self._out = out
        self._collected = False
        start_host_copy(out)

    def is_ready(self) -> bool:
        try:
            return bool(self._out.is_ready())
        except AttributeError:
            return True

    def wait_device(self) -> None:
        """Block until the local shard's step finishes computing (parity
        with PendingStep.wait_device — the aoi.drain latency seam)."""
        jax.block_until_ready(self._out)

    def collect(self) -> tuple[np.ndarray, np.ndarray, int]:
        """(local_enters, local_leaves, dropped): pairs whose ENTITY side
        lives on this process (global ids)."""
        assert not self._collected, "already collected"
        self._collected = True
        eng = self._engine
        e = eng.events_inline
        nd = eng.n_devices
        block = 3 + nd + 2 * e
        # Local shards only — the only addressable data in multi-controller.
        shards = sorted(
            self._out.addressable_shards, key=lambda s: s.index[0].start
        )
        local = {
            s.index[0].start // block: np.asarray(s.data) for s in shards
        }
        counts_all = next(iter(local.values()))[3:3 + nd]  # replicated
        enters, leaves = [], []
        dropped = 0
        for d, o in local.items():
            n_e, n_l = int(o[0, 0]), int(o[0, 1])
            dropped = int(o[1, 0])
            enters.append(o[3 + nd:3 + nd + min(n_e, e)])
            leaves.append(o[3 + nd + e:3 + nd + e + min(n_l, e)])
        # Storm paging: loop counts derive from the REPLICATED counts, so
        # every process dispatches the same global drain sequence and then
        # keeps only its local shards' chunks.
        rank_paging = eng.backend != "jnp"
        for which, ctx, bucket in (
            ("enter", self._enter_ctx, enters),
            ("leave", self._leave_ctx, leaves),
        ):
            col = 0 if which == "enter" else 1
            deficit = np.maximum(
                0, counts_all[:, col].astype(np.int64) - e
            )
            # jnp-path paging resumes AFTER the last drained flat position
            # (per-shard data, read from the local header); the pallas path
            # pages by event RANK — a globally known cursor.
            local_starts = {
                d: (e if rank_paging else int(o[2, col]) + 1)
                for d, o in local.items()
            }
            rounds = int(np.ceil(deficit / e).max()) if deficit.any() else 0
            cursor = np.zeros(nd, np.int64)
            for _ in range(rounds):
                start_global = eng._make_starts(local_starts)
                pairs, aux = eng._jit_drain(*ctx, start_global)
                for s in sorted(
                    pairs.addressable_shards,
                    key=lambda s: s.index[0].start,
                ):
                    d = s.index[0].start // e
                    take = int(min(e, deficit[d] - cursor[d]))
                    if take > 0:
                        arr = np.asarray(s.data)
                        bucket.append(arr[:take])
                for s in aux.addressable_shards:
                    d = s.index[0].start  # aux is [D, E] (jnp) / [D, 1]
                    taken = int(min(e, max(0, deficit[d] - cursor[d])))
                    if taken > 0:
                        local_starts[d] = (
                            local_starts[d] + taken if rank_paging
                            else int(np.asarray(s.data)[0, taken - 1]) + 1
                        )
                cursor += np.minimum(e, np.maximum(0, deficit - cursor))
        eng.last_grid_dropped = dropped
        return (
            np.concatenate(enters) if enters else np.empty((0, 2), np.int32),
            np.concatenate(leaves) if leaves else np.empty((0, 2), np.int32),
            dropped,
        )


class MultiHostNeighborEngine:
    """Per-process handle on the cross-host engine.

    Every process constructs it with identical params over the same global
    mesh and steps it with its LOCAL entity rows — rows
    [process_lo, process_lo + local_capacity). ``backend``: "jnp" (CPU
    rigs), "pallas" (TPU pods — grid-row kernel slabs per device, as in
    ShardedNeighborEngine), or "pallas_interpret" (tests).
    """

    def __init__(self, params: NeighborParams, mesh: Mesh | None = None,
                 backend: str = "jnp"):
        if mesh is None:
            mesh = make_mesh()  # ALL global devices
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
        self.events_inline = params.max_events // n_dev
        # Transfer accounting (ISSUE 15 satellite): the DCN tier pays the
        # same structural all-gather as the single-host entity tier —
        # rode ICI within a host, DCN between hosts. Live on /metrics so
        # the pod-scale comms story is visible beside the spatial tier's
        # halo gauges. The strip+halo Pallas path stays single-controller
        # (parallel/spatial.py owns the whole slot space host-side); its
        # pallas kernels here still ride the shared slab-kernel builders.
        self.allgather_bytes_per_tick = (
            n_dev * (params.capacity - self.chunk) * 34
        )
        _M_ALLGATHER_EQUIV.set(self.allgather_bytes_per_tick)
        if backend == "jnp":
            self._jit_step = _jitted_sharded_step(
                params, mesh, self.events_inline
            )
            self._jit_drain = _jitted_sharded_drain(
                params, mesh, self.events_inline, self.chunk
            )
        else:
            self._jit_step = _jitted_sharded_step_pallas(
                params, mesh, self.events_inline,
                backend == "pallas_interpret",
            )
            self._jit_drain = _jitted_sharded_drain_bits(
                params, mesh, self.events_inline
            )
        self._sharding = NamedSharding(mesh, P(SHARD_AXIS))
        self._starts_sharding = NamedSharding(mesh, P(SHARD_AXIS))
        # This process's slice of the entity-row space.
        local_dev = set(jax.local_devices())
        mesh_list = list(mesh.devices.reshape(-1))
        owned = [i for i, d in enumerate(mesh_list) if d in local_dev]
        if owned != list(range(owned[0], owned[0] + len(owned))):
            raise ValueError(
                "local devices must be contiguous in the mesh; build the "
                "mesh from jax.devices() order"
            )
        self.local_lo = owned[0] * self.chunk
        self.local_capacity = len(owned) * self.chunk
        self._state: tuple | None = None
        self.last_grid_dropped = 0
        # Per-link split of THIS process's slice of the all-gather: local
        # devices pull each other's rows over ICI and every remote
        # shard's rows over DCN (ROADMAP item 5 — the two link tiers of
        # a pod, attributable per host after the fact).
        n_local = len(owned)
        host = f"host{jax.process_index()}"
        self._ici_bytes = n_local * (n_local - 1) * self.chunk * 34
        self._dcn_bytes = n_local * (n_dev - n_local) * self.chunk * 34
        self._m_link_ici = _M_LINK_BYTES.labels("ici-allgather", host)
        self._m_link_dcn = _M_LINK_BYTES.labels("dcn-allgather", host)

    # --- multi-controller array builders ------------------------------------

    def _put(self, local_np: np.ndarray) -> jax.Array:
        gshape = (self.params.capacity,) + local_np.shape[1:]
        return jax.make_array_from_process_local_data(
            self._sharding, np.ascontiguousarray(local_np), gshape
        )

    def _make_starts(self, local_starts: dict[int, int]) -> jax.Array:
        local = np.array(
            [
                local_starts.get(d, 0)
                for d in sorted(local_starts)
            ],
            np.int32,
        )
        return jax.make_array_from_process_local_data(
            self._starts_sharding, local, (self.n_devices,)
        )

    def reset(self) -> None:
        lc = self.local_capacity
        self._state = (
            self._put(np.zeros((lc, 2), np.float32)),
            self._put(np.zeros((lc,), bool)),
            self._put(np.zeros((lc,), np.int32)),
            self._put(np.zeros((lc,), np.float32)),
        )

    def step_async(
        self,
        pos: np.ndarray,
        active: np.ndarray,
        space: np.ndarray,
        radius: np.ndarray,
        meta_dirty: bool = True,
    ) -> MultiHostPendingStep:
        """Dispatch one tick with this process's LOCAL rows
        ([local_capacity, ...] arrays)."""
        assert self._state is not None, "call reset() first"
        assert len(pos) == self.local_capacity, (
            f"pass LOCAL rows ({self.local_capacity}), got {len(pos)}"
        )
        check_radius(self.params, radius, active)
        if self.backend != "jnp":
            check_space_ids(space, active)
        if meta_dirty:
            meta = (
                self._put(np.array(active, bool)),
                self._put(np.array(space, np.int32)),
                self._put(np.array(radius, np.float32)),
            )
        else:
            meta = self._state[1:4]
        cur = (self._put(np.array(pos, np.float32)),) + meta
        if self.backend == "jnp":
            # Entity-row sharding: a process's local events are exactly
            # its own entities' events.
            enter_ids, leave_ids, out = self._jit_step(*self._state, *cur)
            enter_ctx: tuple = (enter_ids,)
            leave_ctx: tuple = (leave_ids,)
        else:
            # Grid-row (SPATIAL) sharding: each device emits the events of
            # entities binned in ITS slab — every event exactly once, but
            # a process receives events by CELL ownership, not row
            # ownership (spatial partitioning; route or re-shard if row
            # ownership is required).
            res = self._jit_step(*self._state, *cur)
            enter_ctx, leave_ctx, out = res[0:5], res[5:10], res[10]
        self._state = cur
        _M_ALLGATHER_TOTAL.inc(self.allgather_bytes_per_tick)
        if self._ici_bytes:
            self._m_link_ici.inc(self._ici_bytes)
        if self._dcn_bytes:
            self._m_link_dcn.inc(self._dcn_bytes)
        return MultiHostPendingStep(self, enter_ctx, leave_ctx, out)

    def step(self, pos, active, space, radius):
        return self.step_async(pos, active, space, radius).collect()
