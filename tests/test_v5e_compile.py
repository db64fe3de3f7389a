"""Ahead-of-time compiles, for one v5e chip, of the Pallas kernels of the
main path at headline widths (on-chip-measurement guide, section 2).

The TPU compiler is installed here, so Mosaic refuses what the chip would
refuse — interpret-mode tests cannot see that. Nothing runs: these say
nothing about results or times. The topology is described in a module
fixture, never at import, so only the worker given this file loads the
TPU library. Each test compiles one kernel (about a second), asserts the
kernel is in the executable and prints its ``memory_analysis()``. Three
compile the whole single-chip step, at a tiny size and at the
open_world_100k and crowd_10k cells', and read the stage names the
profiler's trace will carry.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from goworld_tpu.ops import boids, neighbor
from goworld_tpu.ops.neighbor import LANES, NeighborParams, _F

# __graft_entry__.entry() / chip_smoke.py headline config.
HEADLINE = NeighborParams(capacity=102400, cell_size=300.0, grid_x=44,
                          grid_z=44, space_slots=4, cell_capacity=128,
                          max_events=262144)
# The 4-chip strip: SpatialShardedNeighborEngine's default strip_cols at
# grid 44 over 4 devices, and its inline event budget (max_events on
# each chip).
STRIP_COLS = 22
EVENTS_INLINE = HEADLINE.max_events
# The benchmark's seamless_250k cell: 312,512 slots over 4 strips of a
# 134-column grid, strip_cols derived (68), the game's 65,536 events a
# side inline on each chip.
SEAMLESS = NeighborParams(capacity=312512, cell_size=300.0, grid_x=134,
                          grid_z=134, space_slots=1, cell_capacity=64,
                          max_events=65536)
SEAMLESS_STRIP_COLS = 68
TINY = NeighborParams(capacity=1024, cell_size=100.0, grid_x=8, grid_z=8,
                      space_slots=1, cell_capacity=64, max_events=1024)
# The benchmark's crowd_10k cell: 12,504 slots, grid 6 of 150-unit cells,
# each cell 4 lane tiles deep (cell_capacity 512), the game's window.
CROWD = NeighborParams(capacity=12504, cell_size=150.0, grid_x=6, grid_z=6,
                       space_slots=1, cell_capacity=512, max_events=65536)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # A compile for a described chip cannot be read back from the
    # persistent cache without one: keep these out of it.
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", saved)
    compilation_cache.reset_cache()


def compile_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    print(compiled.memory_analysis())
    return compiled


def cells(sharding, rows, cols, planes, dtype, p=HEADLINE):
    return jax.ShapeDtypeStruct(
        (p.space_slots, rows + 2, cols, planes, p.cell_lanes), dtype,
        sharding=sharding)


@pytest.mark.parametrize("dual", [False, True], ids=["plain", "dual"])
def test_event_kernel_headline(one_chip, dual):
    kernel = neighbor._compiled_event_kernel(HEADLINE, False, dual=dual)
    gz, gx = HEADLINE.grid_z, HEADLINE.grid_x
    compile_kernel(kernel, cells(one_chip, gz, gx + 2, _F, jnp.float32))


@pytest.mark.parametrize("dual", [False, True], ids=["plain", "dual"])
def test_event_kernel_deep_cells(one_chip, dual):
    """Cells of 4 lane tiles: 512 query lanes against 36 candidate tiles
    a cell, in nine-tile chunks of the one-tile cell's shapes."""
    assert CROWD.lane_tiles == 4
    kernel = neighbor._compiled_event_kernel(CROWD, False, dual=dual)
    gz, gx = CROWD.grid_z, CROWD.grid_x
    compile_kernel(kernel, cells(one_chip, gz, gx + 2, _F, jnp.float32,
                                 CROWD))


@pytest.mark.parametrize("dual", [False, True], ids=["plain", "dual"])
@pytest.mark.parametrize("shape", [
    (HEADLINE, STRIP_COLS, EVENTS_INLINE),
    (SEAMLESS, SEAMLESS_STRIP_COLS, SEAMLESS.max_events),
], ids=["headline", "seamless_250k"])
def test_strip_kernel_inkernel_drain(one_chip, dual, shape):
    p, strip_cols, drain_inline = shape
    kernel = neighbor._compiled_event_kernel(
        p, False, rows=p.grid_z, cols=strip_cols + 2, dual=dual,
        drain_inline=drain_inline)
    gz, gxe = p.grid_z, strip_cols + 4
    compile_kernel(kernel, cells(one_chip, gz, gxe, _F, jnp.float32, p),
                   cells(one_chip, gz, gxe, 2, jnp.int32, p))


def test_boids_kernel(one_chip):
    p = boids.BoidsParams(capacity=51200, cell_size=200.0, grid_x=32,
                          grid_z=32, radius=100.0)
    kernel = boids._compiled_accel(p, False)
    compile_kernel(kernel, jax.ShapeDtypeStruct(
        (p.grid_z + 2, p.grid_x + 2, boids._F, boids.LANES), jnp.float32,
        sharding=one_chip))


def test_step_stage_names(one_chip):
    """The step compiles as ``jit_aoi_step``; its ops carry the stage
    scopes in their metadata and the kernel is ``aoi_event_kernel``."""
    n, buckets = TINY.capacity, TINY.num_buckets * LANES

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    epoch = (arg((n, 2), jnp.float32), arg((n,), jnp.bool_),
             arg((n,), jnp.int32), arg((n,), jnp.float32))
    grid = ((arg((n,), jnp.int32),) * 3 + (arg((buckets,), jnp.int32),)
            + (arg((n,), jnp.int32),) * 3)
    step = neighbor._jitted_step_packed(TINY, "pallas")._jitted
    text = step.lower(*epoch, *grid, *epoch).compile().as_text()
    assert text.startswith("HloModule jit_aoi_step,")
    scopes = set(re.findall(r'op_name="[^"]*?/(aoi\.\w+)', text))
    assert {"aoi.table", "aoi.feats", "aoi.guard", "aoi.gather",
            "aoi.drain", "aoi.pack"} <= scopes
    # 1,024 rows against 1,024 events: the step row-find, in its own scope.
    assert re.search(r'op_name="[^"]*/aoi\.drain/row_find/scatter', text)
    assert re.search(r"%aoi_event_kernel[.\d]* = .*"
                     r'custom_call_target="tpu_custom_call"', text)


def test_step_drain_levels(one_chip):
    """The single-chip step at the open_world_100k cell's shape (128,000
    slots, grid 86, 65,536 events a side) compiles for the chip, and each
    level of its drain after the row-find is a scope of its own."""
    p = NeighborParams(capacity=128000, cell_size=300.0, grid_x=86,
                       grid_z=86, space_slots=1, cell_capacity=64,
                       max_events=65536)
    n, buckets = p.capacity, p.num_buckets * LANES

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    epoch = (arg((n, 2), jnp.float32), arg((n,), jnp.bool_),
             arg((n,), jnp.int32), arg((n,), jnp.float32))
    grid = ((arg((n,), jnp.int32),) * 3 + (arg((buckets,), jnp.int32),)
            + (arg((n,), jnp.int32),) * 3)
    step = neighbor._jitted_step_packed(p, "pallas")._jitted
    compiled = step.lower(*epoch, *grid, *epoch).compile()
    print(compiled.memory_analysis())
    text = compiled.as_text()
    for level in ("row_find", "record", "word"):
        assert re.search(rf'op_name="[^"]*/aoi\.drain/{level}/', text), level


def test_step_crowd_deep_cells(one_chip):
    """The whole single-chip step at the crowd_10k cell's shape compiles
    for the chip with the 4-tile dual kernel in it; its memory analysis
    prints beside test_step_drain_levels' one-tile open_world_100k step."""
    p = CROWD
    n, buckets = p.capacity, p.num_buckets * p.cell_lanes

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    epoch = (arg((n, 2), jnp.float32), arg((n,), jnp.bool_),
             arg((n,), jnp.int32), arg((n,), jnp.float32))
    grid = ((arg((n,), jnp.int32),) * 3 + (arg((buckets,), jnp.int32),)
            + (arg((n,), jnp.int32),) * 3)
    step = neighbor._jitted_step_packed(p, "pallas")._jitted
    compiled = step.lower(*epoch, *grid, *epoch).compile()
    print(compiled.memory_analysis())
    assert re.search(r"%aoi_event_kernel[.\d]* = s32\[1,6,6,512,576\]",
                     compiled.as_text())
