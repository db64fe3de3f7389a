"""The AOI engine's host spans (telemetry.phases.engine_span): each phase
of dispatch and collect lands on ``aoi_host_phase_seconds_total{phase}``
and, under a profiler session, as an ``aoi.<phase>`` span on the host
timeline with the same seconds."""

import glob
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from goworld_tpu.ops import NeighborEngine, NeighborParams
from goworld_tpu.parallel import ShardedNeighborEngine, make_mesh
from goworld_tpu.telemetry.phases import AOI_HOST_PHASE

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# 40 entities packed in one space: the enter storm alone is ~1,500 pairs,
# far over the 64 inline events, so the first collect pages.
PARAMS = NeighborParams(capacity=64, cell_size=100.0, grid_x=8, grid_z=8,
                        space_slots=1, cell_capacity=64, max_events=64)
ENGINE_PHASES = ("upload", "launch", "wait", "readback", "page")


def phase_seconds() -> dict:
    return {labels[0]: child.value
            for labels, child in AOI_HOST_PHASE.children()}


def world(seed):
    rng = np.random.default_rng(seed)
    pos = rng.uniform(0, 200, (PARAMS.capacity, 2)).astype(np.float32)
    active = np.zeros(PARAMS.capacity, bool)
    active[:40] = True
    space = np.zeros(PARAMS.capacity, np.int32)
    radius = np.full(PARAMS.capacity, 100.0, np.float32)
    return rng, pos, active, space, radius


def run_ticks(engine, ticks=4, seed=0):
    """Pipelined like the service: dispatch t+1, then collect t."""
    rng, pos, active, space, radius = world(seed)
    engine.reset()
    pending = engine.step_async(pos, active, space, radius)
    for _ in range(ticks):
        pos = pos + rng.uniform(-5, 5, pos.shape).astype(np.float32)
        nxt = engine.step_async(pos, active, space, radius, meta_dirty=False)
        pending.collect()
        pending = nxt
    pending.collect()


@pytest.mark.parametrize("kind", ["single", "sharded"])
def test_engine_phases_advance(kind):
    if kind == "single":
        engine = NeighborEngine(PARAMS, backend="jnp")
    else:
        engine = ShardedNeighborEngine(PARAMS, make_mesh(8), backend="jnp")
    before = phase_seconds()
    run_ticks(engine)
    after = phase_seconds()
    for phase in ENGINE_PHASES:
        assert after.get(phase, 0.0) > before.get(phase, 0.0), phase


def test_wait_span_matches_counter_under_profiler(tmp_path):
    # 4,096 entities over 20 x 20 cells: a step of tens of ms on the CPU,
    # so each wait is long beside a preemption that falls between the
    # span's edges and its clock reads on a loaded host.
    params = NeighborParams(capacity=4096, cell_size=100.0, grid_x=20,
                            grid_z=20, space_slots=1, cell_capacity=64,
                            max_events=65536)
    engine = NeighborEngine(params, backend="jnp")
    rng = np.random.default_rng(1)
    pos = rng.uniform(0, 2000, (params.capacity, 2)).astype(np.float32)
    epoch = (np.ones(params.capacity, bool),
             np.zeros(params.capacity, np.int32),
             np.full(params.capacity, 100.0, np.float32))
    engine.reset()
    engine.step(pos, *epoch)  # the storm, compiled before the session
    before = phase_seconds()["wait"]
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(8):
            pos = pos + rng.uniform(-5, 5, pos.shape).astype(np.float32)
            engine.step(pos, *epoch)
    counted = phase_seconds()["wait"] - before
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                         "*.xplane.pb"))[0]
    data = jax.profiler.ProfileData.from_file(path)
    spans = [e.duration_ns / 1e9 for plane in data.planes
             for line in plane.lines for e in line.events
             if e.name == "aoi.wait"]
    assert len(spans) == 8
    assert sum(spans) == pytest.approx(counted, rel=0.05, abs=1e-3)


def test_phases_import_without_jax():
    code = ("import sys, goworld_tpu.telemetry.phases as p; "
            "assert p.AOI_HOST_PHASE is not None; "
            "assert 'jax' not in sys.modules, 'jax imported'")
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
