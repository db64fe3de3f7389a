"""Fused boids kernel correctness: Pallas (interpret mode on CPU) vs the
O(N^2) numpy oracle, plus integration behavior (flocking converges)."""

import numpy as np
import pytest

from goworld_tpu.ops.boids import BoidsEngine, BoidsParams, reference_accel


def make_params(**kw):
    defaults = dict(
        capacity=512, cell_size=100.0, grid_x=8, grid_z=8,
        max_speed=8.0, max_accel=2.0,
    )
    defaults.update(kw)
    return BoidsParams(**defaults)


def make_world(p, n_active, seed=0, speed=3.0):
    rng = np.random.default_rng(seed)
    pos = rng.uniform(0, [p.world_x, p.world_z], (p.capacity, 2)).astype(np.float32)
    vel = rng.normal(0, speed, (p.capacity, 2)).astype(np.float32)
    active = np.zeros(p.capacity, bool)
    active[:n_active] = True
    return pos, vel, active


def test_accel_matches_oracle():
    p = make_params()
    pos, vel, active = make_world(p, 300, seed=1)
    eng = BoidsEngine(p, interpret=True)
    _, _, accel = eng.step(pos, vel, active)
    want = reference_accel(p, pos, vel, active)
    got = np.asarray(accel, np.float64)
    np.testing.assert_allclose(got[active], want[active], rtol=2e-3, atol=2e-3)
    assert np.all(got[~active] == 0.0)


def test_accel_matches_oracle_dense_wrap():
    """Dense cluster straddling the torus seam: halo + minimal-image math."""
    p = make_params()
    rng = np.random.default_rng(2)
    pos = np.mod(rng.normal(0, 60.0, (p.capacity, 2)), p.world_x).astype(np.float32)
    vel = rng.normal(0, 3.0, (p.capacity, 2)).astype(np.float32)
    active = np.ones(p.capacity, bool)
    active[400:] = False
    eng = BoidsEngine(p, interpret=True)
    _, _, accel = eng.step(pos, vel, active)
    want = reference_accel(p, pos, vel, active)
    np.testing.assert_allclose(
        np.asarray(accel, np.float64)[active], want[active], rtol=2e-3, atol=2e-3
    )


def test_accel_matches_oracle_supercells():
    """radius decoupled from cell_size (the bench's supercell sweep):
    cell 250 at radius 100 must give the same forces as the oracle — the
    3x3 halo over-covers and the r2 predicate prunes."""
    p = make_params(cell_size=250.0, grid_x=4, grid_z=4, radius=100.0)
    pos, vel, active = make_world(p, 400, seed=5)
    eng = BoidsEngine(p, interpret=True)
    _, _, accel = eng.step(pos, vel, active)
    want = reference_accel(p, pos, vel, active)
    np.testing.assert_allclose(
        np.asarray(accel, np.float64)[active], want[active],
        rtol=2e-3, atol=2e-3,
    )
    with pytest.raises(ValueError, match="radius"):
        make_params(radius=150.0)  # > cell_size 100


def test_isolated_agent_no_force():
    p = make_params()
    pos = np.zeros((p.capacity, 2), np.float32)
    pos[0] = (50.0, 50.0)
    pos[1] = (450.0, 450.0)  # > cell_size away from agent 0
    vel = np.zeros((p.capacity, 2), np.float32)
    active = np.zeros(p.capacity, bool)
    active[:2] = True
    eng = BoidsEngine(p, interpret=True)
    _, _, accel = eng.step(pos, vel, active)
    np.testing.assert_allclose(np.asarray(accel)[:2], 0.0, atol=1e-6)


def test_speed_clamped_and_world_wrapped():
    p = make_params(max_speed=5.0)
    pos, vel, active = make_world(p, 400, seed=3, speed=20.0)
    eng = BoidsEngine(p, interpret=True)
    pos2, vel2, _ = eng.step(pos, vel, active)
    pos2, vel2 = np.asarray(pos2), np.asarray(vel2)
    speeds = np.linalg.norm(vel2, axis=1)
    assert speeds.max() <= p.max_speed * 1.001
    assert (pos2 >= 0).all() and (pos2[:, 0] <= p.world_x).all() \
        and (pos2[:, 1] <= p.world_z).all()


def test_alignment_converges_headings():
    """Flocking sanity: alignment shrinks velocity variance over time."""
    p = make_params(w_sep=0.1, w_coh=0.2, w_align=1.5, max_speed=6.0)
    rng = np.random.default_rng(4)
    # One loose cluster so everyone interacts transitively.
    pos = np.mod(rng.normal(300.0, 80.0, (p.capacity, 2)), p.world_x).astype(np.float32)
    vel = rng.normal(0, 4.0, (p.capacity, 2)).astype(np.float32)
    active = np.ones(p.capacity, bool)
    eng = BoidsEngine(p, interpret=True)
    var0 = np.var(np.asarray(vel)[active], axis=0).sum()
    for _ in range(25):
        pos, vel, _ = eng.step(pos, vel, active)
    var1 = np.var(np.asarray(vel)[active], axis=0).sum()
    assert var1 < var0 * 0.5, (var0, var1)
