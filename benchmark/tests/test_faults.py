"""Each fault the timed path can have, planted under a tiny CPU run,
must make ``correct`` false (the check's proof that it can fail)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from test_harness_cpu import run_tiny


def wrap_engine(monkeypatch, wrap):
    real = harness.build_engine

    def build(config, backend=None):
        eng = real(config, backend)
        wrap(eng)
        return eng

    monkeypatch.setattr(harness, "build_engine", build)


def state_unchanged(eng):
    """The step returns its state unchanged: every tick diffs against the
    storm's epoch."""
    step = eng.step_async
    calls = [0]

    def frozen(*a, **k):
        kept = eng._state
        pending = step(*a, **k)
        calls[0] += 1
        if calls[0] > 1:
            eng._state = kept
        return pending

    eng.step_async = frozen


def half_left_out(eng):
    """Half of the entity slots never reach the step."""
    step = eng.step_async

    def half(pos, active, space, radius, **k):
        active = active & (np.arange(len(active)) < len(active) // 2)
        return step(pos, active, space, radius, **k)

    eng.step_async = half


def pair_altered(eng):
    """One pair of each tick's answer is altered where it is produced."""
    step = eng.step_async

    class Altered:
        def __init__(self, pending):
            self.pending = pending

        def collect(self):
            enters, leaves, dropped = self.pending.collect()
            enters, leaves = enters.copy(), leaves.copy()
            out = enters if len(enters) else leaves
            if len(out):
                out[0, 1] = (out[0, 1] + 1) % eng.params.capacity
            return enters, leaves, dropped

    eng.step_async = lambda *a, **k: Altered(step(*a, **k))


@pytest.mark.parametrize("fault", [state_unchanged, half_left_out,
                                   pair_altered])
def test_single_chip_fault_fails_the_check(tiny, monkeypatch, fault):
    wrap_engine(monkeypatch, fault)
    r = run_tiny(tiny, "tiny_1chip.walk")
    assert r["correct"] is False and r["failed"] > 0


def test_halo_exchange_left_out_fails_the_check(tiny, monkeypatch):
    """The strip engine's exchange between chips sends nothing."""
    from goworld_tpu.parallel import spatial

    real = spatial._exchange_halo

    def no_exchange(p, n_dev, *args):
        *rest, send_lo, send_hi = args
        chunk = args[4].shape[0]
        none = jnp.full_like(send_lo, chunk)
        return real(p, n_dev, *rest, none, none)

    monkeypatch.setattr(spatial, "_exchange_halo", no_exchange)
    spatial._jitted_spatial_step_pallas.cache_clear()
    jax.clear_caches()
    try:
        r = run_tiny(tiny, "tiny_4chip.walk")
    finally:
        spatial._jitted_spatial_step_pallas.cache_clear()
        jax.clear_caches()
    assert r["correct"] is False and r["failed"] > 0
    assert r["checks"]["missing_pairs"]["value"] > 0
