"""Scenario matrix tests (ISSUE 16).

tests/conftest.py forces the 8-device CPU mesh before the first jax
import, so BOTH engines (batched and spatially sharded) run in-process
here.
"""

from __future__ import annotations

import numpy as np
import pytest

from goworld_tpu.scenarios import (
    ScenarioInvariantError,
    get_scenario,
    scenario_names,
)
from goworld_tpu.scenarios.runner import (
    InterestOracle,
    make_engine,
    run_scenario,
)

# In-process runs shrink the tick count where the scenario's own
# invariants allow it; hotspot needs enough ticks for the crowd to
# actually form (its density invariants assert on the ENDGAME state) and
# service_heavy needs the post-outage ticks for the breaker to be seen
# open, so both stop at 0.5.
_TICKS_SCALE = {"battle_royale": 0.25, "hotspot": 0.5, "service_heavy": 0.5}


# --- registry ----------------------------------------------------------------


def test_registry_contents():
    assert scenario_names() == ("battle_royale", "hotspot", "service_heavy")
    for name in scenario_names():
        spec = get_scenario(name)
        assert spec.description
        for key in ("n", "cell_size", "grid", "space_slots",
                    "cell_capacity", "max_events", "ticks", "repeats",
                    "seed", "shards"):
            assert key in spec.config, f"{name} config missing {key}"


def test_unknown_scenario_lists_available():
    with pytest.raises(KeyError, match="battle_royale"):
        get_scenario("free_for_all")


def test_spec_make_scales_ticks_and_defaults_seed():
    spec = get_scenario("battle_royale")
    w = spec.make()
    assert w.seed == spec.config["seed"]
    assert w.config["ticks"] == spec.config["ticks"]
    half = spec.make(seed=3, ticks_scale=0.5)
    assert half.seed == 3
    assert half.config["ticks"] == spec.config["ticks"] // 2
    # The floor never collapses below a runnable tick count.
    assert spec.make(ticks_scale=0.001).config["ticks"] == 8


def test_make_engine_rejects_unknown():
    with pytest.raises(ValueError, match="batched | sharded"):
        make_engine(dict(get_scenario("hotspot").config), "pallas")


# --- the interest-set oracle -------------------------------------------------


def test_oracle_rejects_bad_streams():
    ev = lambda *pairs: np.asarray(pairs, np.int64).reshape(-1, 2)
    o = InterestOracle(100)
    o.apply(0, ev((1, 2), (2, 1)), ev())
    with pytest.raises(ScenarioInvariantError, match="already interested"):
        o.apply(1, ev((1, 2)), ev())
    with pytest.raises(ScenarioInvariantError, match="never entered"):
        o.apply(1, ev(), ev((3, 4)))
    with pytest.raises(ScenarioInvariantError, match="duplicate enter"):
        o.apply(1, ev((5, 6), (5, 6)), ev())
    # A pair surviving a dead endpoint is the classic leave-drain bug.
    active = np.ones(100, bool)
    active[1] = False
    with pytest.raises(ScenarioInvariantError, match="stale interest"):
        o.check_alive(active)
    o.apply(2, ev(), ev((1, 2), (2, 1)))
    o.check_alive(active)


# --- determinism + per-scenario invariants (batched, in-process) -------------


@pytest.mark.parametrize("name", ["battle_royale", "hotspot", "service_heavy"])
def test_scenario_determinism_batched(name):
    """THE determinism gate: two back-to-back runs of one scenario at one
    seed produce bit-identical ``invariants`` dicts — the whole field set,
    not a sample.  Plus each scenario's shape-specific clauses."""
    scale = _TICKS_SCALE[name]
    a = run_scenario(name, engine="batched", ticks_scale=scale)
    b = run_scenario(name, engine="batched", ticks_scale=scale)
    assert a["errors"] == 0 and b["errors"] == 0
    assert a["steady_state_retraces"] == 0
    assert b["steady_state_retraces"] == 0
    # ISSUE 19 acceptance: one step-family launch per dispatched tick,
    # surfaced as headline fields (the runner hard-raises on violation).
    for r in (a, b):
        assert r["one_launch_per_tick"] is True
        assert r["step_launches"] == r["ticks_dispatched"] > 0
    assert a["invariants"] == b["invariants"], (
        f"{name}: invariants differ across identical-seed runs")
    inv = a["invariants"]
    assert inv["dropped"] == 0
    if name == "battle_royale":
        n = a["config"]["n"]
        assert inv["alive_final"] + inv["eliminated"] == n
        assert inv["storm_kills"] + inv["combat_kills"] == inv["eliminated"]
        traj = inv["alive_trajectory"]
        assert all(x >= y for x, y in zip(traj, traj[1:])), traj
        assert inv["eliminated"] > 0
    elif name == "hotspot":
        # Density invariants are asserted INSIDE invariants() (a weak
        # crowd raises); re-pin the headline fields here.
        assert inv["avg_aoi_neighbors"] >= 100.0
        assert inv["tier0_share"] >= 0.25
        assert inv["max_cell_density"] <= a["config"]["cell_capacity"]
    elif name == "service_heavy":
        assert inv["circuit_opened"] is True
        assert inv["lost_saves"] == 0
        assert sum(sum(v) for v in inv["receipts"].values()) \
            == inv["ops_total"]
        assert "service_op_p95_ms" in a  # wall-clock: beside, not inside


def test_different_seed_changes_trajectory():
    """The converse clause: the seed is LOAD-BEARING — a different seed
    must actually change the world (guards against a scenario silently
    ignoring its rng)."""
    a = run_scenario("battle_royale", engine="batched", seed=16,
                     ticks_scale=0.25)
    b = run_scenario("battle_royale", engine="batched", seed=17,
                     ticks_scale=0.25)
    assert a["invariants"] != b["invariants"]


# --- both engines ------------------------------------------------------------


@pytest.mark.parametrize("name", ["battle_royale", "hotspot", "service_heavy"])
def test_scenario_sharded_engine(name):
    """Every scenario runs on the spatially sharded engine (conftest's
    forced 8-device mesh) with the same oracle + invariants green; the
    hotspot scenario must additionally force the hotter-than-a-strip
    exact fallback (its check_engine raises if the crowd ever fit)."""
    r = run_scenario(name, engine="sharded",
                     ticks_scale=_TICKS_SCALE[name])
    assert r["errors"] == 0
    assert r["steady_state_retraces"] == 0
    assert r["invariants"]["dropped"] == 0
    assert r["engine"] == "sharded"
    assert r["one_launch_per_tick"] is True
    assert r["step_launches"] == r["ticks_dispatched"] > 0
    if name == "hotspot":
        assert r["fallback_ticks"] > 0, (
            "the hotspot crowd must overflow a strip's row budget")


def test_batched_and_sharded_agree_on_world_invariants():
    """Engine-agnostic contract: the WORLD-side invariant fields (census,
    kill counts — driven by the rng, not the engine) are identical across
    engines.  Event totals may differ only in that both engines must see
    the same interest set (the oracle enforces per-run correctness);
    battle_royale's event counts are trajectory-determined, so they match
    too."""
    a = run_scenario("battle_royale", engine="batched", ticks_scale=0.25)
    b = run_scenario("battle_royale", engine="sharded", ticks_scale=0.25)
    assert a["invariants"] == b["invariants"]


# --- the hotspot gate --------------------------------------------------------


def test_scenario_hotspot_floor_gate():
    """The scenario matrix's densest workload at its FIXED registry
    config on the batched engine: 1024 entities converging on one
    200-radius disc, through the verify pass (interest-set oracle,
    per-tick invariants) and the measure pass, with zero errors, zero
    dropped entities and zero steady-state retraces."""
    result = run_scenario("hotspot", engine="batched")
    assert result["config"] == dict(get_scenario("hotspot").config)
    assert result["scenario"] == "hotspot"
    assert result["errors"] == 0
    assert result["steady_state_retraces"] == 0
    assert result["invariants"]["dropped"] == 0
