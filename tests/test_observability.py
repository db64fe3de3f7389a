"""Cluster observability plane + device-runtime sentinel (ISSUE 13).

Four layers:

- Sentinel semantics (telemetry/sentinel.py): launch/trace accounting on
  the wrapped engine jits, the seeded-retrace mutation test (perturb a
  step-jit arg signature mid-run → exactly ONE structured WARN with the
  correct delta + ``jit_retrace_events_total``), and the converse pin —
  ZERO retrace events across a steady-state fused engine run.
- Collector semantics (telemetry/collector.py): the aggregated view over
  fake and real (HTTP) targets — census conservation, stale-generation
  detection, down-process rows, staleness.
- The production wire: DebugHTTPServer ``/snapshot`` + ``/cluster``
  round-trips, gwtop's render + ``--once`` machine-readable snapshot.
- Concurrent-scrape safety: /metrics + /cluster renders hammered from
  threads while a hot loop records into the same histogram family —
  rendering must neither block nor corrupt the recording path.
"""

from __future__ import annotations

import asyncio
import json
import logging
import threading
import time

import numpy as np
import pytest

from goworld_tpu import telemetry
from goworld_tpu.telemetry import sentinel
from goworld_tpu.telemetry.collector import (
    ClusterCollector,
    build_local_snapshot,
    http_fetch_json,
    http_target,
    http_targets_from_config,
    summarize,
)

RETRACE_MSG = "steady-state retrace"


@pytest.fixture(autouse=True)
def _restore_sentinel_config():
    yield
    sentinel.configure(warm_launches=32)


class _WarnCapture(logging.Handler):
    """Handler on the gwlog logger (it sets propagate=False, so pytest's
    caplog never sees its records)."""

    def __init__(self) -> None:
        super().__init__(level=logging.WARNING)
        self.records: list[logging.LogRecord] = []

    def emit(self, record: logging.LogRecord) -> None:
        self.records.append(record)


@pytest.fixture()
def gwlog_warns():
    from goworld_tpu.utils import gwlog

    gwlog._ensure()  # lazy setup() would clear our handler otherwise
    handler = _WarnCapture()
    logger = logging.getLogger("goworld_tpu")
    logger.addHandler(handler)
    yield handler
    logger.removeHandler(handler)


def _retrace_warns(handler: _WarnCapture) -> list[dict]:
    out = []
    for rec in handler.records:
        msg = rec.getMessage()
        if RETRACE_MSG in msg:
            out.append(json.loads(msg.split(": ", 1)[1]))
    return out


# --- sentinel: launch/trace accounting ---------------------------------------


def test_sentinel_counts_launches_traces_and_cache():
    import jax
    import jax.numpy as jnp

    j = sentinel.SentinelJit("t_obs_basic", jax.jit(lambda x: x + 1))
    l0 = sentinel.launches_total("t_obs_basic")
    t0 = sentinel.traces_total("t_obs_basic")
    for _ in range(4):
        j(jnp.zeros(4))
    assert sentinel.launches_total("t_obs_basic") - l0 == 4
    assert sentinel.traces_total("t_obs_basic") - t0 == 1
    assert j._cache_size() == 1
    # A second shape within the warm window: a trace, NOT a retrace.
    j(jnp.zeros(8))
    assert sentinel.traces_total("t_obs_basic") - t0 == 2
    assert sentinel.retrace_events_total("t_obs_basic") == 0


def test_seeded_retrace_fires_exactly_one_warn(gwlog_warns):
    """The seeded-retrace mutation test (toy jit): past the warm
    threshold, a shape-perturbed call fires exactly ONE structured WARN
    naming the delta and bumps jit_retrace_events_total; a repeat of the
    cached signature neither re-traces nor re-warns; a THIRD distinct
    signature warns again."""
    import jax
    import jax.numpy as jnp

    sentinel.configure(warm_launches=5)
    j = sentinel.SentinelJit("t_obs_seeded", jax.jit(lambda x: x * 2))
    for _ in range(6):
        j(jnp.zeros(4, jnp.float32))
    assert sentinel.retrace_events_total("t_obs_seeded") == 0
    assert not _retrace_warns(gwlog_warns)
    j(jnp.zeros(8, jnp.float32))  # the seeded perturbation
    warns = _retrace_warns(gwlog_warns)
    assert sentinel.retrace_events_total("t_obs_seeded") == 1
    assert len(warns) == 1
    w = warns[0]
    assert w["fn"] == "t_obs_seeded"
    assert w["delta"] == [{
        "arg": 0,
        "was": "jaxlib:float32[4]",
        "now": "jaxlib:float32[8]",
    }]
    assert "flight" in w
    # Both signatures now cached: ping-ponging between them is
    # launch traffic, not traces — no new WARN, no new retrace.
    j(jnp.zeros(4, jnp.float32))
    j(jnp.zeros(8, jnp.float32))
    assert sentinel.retrace_events_total("t_obs_seeded") == 1
    assert len(_retrace_warns(gwlog_warns)) == 1
    # A third distinct signature is a NEW incident.
    j(jnp.zeros(16, jnp.float32))
    assert sentinel.retrace_events_total("t_obs_seeded") == 2
    assert len(_retrace_warns(gwlog_warns)) == 2


def test_engine_step_jit_seeded_retrace(gwlog_warns):
    """The REAL step jit: warm the jnp engine past the threshold, then
    hand the jit numpy arrays (the production regression this catches —
    host code bypassing the device-array upload adds a per-call transfer
    AND a separate trace-cache entry). Exactly one WARN, correct kind
    delta, counter incremented."""
    from goworld_tpu.ops.neighbor import NeighborEngine, NeighborParams

    # Distinctive params: the lru-cached jit instance (and its launch
    # count) must belong to this test alone.
    params = NeighborParams(
        capacity=64, cell_size=37.0, grid_x=16, grid_z=16,
        space_slots=1, cell_capacity=16, max_events=512)
    sentinel.configure(warm_launches=4)
    eng = NeighborEngine(params, backend="jnp")
    eng.reset()
    rng = np.random.default_rng(3)
    pos = rng.uniform(0, 16 * 37.0, (64, 2)).astype(np.float32)
    act = np.ones(64, bool)
    spc = np.zeros(64, np.int32)
    rad = np.full(64, 37.0, np.float32)
    r0 = sentinel.retrace_events_total("aoi_step_jnp")
    for _ in range(6):
        eng.step(pos, act, spc, rad)
    assert sentinel.retrace_events_total("aoi_step_jnp") == r0
    # Seed the perturbation: numpy args straight into the warm jit.
    eng._jit_step(pos, act, spc, rad, pos, act, spc, rad)
    assert sentinel.retrace_events_total("aoi_step_jnp") == r0 + 1
    warns = [w for w in _retrace_warns(gwlog_warns)
             if w["fn"] == "aoi_step_jnp"]
    assert len(warns) == 1
    assert all(d["was"].startswith("jaxlib:")
               and d["now"].startswith("numpy:")
               for d in warns[0]["delta"])


def test_zero_retraces_across_steady_fused_run():
    """The converse pin: a steady-state FUSED engine run (constant
    program set, constant shapes, varying dt and positions) must count
    launches and exactly one trace — zero retrace events — well past the
    warm threshold."""
    from goworld_tpu.entity.columns import FusedProgram
    from goworld_tpu.ops.neighbor import NeighborEngine, NeighborParams

    def prog(x, y, z, yaw, dt, vx):
        return x + vx * dt, y, z, yaw + dt, vx

    pa = FusedProgram(prog, ("vx",))
    params = NeighborParams(
        capacity=64, cell_size=41.0, grid_x=16, grid_z=16,
        space_slots=1, cell_capacity=16, max_events=512)
    sentinel.configure(warm_launches=5)
    eng = NeighborEngine(params, backend="jnp")
    eng.reset()
    rng = np.random.default_rng(7)
    pos = rng.uniform(0, 16 * 41.0, (64, 2)).astype(np.float32)
    act = np.ones(64, bool)
    spc = np.zeros(64, np.int32)
    rad = np.full(64, 41.0, np.float32)
    y = np.zeros(64, np.float32)
    yaw = np.zeros(64, np.float32)
    vx = rng.normal(0, 2, 64).astype(np.float32)
    sel = np.ones(64, np.int32)
    l0 = sentinel.launches_total("aoi_step_fused_jnp")
    t0 = sentinel.traces_total("aoi_step_fused_jnp")
    r0 = sentinel.steady_state_retraces()
    for t in range(20):
        pend = eng.step_async(pos, act, spc, rad,
                              logic=((pa,), sel, y, yaw, 0.05 + 0.01 * t,
                                     (vx,)))
        pend.collect()
        outs = pend.fused[3]
        pos = np.asarray(outs[0]).copy()
    assert sentinel.launches_total("aoi_step_fused_jnp") - l0 == 20
    assert sentinel.traces_total("aoi_step_fused_jnp") - t0 == 1
    assert sentinel.steady_state_retraces() == r0
    assert eng.fused_trace_count((pa,)) == 1


def test_sentinel_configure_from_config():
    from goworld_tpu.config.read_config import TelemetryConfig
    from goworld_tpu.telemetry import tracing

    tracing.configure_from_config(TelemetryConfig(retrace_warm_ticks=7))
    assert sentinel.warm_launches() == 7


# --- collector: aggregation semantics ----------------------------------------


def _row(ok: bool, health: dict, metrics: dict | None = None) -> dict:
    return {"ok": ok, "age_s": 0.1, "error": None,
            "health": health, "metrics": metrics or {}}


def _healthy_rows() -> dict:
    return {
        "dispatcher1": _row(True, {
            "kind": "dispatcher", "id": 1, "entities_routed": 3,
            "gates": {"1": {"connected": True, "gen": 111}},
        }, {"dispatcher_migrates_total": {"type": "counter", "series": [
            {"labels": {"dispid": "1", "kind": "routed"}, "value": 4},
            {"labels": {"dispid": "1", "kind": "bounced"}, "value": 1},
        ]}}),
        "game1": _row(True, {
            "kind": "game", "id": 1, "entities": 4, "clients": 2,
            "client_gate_gens": {"1": [111]},
        }),
        "gate1": _row(True, {
            "kind": "gate", "id": 1, "generation": 111, "clients": 2,
        }),
    }


def test_summarize_census_generations_and_counters():
    s = summarize(_healthy_rows())
    assert s["reporting"] == 3 and s["expected"] == 3 and not s["down"]
    assert s["census"] == {
        "game_entities": 4, "game_clients": 2, "gate_clients": 2,
        "clients_conserved": True}
    assert s["generations"]["gates"] == {"1": 111}
    assert s["generations"]["stale"] == []
    assert s["migrations"] == {"routed": 4, "bounced": 1, "cancel": 0}
    assert s["alerts"] == []


def test_summarize_flags_stale_generation_and_census_mismatch():
    rows = _healthy_rows()
    # A dead gate incarnation's binding still on the game...
    rows["game1"]["health"]["client_gate_gens"]["1"] = [222]
    # ...and one client short on the gate.
    rows["gate1"]["health"]["clients"] = 1
    s = summarize(rows)
    assert s["census"]["clients_conserved"] is False
    assert s["generations"]["stale"] == [{
        "where": "game1", "gate": "1", "bound_gen": 222, "gate_gen": 111}]
    assert any("census mismatch" in a for a in s["alerts"])
    assert any("stale generation" in a for a in s["alerts"])
    # gen 0 = legacy/unknown binding: explicitly NOT stale.
    rows["game1"]["health"]["client_gate_gens"]["1"] = [0]
    assert summarize(rows)["generations"]["stale"] == []


def test_summarize_counts_retraces_as_alert():
    rows = _healthy_rows()
    rows["game1"]["metrics"]["jit_retrace_events_total"] = {
        "type": "counter",
        "series": [{"labels": {"fn": "aoi_step_jnp"}, "value": 2}]}
    s = summarize(rows)
    assert s["steady_state_retraces"] == 2
    assert any("retrace" in a for a in s["alerts"])


def test_summarize_rebalance_plane_and_alerts():
    """ISSUE 18: the /cluster rebalance section aggregates the planner
    host (sharded-service healthz row), pause reasons, in-flight spaces,
    parked streams and space-migration outcomes — and a paused planner /
    a host-less enabled planner service each raise an alert."""
    rows = _healthy_rows()
    rows["dispatcher1"]["health"]["rebalance"] = {
        "enabled": True, "driver": True, "planner_service": True,
        "last_result": None, "reporting_games": [], "space_handoffs": 2}
    rows["game1"]["health"]["rebalance_planner"] = {
        "last_result": "paused_stale", "reporting_games": [1]}
    rows["game1"]["metrics"].update({
        "rebalance_plans_total": {"type": "counter", "series": [
            {"labels": {"result": "paused_stale"}, "value": 3}]},
        "rebalance_spaces_in_flight": {"type": "gauge", "series": [
            {"labels": {}, "value": 1}]},
        "rebalance_space_migrations_total": {"type": "counter", "series": [
            {"labels": {"outcome": "done"}, "value": 5},
            {"labels": {"outcome": "rolled_back"}, "value": 1}]},
    })
    s = summarize(rows)
    rb = s["rebalance"]
    assert rb["enabled"] is True and rb["planner_service"] is True
    assert rb["planner_host"] == "game1"
    assert rb["last_result"] == "paused_stale"
    assert rb["rounds_paused"]["paused_stale"] == 3
    assert rb["spaces_in_flight"] == 1
    assert rb["space_handoffs_parked"] == 2
    assert rb["space_migrations"] == {
        "done": 5, "aborted": 0, "timeout": 0, "rolled_back": 1}
    assert any("rebalance paused: paused_stale" in a for a in s["alerts"])
    # Planner service enabled but NO live host anywhere reporting: the
    # failover-in-flight alert (what a wedged kvreg re-claim looks like).
    del rows["game1"]["health"]["rebalance_planner"]
    s2 = summarize(rows)
    assert s2["rebalance"]["planner_host"] is None
    assert any("no live host" in a for a in s2["alerts"])
    # A healthy moving planner raises neither alert.
    rows["game1"]["health"]["rebalance_planner"] = {
        "last_result": "moved", "reporting_games": [1]}
    s3 = summarize(rows)
    assert not any("rebalance" in a for a in s3["alerts"])


def test_collector_poll_view_and_down_target():
    async def run():
        healthy = {"health": {"kind": "game", "id": 1, "entities": 2,
                              "clients": 1}, "metrics": {}}
        state = {"fail": False}

        async def good():
            return healthy

        async def flaky():
            if state["fail"]:
                raise RuntimeError("killed")
            return {"health": {"kind": "gate", "id": 1, "generation": 9,
                               "clients": 1}, "metrics": {}}

        coll = ClusterCollector(
            [("game1", good), ("gate1", flaky)], interval=0.05)
        await coll.poll_once()
        v = coll.view()
        assert v["collector"]["targets"] == 2
        assert v["summary"]["reporting"] == 2
        assert v["summary"]["census"]["clients_conserved"] is True
        # Target dies: its row goes red but keeps the last snapshot.
        state["fail"] = True
        await coll.poll_once()
        v = coll.view()
        row = v["processes"]["gate1"]
        assert row["ok"] is False
        assert "killed" in row["error"]
        assert row["health"]["generation"] == 9  # last good snapshot kept
        assert v["summary"]["down"] == ["gate1"]
        assert any("not reporting" in a for a in v["summary"]["alerts"])

    asyncio.run(run())


def test_collector_staleness_marks_row_not_ok():
    async def run():
        async def good():
            return {"health": {"kind": "game", "id": 1}, "metrics": {}}

        coll = ClusterCollector([("game1", good)], interval=0.05,
                                stale_after=0.05)
        await coll.poll_once()
        assert coll.view()["processes"]["game1"]["ok"] is True
        await asyncio.sleep(0.12)
        assert coll.view()["processes"]["game1"]["ok"] is False

    asyncio.run(run())


def test_http_targets_from_config_enumeration():
    from goworld_tpu.config.read_config import (
        DispatcherConfig,
        GameConfig,
        GateConfig,
        GoWorldConfig,
    )

    cfg = GoWorldConfig()
    cfg.dispatchers = {1: DispatcherConfig(http_addr="127.0.0.1:1"),
                       2: DispatcherConfig()}
    cfg.games = {1: GameConfig(http_addr="127.0.0.1:2")}
    cfg.gates = {1: GateConfig(http_addr="127.0.0.1:3")}
    names = [n for n, _ in http_targets_from_config(cfg)]
    assert names == ["dispatcher1", "game1", "gate1"]


# --- the production wire: /snapshot + /cluster + gwtop ------------------------


def test_snapshot_cluster_roundtrip_and_gwtop():
    from goworld_tpu.tools import gwtop
    from goworld_tpu.utils import debug_http
    from goworld_tpu.utils.debug_http import DebugHTTPServer

    def provider() -> dict:
        return {"kind": "game", "id": 1, "entities": 3, "clients": 2,
                "queue_depth": 0, "client_gate_gens": {"1": [5]}}

    async def run():
        srv = DebugHTTPServer("127.0.0.1", 0)
        await srv.start()
        addr = f"127.0.0.1:{srv.port}"
        debug_http.set_health_provider(provider)
        try:
            snap = await http_fetch_json(addr, "/snapshot")
            assert snap["health"]["kind"] == "game"
            assert snap["health"]["proto_version"] >= 5
            assert isinstance(snap["metrics"], dict)
            # /cluster 404s where no collector is hosted...
            with pytest.raises(ValueError, match="404"):
                await http_fetch_json(addr, "/cluster")
            # ...and serves the aggregate where one is.
            coll = ClusterCollector([http_target("game1", addr)],
                                    interval=0.05)
            await coll.poll_once()
            debug_http.set_cluster_provider(coll.view)
            try:
                view = await http_fetch_json(addr, "/cluster")
                assert view["processes"]["game1"]["ok"] is True
                assert view["summary"]["census"]["game_entities"] == 3
                # gwtop --once: the machine-readable snapshot on stdout.
                import contextlib
                import io

                buf = io.StringIO()
                loop = asyncio.get_running_loop()

                def once() -> int:
                    with contextlib.redirect_stdout(buf):
                        return gwtop.main(["--addr", addr, "--once"])

                rc = await loop.run_in_executor(None, once)
                assert rc == 0
                parsed = json.loads(buf.getvalue())
                assert parsed["processes"]["game1"]["health"]["clients"] == 2
                # The live page renders every process row + summary line.
                page = gwtop.render(parsed)
                assert "game1" in page and "alerts:" in page
                assert "1/1 reporting" in page
            finally:
                debug_http.clear_cluster_provider(coll.view)
        finally:
            debug_http.clear_health_provider(provider)
            await srv.stop()

    asyncio.run(run())


def test_gwtop_render_flags_trouble():
    view = {
        "collector": {"targets": 2, "polls": 9, "interval_s": 1.0,
                      "stale_after_s": 3.0, "ts": 0},
        "processes": {
            "game1": {"ok": True, "age_s": 0.2, "error": None,
                      "health": {"kind": "game", "uptime_s": 5.0,
                                 "entities": 3, "clients": 2,
                                 "queue_depth": 1},
                      "metrics": {
                          "game_tick_phase_seconds": {
                              "type": "histogram",
                              "series": [{"labels": {"phase": "total"},
                                          "count": 10, "sum": 0.1,
                                          "avg": 0.01, "max": 0.02,
                                          "p50": 0.01, "p95": 0.02,
                                          "p99": 0.02}]},
                          "jit_launches_total": {
                              "type": "counter",
                              "series": [{"labels": {"fn": "aoi_step_jnp"},
                                          "value": 40}]},
                          "jit_retrace_events_total": {
                              "type": "counter",
                              "series": [{"labels": {"fn": "aoi_step_jnp"},
                                          "value": 1}]},
                      }},
            "gate1": {"ok": False, "age_s": 9.0, "error": "boom",
                      "health": {"kind": "gate", "clients": 2,
                                 "generation": 7, "queue_depth": 0},
                      "metrics": {}},
        },
        "summary": {"reporting": 1, "expected": 2, "down": ["gate1"],
                    "census": {"game_entities": 3, "game_clients": 2,
                               "gate_clients": 2,
                               "clients_conserved": True},
                    "generations": {"gates": {"1": 7}, "stale": []},
                    "migrations": {"routed": 0, "bounced": 0, "cancel": 0},
                    "steady_state_retraces": 1,
                    "fused": {"classes": 0, "slots": 0},
                    "alerts": ["processes not reporting: gate1"]},
    }
    page = gwtop_render(view)
    assert "DOWN" in page
    assert "retraces 1" in page
    assert "processes not reporting: gate1" in page
    assert "10.0/20.0" in page  # tick p50/p95 ms of game1


def test_gwtop_rebal_column_and_summary_line():
    """ISSUE 18: the REBAL column marks the planner host (game service
    entity or non-service driver dispatcher), spaces mid-handoff and
    parked member streams; an enabled plane adds its segment to the
    summary line."""
    from goworld_tpu.tools import gwtop

    game_h = {"kind": "game",
              "rebalance_planner": {"last_result": "moved",
                                    "reporting_games": [1, 2]}}
    game_m = {"rebalance_spaces_in_flight": {
        "type": "gauge", "series": [{"labels": {}, "value": 1}]}}
    assert gwtop._rebal_col(game_h, game_m) == "P:moved 1sp→"
    disp_h = {"kind": "dispatcher",
              "rebalance": {"enabled": True, "driver": True,
                            "planner_service": False,
                            "last_result": "balanced",
                            "space_handoffs": 2}}
    assert gwtop._rebal_col(disp_h, {}) == "P:balanced 2park"
    # Service mode: the dispatcher is just the conduit — no P: marker.
    disp_h["rebalance"]["planner_service"] = True
    assert gwtop._rebal_col(disp_h, {}) == "2park"
    assert gwtop._rebal_col({"kind": "gate"}, {}) == "-"

    view = {"collector": {}, "processes": {},
            "summary": {"rebalance": {
                "enabled": True, "planner_service": True,
                "planner_host": "game2", "last_result": "moved",
                "rounds_paused": {"paused_stale": 1},
                "spaces_in_flight": 2, "space_handoffs_parked": 0,
                "space_migrations": {"done": 5, "aborted": 0,
                                     "timeout": 0, "rolled_back": 1}}}}
    page = gwtop.render(view)
    assert "REBAL" in page
    assert "rebal host=game2" in page
    assert "paused=1" in page and "infl=2" in page
    assert "d5/a0/t0/r1" in page
    # A disabled plane keeps the summary line quiet.
    view["summary"]["rebalance"]["enabled"] = False
    assert "rebal host" not in gwtop.render(view)


def gwtop_render(view):
    from goworld_tpu.tools import gwtop

    return gwtop.render(view)


# --- concurrent-scrape safety -------------------------------------------------


def test_concurrent_scrape_never_corrupts_recording():
    """Satellite: hammer /metrics text + /snapshot (the /cluster row
    source) renders from threads while a hot loop records into the same
    histogram family — the renders must all complete, and the recording
    path must land EVERY observation (no corruption, no blocking)."""
    hist = telemetry.histogram(
        "t_obs_scrape_seconds", "concurrent scrape test", ("lane",))
    ctr = telemetry.counter("t_obs_scrape_total", "", ("lane",))
    n = 20000
    errors: list = []
    done = threading.Event()

    def hot():
        child_h = hist.labels("a")
        child_c = ctr.labels("a")
        for i in range(n):
            child_h.observe(0.001 * (i % 7))
            child_c.inc()
        done.set()

    def scraper():
        try:
            while not done.is_set():
                text = telemetry.render()
                assert "t_obs_scrape_seconds" in text
                snap = build_local_snapshot()
                assert isinstance(snap["metrics"], dict)
        except Exception as exc:  # pragma: no cover - the failure mode
            errors.append(exc)

    threads = [threading.Thread(target=scraper) for _ in range(4)]
    hot_t = threading.Thread(target=hot)
    t0 = time.monotonic()
    for t in threads + [hot_t]:
        t.start()
    for t in threads + [hot_t]:
        t.join(timeout=60)
    assert not errors, errors
    assert time.monotonic() - t0 < 60
    assert hist.labels("a").count == n
    assert ctr.labels("a").value == n


# --- chaos: recovery judged from the aggregated view --------------------------


@pytest.mark.chaos
def test_chaos_cluster_view_convergence(tmp_path):
    """A dispatcher kill+restart scenario, then the ISSUE 13 check the
    chaos suite now runs after EVERY scenario: the aggregated cluster
    view (collector over the live services) re-converges — all processes
    reporting, client census conserved at the bot count, zero alerts."""
    from goworld_tpu.chaos.harness import (
        ChaosCluster,
        scenario_dispatcher_restart,
    )

    async def run():
        cluster = ChaosCluster(
            str(tmp_path), n_dispatchers=2, n_bots=6,
            storage_knobs=dict(retry_base_interval=0.05,
                               retry_max_interval=0.2,
                               circuit_failure_threshold=3,
                               circuit_cooldown=0.3))
        await cluster.start()
        try:
            r = await scenario_dispatcher_restart(cluster)
            assert r["bot_errors"] == 0
            converge_s = await cluster.assert_cluster_view_converged()
            assert converge_s < 20.0
            # The view that converged really carries the cluster shape.
            coll = ClusterCollector(cluster.collector_targets(),
                                    interval=0.05)
            await coll.poll_once()
            s = coll.view()["summary"]
            assert s["census"]["gate_clients"] == 6
            assert s["generations"]["stale"] == []
        finally:
            await cluster.stop()

    asyncio.run(run())


# --- config + lint coverage ---------------------------------------------------


def test_telemetry_observability_keys_parse(tmp_path):
    from goworld_tpu.config import read_config

    ini = (
        "[deployment]\ndispatchers = 1\ngames = 1\ngates = 1\n"
        "[telemetry]\ncluster_snapshot_interval = 0.5\n"
        "retrace_warm_ticks = 7\n")
    p = tmp_path / "obs.ini"
    p.write_text(ini)
    read_config.set_config_file(str(p))
    try:
        t = read_config.get().telemetry
        assert t.cluster_snapshot_interval == 0.5
        assert t.retrace_warm_ticks == 7
    finally:
        read_config.set_config_file(None)
    bad = ini.replace("retrace_warm_ticks = 7", "retrace_warm_ticks = 0")
    p2 = tmp_path / "obs_bad.ini"
    p2.write_text(bad)
    read_config.set_config_file(str(p2))
    try:
        with pytest.raises(ValueError, match="retrace_warm_ticks"):
            read_config.get()
    finally:
        read_config.set_config_file(None)


def test_r6_covers_observability_keys():
    """ISSUE 13 satellite: the new [telemetry] keys are documented in
    goworld.ini.sample AND consumed by read_config — inside gwlint R6's
    coverage, so drift in either direction fails the gate."""
    import os

    from goworld_tpu.analysis.rules import _sample_keys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    fams, _lines = _sample_keys(root)
    assert {"cluster_snapshot_interval", "retrace_warm_ticks"} <= \
        fams["telemetry"]
