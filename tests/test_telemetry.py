"""Telemetry subsystem tests: registry semantics, Prometheus exposition,
opmon shim compatibility, /metrics round-trip, phase tracer, KCP session
caps, and the correctness of the pipelined AOI loop and of the sync
fan-out pipeline on live clusters (goworld_tpu/telemetry; ISSUE 1)."""

from __future__ import annotations

import asyncio
import json
import threading
import time

import pytest

from goworld_tpu import telemetry
from goworld_tpu.telemetry.metrics import Registry, exponential_buckets


# --- registry semantics ------------------------------------------------------


def test_counter_get_or_create_and_monotonic():
    reg = Registry()
    c = reg.counter("jobs_total", "help")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    assert reg.counter("jobs_total") is c  # same child back
    with pytest.raises(ValueError):
        c.inc(-1)  # counters only go up
    with pytest.raises(ValueError):
        reg.gauge("jobs_total")  # kind mismatch
    with pytest.raises(ValueError):
        reg.counter("jobs_total", labelnames=("x",))  # schema mismatch
    with pytest.raises(ValueError):
        reg.counter("bad name")


def test_label_families_distinct_children():
    reg = Registry()
    fam = reg.counter("rpc_total", "h", labelnames=("method", "ok"))
    a = fam.labels("foo", "true")
    b = fam.labels(method="foo", ok="false")
    assert a is not b
    assert fam.labels("foo", "true") is a  # cached
    a.inc(3)
    b.inc()
    assert a.value == 3 and b.value == 1
    with pytest.raises(ValueError):
        fam.labels("onlyone")  # arity mismatch
    with pytest.raises(ValueError):
        fam.labels(method="foo", nope="x")
    fam.remove("foo", "true")
    assert fam.labels("foo", "true") is not a  # fresh child after remove


def test_gauge_set_function_and_error_isolation():
    reg = Registry()
    g = reg.gauge("depth")
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.value == 3
    g.set_function(lambda: 42)
    assert g.value == 42
    g.set_function(lambda: 1 / 0)  # broken probe must not kill collection
    assert g.value != g.value  # NaN
    assert "NaN" in reg.render()


def test_histogram_bucketing_and_percentiles():
    reg = Registry()
    h = reg.histogram("lat", buckets=exponential_buckets(0.001, 2.0, 4))
    # bounds: 0.001, 0.002, 0.004, 0.008 (+Inf overflow)
    for v in (0.0005, 0.001, 0.0015, 0.003, 0.1):
        h.observe(v)
    buckets = dict(h.cumulative_buckets())
    assert buckets[0.001] == 2  # le is INCLUSIVE (0.0005, 0.001)
    assert buckets[0.002] == 3
    assert buckets[0.004] == 4
    assert buckets[0.008] == 4
    assert buckets[float("inf")] == 5
    assert h.count == 5
    assert abs(h.sum - 0.106) < 1e-9
    assert h.max == 0.1
    assert 0.0 < h.percentile(0.50) <= h.percentile(0.99) <= h.max


def test_concurrent_increments_exact():
    reg = Registry()
    c = reg.counter("hits_total")
    h = reg.histogram("obs")
    n_threads, per = 8, 5000

    def work():
        for _ in range(per):
            c.inc()
            h.observe(0.001)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * per
    assert h.count == n_threads * per


# --- Prometheus text exposition ----------------------------------------------


def test_prometheus_rendering():
    reg = Registry()
    reg.counter("a_total", "things counted").inc(7)
    reg.gauge("b", "a gauge", ("svc",)).labels('we"ird\\').set(1.5)
    reg.histogram("c_seconds", buckets=(0.1, 1.0)).observe(0.5)
    text = reg.render()
    lines = text.strip().splitlines()
    assert "# HELP a_total things counted" in lines
    assert "# TYPE a_total counter" in lines
    assert "a_total 7" in lines
    assert 'b{svc="we\\"ird\\\\"} 1.5' in lines
    assert 'c_seconds_bucket{le="0.1"} 0' in lines
    assert 'c_seconds_bucket{le="1"} 1' in lines
    assert 'c_seconds_bucket{le="+Inf"} 1' in lines
    assert "c_seconds_sum 0.5" in lines
    assert "c_seconds_count 1" in lines


def test_snapshot_shape():
    reg = Registry()
    reg.counter("x_total").inc(2)
    reg.histogram("y").observe(1.0)
    snap = reg.snapshot()
    json.dumps(snap)  # must be JSON-able
    assert snap["x_total"]["type"] == "counter"
    assert snap["x_total"]["series"][0]["value"] == 2
    ys = snap["y"]["series"][0]
    assert ys["count"] == 1 and ys["avg"] == 1.0 and ys["p99"] == 1.0


# --- opmon shim --------------------------------------------------------------


def test_opmon_shim_feeds_telemetry_registry():
    from goworld_tpu.utils import opmon

    opmon.reset()
    op = opmon.Operation("shim.op")
    time.sleep(0.001)
    op.finish()
    # Legacy dump shape intact...
    d = opmon.dump()
    assert d["shim.op"]["count"] == 1
    assert d["shim.op"]["avg"] > 0
    assert 0.0 < d["shim.op"]["p50"] <= d["shim.op"]["p99"] <= d["shim.op"]["max"]
    # ...and the same samples are visible on the Prometheus surface.
    text = telemetry.render()
    assert 'op_duration_seconds_count{op="shim.op"} 1' in text
    opmon.reset()
    assert "shim.op" not in opmon.dump()


# --- phase tracer ------------------------------------------------------------


def test_phase_tracer_accumulates_segments():
    reg = Registry()
    tracer = telemetry.PhaseTracer(
        "tick_phase_seconds", ("a", "b"), registry=reg)
    tracer.begin()
    time.sleep(0.002)
    tracer.mark("a")
    time.sleep(0.001)
    tracer.mark("b")
    time.sleep(0.001)
    tracer.mark("a")  # second 'a' segment accumulates into the same tick
    tracer.commit()
    fam = reg.family("tick_phase_seconds")
    children = dict(fam.children())
    assert children[("a",)].count == 1  # ONE observation despite two marks
    assert children[("a",)].sum >= 0.003
    assert children[("b",)].count == 1
    total = children[(telemetry.TOTAL_PHASE,)]
    assert total.count == 1
    assert total.sum >= children[("a",)].sum + children[("b",)].sum - 1e-9
    tracer.commit()  # commit without begin: no-op
    assert total.count == 1


# --- /metrics endpoint round-trip --------------------------------------------


def test_metrics_endpoint_roundtrip():
    import urllib.request

    from goworld_tpu.utils import opmon
    from goworld_tpu.utils.debug_http import DebugHTTPServer

    telemetry.counter(
        "endpoint_test_total", "visible on /metrics").inc(11)
    op = opmon.Operation("endpoint.op")
    op.finish()

    async def run():
        srv = DebugHTTPServer("127.0.0.1", 0)
        await srv.start()

        def fetch(path):
            with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}{path}", timeout=5
            ) as r:
                return r.status, r.headers.get("Content-Type", ""), r.read()

        status, ctype, body = await asyncio.to_thread(fetch, "/metrics")
        assert status == 200
        assert ctype.startswith("text/plain")
        text = body.decode()
        assert "endpoint_test_total 11" in text
        assert 'op_duration_seconds_count{op="endpoint.op"}' in text
        # /heap/types now runs its gc census in a thread executor — the
        # route must still answer correctly.
        status, _, body = await asyncio.to_thread(fetch, "/heap/types")
        assert status == 200 and b"dict" in body
        await srv.stop()

    asyncio.run(run())


def test_metrics_endpoint_serves_service_gauges():
    """Dispatcher/gate queue-depth gauges appear on /metrics while the
    services run, and are removed at stop (no stale series)."""
    from goworld_tpu.dispatcher.service import DispatcherService

    async def run():
        svc = DispatcherService(77, desired_games=1, desired_gates=1)
        await svc.start()
        try:
            text = telemetry.render()
            assert 'dispatcher_queue_depth{dispid="77"} 0' in text
            assert 'dispatcher_pending_entities{dispid="77"} 0' in text
            assert 'dispatcher_entity_table_size{dispid="77"} 0' in text
        finally:
            await svc.stop()
        assert 'dispid="77"' not in telemetry.render()

    asyncio.run(run())


def test_metrics_during_running_deployment(tmp_path):
    """Acceptance: a live dispatcher+game deployment populates the
    tick-phase histograms and service gauges that /metrics renders."""
    from tests.test_game_service import start_stack, stop_stack
    from goworld_tpu.entity import entity_manager as em
    from goworld_tpu.utils import post

    em.cleanup_for_tests()
    try:
        async def run():
            disp, svc, task, cg, _peer = await start_stack(tmp_path)
            await asyncio.sleep(0.3)  # let the loop tick a few dozen times
            text = telemetry.render()
            await stop_stack(disp, svc, task, cg)
            return text

        text = asyncio.run(run())
        for phase in ("dispatch", "entity_logic", "aoi", "total"):
            assert (
                f'game_tick_phase_seconds_count{{phase="{phase}"}}' in text
            ), f"missing phase {phase}"
        # total observed on (almost) every busy tick of the 0.3 s window
        count_line = next(
            ln for ln in text.splitlines()
            if ln.startswith('game_tick_phase_seconds_count{phase="total"}')
        )
        assert int(count_line.rsplit(" ", 1)[1]) >= 10
        assert 'dispatcher_queue_depth{dispid="1"}' in text
        assert 'game_entities{gameid="1"}' in text
    finally:
        from goworld_tpu import kvdb, storage

        storage.set_backend(None)
        kvdb.set_backend(None)
        em.cleanup_for_tests()
        post.clear()


# --- AOI stage metrics -------------------------------------------------------


def test_aoi_backlog_gauge_and_tick_metrics():
    from goworld_tpu.entity.aoi.batched import BatchAOIService
    from goworld_tpu.ops.neighbor import NeighborParams

    class _E:
        def __init__(self):
            self.entered = []

        def is_destroyed(self):
            return False

        def on_enter_aoi(self, other):
            self.entered.append(other)

        def on_leave_aoi(self, other):
            pass

    svc = BatchAOIService(NeighborParams(
        capacity=64, cell_size=100.0, grid_x=8, grid_z=8, space_slots=1,
        cell_capacity=16, max_events=256))
    a, b = _E(), _E()
    sid = svc.alloc_space_id()
    svc.alloc_slot(a, sid, 10.0, 10.0, 50.0)
    svc.alloc_slot(b, sid, 20.0, 20.0, 50.0)
    svc.tick()  # dispatch 1 (nothing to deliver yet)
    svc.tick()  # delivers the first step's enter events
    backlog = telemetry.gauge("aoi_event_backlog")
    assert backlog.value >= 2  # a↔b enters delivered
    assert a.entered  # events really fired
    # The sync stall bound is config-sized and sub-second by default.
    assert svc.sync_wait_budget == 0.5
    text = telemetry.render()
    assert "aoi_event_backlog" in text
    assert "aoi_in_flight_age_seconds" in text


def test_sync_wait_budget_config():
    from goworld_tpu.config.read_config import AOIConfig, GoWorldConfig, _validate

    cfg = GoWorldConfig()
    cfg.aoi = AOIConfig(sync_wait_budget=0.0)
    with pytest.raises(ValueError, match="sync_wait_budget"):
        _validate(cfg)
    cfg.aoi = AOIConfig(sync_wait_budget=0.25, delivery="sync")
    _validate(cfg)  # fine


# --- KCP listener session caps -----------------------------------------------


def test_kcp_listener_session_caps():
    import struct

    from goworld_tpu.netutil.kcp import CMD_PUSH, KCPListener

    def sn0_push(conv: int) -> bytes:
        # 24-byte KCP segment header: conv, cmd, frg, wnd, ts, sn, una, len
        return struct.pack("<IBBHIIII", conv, CMD_PUSH, 0, 32, 0, 0, 0, 0)

    async def run():
        accepted = []
        lst = KCPListener(accepted.append, fec=None, max_sessions=4,
                          max_sessions_per_ip=2)
        drops = telemetry.counter(
            "kcp_sessions_dropped_total", labelnames=("reason",))
        ip_drops0 = drops.labels("ip_cap").value
        cap_drops0 = drops.labels("listener_cap").value
        try:
            # Per-IP cap: third session from the same address is dropped.
            for port in (1, 2, 3):
                lst.datagram_received(sn0_push(port), ("10.0.0.1", port))
            assert len(accepted) == 2
            assert drops.labels("ip_cap").value == ip_drops0 + 1
            # Listener cap: fill to 4 total, then any new address drops.
            lst.datagram_received(sn0_push(9), ("10.0.0.2", 9))
            lst.datagram_received(sn0_push(10), ("10.0.0.3", 10))
            assert len(accepted) == 4
            lst.datagram_received(sn0_push(11), ("10.0.0.4", 11))
            assert len(accepted) == 4
            assert drops.labels("listener_cap").value == cap_drops0 + 1
            # Closing a session frees its per-IP slot.
            accepted[0].close()
            lst.datagram_received(sn0_push(5), ("10.0.0.1", 5))
            assert len(accepted) == 5
        finally:
            for sess in accepted:
                sess.close()

    asyncio.run(run())


# --- the pipelined AOI loop --------------------------------------------------


def test_pinned_floor_gate():
    """The production pipelined AOI loop (``step_async`` dispatches tick
    t+1 before tick t is collected, one packed readback a tick) at a fixed
    2048-entity config, three engine instances over the same world: they
    share one compiled program, so the run counts no steady-state
    retrace."""
    import numpy as np

    from goworld_tpu.ops import NeighborEngine, NeighborParams
    from goworld_tpu.telemetry import sentinel

    n, cell, grid, steps = 2048, 100.0, 32, 20
    params = NeighborParams(
        capacity=n, cell_size=cell, grid_x=grid, grid_z=grid,
        space_slots=1, cell_capacity=64, max_events=32768,
    )
    world = grid * cell
    retraces0 = sentinel.steady_state_retraces()
    for _rep in range(3):
        eng = NeighborEngine(params)
        eng.reset()
        rng = np.random.default_rng(0)
        pos = rng.uniform(0, world, (n, 2)).astype(np.float32)
        active = np.ones(n, bool)
        space = np.zeros(n, np.int32)
        radius = np.full(n, 100.0, np.float32)
        vel = rng.normal(0, 3.0, (n, 2)).astype(np.float32)
        eng.step(pos, active, space, radius)  # compile + enter storm
        pending = None
        for _ in range(steps):
            pos += vel
            np.clip(pos, 0.0, world, out=pos)
            nxt = eng.step_async(pos, active, space, radius,
                                 meta_dirty=False)
            if pending is not None:
                pending.collect()
            pending = nxt
        pending.collect()
    assert sentinel.steady_state_retraces() - retraces0 == 0


# --- the sync fan-out pipeline -----------------------------------------------

# The per-hop attribution counters of fanout_hop_seconds_total (created by
# the game, dispatcher and gate services): the game's slab flag scan and
# interest-edge gather, its per-gate record pack and link send, the
# dispatcher's route, the gate's demux and its client writes.
FANOUT_HOPS = ("game_collect", "game_pack", "game_send",
               "dispatcher_route", "gate_demux", "client_write")


def _hop_seconds() -> dict[str, float]:
    fam = telemetry.counter("fanout_hop_seconds_total", "", ("hop",))
    return {h: fam.labels(h).value for h in FANOUT_HOPS}


@pytest.mark.parametrize("n_gates,n_bots,trace_off", [
    pytest.param(1, 80, False, id="one_gate"),
    pytest.param(2, 104, False, id="two_gates"),
    pytest.param(1, 80, True, id="trace_off"),
])
def test_fanout_pipeline_delivers(tmp_path, n_gates, n_bots, trace_off):
    """The host half of the sync pipeline end to end on a live in-process
    cluster (dispatcher + game + gates, xzlist AOI): every bot's avatar
    shares one AOI neighbourhood, every bot moves its avatar, and each
    move fans out through the game's collect and pack, the dispatcher's
    route and the gate's demux to every other bot's socket. Every bot
    receives a sync record of every other avatar with zero strict-bot
    errors; every hop of ``fanout_hop_seconds_total`` records busy time
    and the shares sum to 1; the run counts no steady-state retrace.
    With ``trace_sample_rate`` 0 no span reaches the span ring: tracing
    off costs no recording on the packet path."""
    from goworld_tpu.chaos import ChaosCluster
    from goworld_tpu.telemetry import sentinel, tracing

    saved_rate = tracing.sample_rate()

    async def run():
        cluster = ChaosCluster(str(tmp_path), n_dispatchers=1,
                               n_bots=n_bots, n_gates=n_gates)
        await cluster.start()
        try:
            if trace_off:
                tracing.configure(sample_rate=0)
                # Spans of packets sampled during boot land within a
                # tick or two; the ring is read after they settle.
                await asyncio.sleep(0.5)
            spans0 = tracing.snapshot()
            hops0 = _hop_seconds()
            retraces0 = sentinel.steady_state_retraces()

            def heard_every_avatar(bot) -> bool:
                others = [e for e in bot.entities.values()
                          if e.typename == "ChaosAvatar" and not e.is_player]
                return (len(others) == n_bots - 1
                        and all(e.keyframes > 0 for e in others))

            base = {b.name: (b.player.x, b.player.y, b.player.z)
                    for b in cluster.bots}
            deadline = time.monotonic() + 60.0
            beat = 0
            while not all(heard_every_avatar(b) for b in cluster.bots):
                assert time.monotonic() < deadline, (
                    "sync records never reached every bot: "
                    f"{sum(map(heard_every_avatar, cluster.bots))} of "
                    f"{n_bots} bots heard every avatar")
                beat += 1
                for b in cluster.bots:
                    x, y, z = base[b.name]
                    b.player.sync_position(x + 0.5 * (beat % 2), y, z, 0.0)
                await asyncio.sleep(cluster.sync_interval)
            hop_ms = {h: (v - hops0[h]) * 1000.0
                      for h, v in _hop_seconds().items()}
            return (cluster.bot_errors(), hop_ms,
                    sentinel.steady_state_retraces() - retraces0,
                    spans0, tracing.snapshot())
        finally:
            await cluster.stop()
            tracing.configure(sample_rate=saved_rate)

    errors, hop_ms, retraces, spans0, spans1 = asyncio.run(run())
    assert errors == [], errors[:5]
    assert all(ms > 0.0 for ms in hop_ms.values()), hop_ms
    total = sum(hop_ms.values())
    shares = {h: ms / total for h, ms in hop_ms.items()}
    assert set(shares) == set(FANOUT_HOPS)
    assert abs(sum(shares.values()) - 1.0) < 0.02
    assert retraces == 0
    if trace_off:
        assert spans1 == spans0, (
            f"{len(spans1) - len(spans0)} spans recorded with tracing off")


def test_multigame_floor_gate(tmp_path):
    """Live rebalancing over 2 real game subprocesses: from a fully
    skewed placement (every avatar on game1) the planner converges with
    ZERO entity loss and ZERO strict-bot errors, the census sums to the
    bot count, and the same cluster then survives the
    migrate-during-dispatcher-restart phase (commanded migrations either
    complete via the replay-ring flush or roll back; zero loss)."""
    from goworld_tpu.chaos.multigame import run_multigame

    n_bots = 12
    result = run_multigame(str(tmp_path), n_bots=n_bots, transport="tcp",
                           with_restart_phase=True)
    assert result["bot_errors"] == 0, result
    assert result["zero_loss"] is True, result
    assert result["census"][0] + result["census"][1] == n_bots
    phase = result["dispatcher_restart_phase"]
    assert phase["zero_loss"] is True, phase
    assert phase["bot_errors"] == 0, phase


def test_fanout_massive_floor_gate():
    """Thousands of clients under adaptive sync (``chaos/massive.py``):
    >= 1000 real client sockets across >= 2 gates served by the tiered +
    delta sync pipeline, with zero bot errors (the fleets decode
    strictly: a delta before its keyframe counts), zero steady-state
    retraces, the adaptive encoding's bytes/client/s at least 3x below
    the full-rate/full-precision phase measured on the SAME live cluster
    and movement, and a reconnect storm that re-converges the aggregated
    cluster view with the census conserved."""
    from goworld_tpu.chaos.massive import run_fanout_massive

    result = run_fanout_massive()
    assert result["clients"] >= 1000
    assert result["gates"] >= 2
    assert result["bot_errors"] == 0, result.get("bot_error_samples")
    assert result["steady_state_retraces"] == 0
    assert result["bytes_reduction"] >= 3.0, (
        f"adaptive sync must cut bytes/client/s >= 3x vs full-rate: "
        f"tiered {result['bytes_per_client_s']} vs full "
        f"{result['full_equiv_bytes_per_client_s']}")
    storm = result["reconnect_storm"]
    assert storm["bot_errors"] == 0, storm
    assert storm["census_clients"] == result["clients"]
