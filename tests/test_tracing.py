"""Distributed tracing + flight recorder suite (ISSUE 5).

Covers: span-ring semantics, trailer wire format (unsampled packets
byte-identical to v3 framing; v4 trailers ignored-compatible at the recv
seam), scope nesting, the slow-tick flight recorder, /trace//flight/
/healthz endpoints, gwlog JSON mode with trace_id injection, cross-process
propagation over a REAL in-process cluster (including through a dispatcher
crash + replay-ring flush), and the tracecat merge. Tracing-off records
no span on the fan-out path:
tests/test_telemetry.py::test_fanout_pipeline_delivers[trace_off]. The
multi-process tracecat soak over a CLI cluster is marked ``slow``.
"""

from __future__ import annotations

import asyncio
import json
import pathlib
import time

import pytest

from goworld_tpu.telemetry import tracing

_REPO = pathlib.Path(__file__).resolve().parents[1]

pytestmark = pytest.mark.tracing


@pytest.fixture(autouse=True)
def _fresh_tracing():
    tracing.reset_for_tests()
    yield
    tracing.reset_for_tests()


# --- span ring ----------------------------------------------------------------


def test_span_ring_drop_oldest_counted():
    from goworld_tpu import telemetry

    ring = tracing.SpanRing(capacity=3)
    dropped0 = telemetry.counter("trace_spans_dropped_total").value
    for i in range(5):
        ring.append({"name": f"s{i}", "ts": float(i), "dur": 0.0,
                     "trace": 1, "span": i, "parent": 0})
    snap = ring.snapshot()
    assert [s["name"] for s in snap] == ["s2", "s3", "s4"]  # oldest gone
    assert telemetry.counter("trace_spans_dropped_total").value == dropped0 + 2


def test_configure_resizes_ring_keeping_tail():
    tracing.configure(sample_rate=1, ring_size=8)
    for i in range(8):
        tracing.record_span(f"s{i}", time.monotonic(), 0.001, 1, i + 1)
    tracing.configure(ring_size=4)
    assert [s["name"] for s in tracing.snapshot()] == ["s4", "s5", "s6", "s7"]


# --- sampling + scopes --------------------------------------------------------


def test_sampling_rates():
    tracing.configure(sample_rate=0)
    assert all(tracing.maybe_sample() is None for _ in range(50))
    assert tracing.root_scope("x") is None  # off = no allocation path
    tracing.configure(sample_rate=1)
    ctx = tracing.maybe_sample()
    assert ctx is not None and ctx.sampled and ctx.trace_id and ctx.span_id


def test_scope_nesting_and_parenting():
    tracing.configure(sample_rate=1)
    root = tracing.root_scope("root")
    assert root is not None and root.parent_id == 0
    with root:
        assert tracing.current() is root.ctx
        child = tracing.child_scope("child")
        with child:
            assert tracing.current() is child.ctx
            assert child.parent_id == root.ctx.span_id
        assert tracing.current() is root.ctx
    assert tracing.current() is None
    spans = {s["name"]: s for s in tracing.snapshot()}
    assert spans["child"]["parent"] == spans["root"]["span"]
    assert spans["child"]["trace"] == spans["root"]["trace"]
    # outside any scope, child_scope is free
    assert tracing.child_scope("nope") is None


def test_scope_records_error_and_restores_current():
    tracing.configure(sample_rate=1)
    scope = tracing.root_scope("boom")
    with pytest.raises(RuntimeError):
        with scope:
            raise RuntimeError("x")
    assert tracing.current() is None
    (span,) = tracing.snapshot()
    assert span["args"]["error"] == "RuntimeError"


# --- wire format --------------------------------------------------------------


class _CaptureConn:
    """PacketConnection stand-in recording (msgtype, payload) sends."""

    closed = False

    def __init__(self):
        self.sent = []

    def send_packet(self, msgtype, packet):
        self.sent.append((msgtype, packet.payload))


def test_unsampled_sends_byte_identical_and_sampled_trailer():
    from goworld_tpu.netutil.packet import Packet
    from goworld_tpu.proto.conn import GoWorldConnection
    from goworld_tpu.proto.msgtypes import MSGTYPE_TRACE_FLAG, MsgType

    tracing.configure(sample_rate=1)
    plain = _CaptureConn()
    wired = _CaptureConn()
    GoWorldConnection(plain).send_call_entity_method("e" * 16, "M", (1,))
    GoWorldConnection(wired, trace_wire=True).send_call_entity_method(
        "e" * 16, "M", (1,))
    # trace_wire with NO active context: byte-identical to a plain link.
    assert wired.sent == plain.sent

    scope = tracing.root_scope("t")
    with scope:
        GoWorldConnection(wired, trace_wire=True).send_call_entity_method(
            "e" * 16, "M", (1,))
    msgtype, payload = wired.sent[-1]
    assert msgtype == MsgType.CALL_ENTITY_METHOD | MSGTYPE_TRACE_FLAG
    base_payload = plain.sent[0][1]
    assert payload[:-tracing.TRAILER_SIZE] == base_payload
    ctx = tracing.decode_trailer(payload[-tracing.TRAILER_SIZE:])
    assert ctx.trace_id == scope.ctx.trace_id
    assert ctx.span_id == scope.ctx.span_id  # downstream parents onto it
    # HEARTBEAT stays wire-identical even inside a scope? No — heartbeats
    # are sent from link tasks outside scopes; simulate that:
    GoWorldConnection(wired, trace_wire=True).send_cluster_heartbeat()
    assert wired.sent[-1][0] == MsgType.HEARTBEAT


def test_recv_seam_strips_trailer_ignored_compatible():
    """A v4 flagged frame decodes to the unflagged msgtype + original
    payload with packet.trace attached; unflagged frames pass untouched
    (so pre-trace payload framing is unchanged — proto round-trip)."""
    from goworld_tpu.netutil.packet import Packet
    from goworld_tpu.netutil.packet_conn import PacketConnection
    from goworld_tpu.proto.conn import GoWorldConnection
    from goworld_tpu.proto.msgtypes import MSGTYPE_TRACE_FLAG, MsgType

    async def run():
        server_conns = []

        async def on_conn(reader, writer):
            server_conns.append(PacketConnection(reader, writer))

        server = await asyncio.start_server(on_conn, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        client = GoWorldConnection(PacketConnection(reader, writer))
        for _ in range(100):
            if server_conns:
                break
            await asyncio.sleep(0.01)
        sender = server_conns[0]

        body = b"hello-payload"
        ctx = tracing.TraceContext(0xABCD, 0x1234)
        # v4: flagged msgtype + trailer
        sender.send_packet(
            int(MsgType.CALL_ENTITY_METHOD) | MSGTYPE_TRACE_FLAG,
            Packet(body + tracing.encode_trailer(ctx)))
        # v3-style: plain frame
        sender.send_packet(int(MsgType.CALL_ENTITY_METHOD), Packet(body))
        sender.flush()

        mt1, p1 = await client.recv()
        mt2, p2 = await client.recv()
        assert mt1 == mt2 == MsgType.CALL_ENTITY_METHOD
        assert p1.payload == p2.payload == body
        assert p1.trace is not None and p1.trace.trace_id == 0xABCD
        assert p1.trace.span_id == 0x1234 and p1.trace.born is not None
        assert p2.trace is None
        writer.close()
        # Python 3.12's wait_closed waits for every accepted connection.
        sender.close()
        server.close()
        await server.wait_closed()

    asyncio.run(run())


def test_proto_version_bumped_for_trailer():
    from goworld_tpu.proto.msgtypes import MSGTYPE_TRACE_FLAG, PROTO_VERSION

    # v4 added the trailer; later protocol work may bump further (v5:
    # rebalancing + gate generations) but can never go back below it.
    assert PROTO_VERSION >= 4
    # The flag bit must sit above every routing class (gate↔client 2001+).
    assert MSGTYPE_TRACE_FLAG > 2001


# --- flight recorder ----------------------------------------------------------


def test_flight_recorder_ring_and_slow_dump():
    rec = tracing.FlightRecorder(capacity=4, slow_budget=0.05,
                                 warn_interval=0.0)
    t = time.monotonic()
    for i in range(6):
        rec.record(t + i, 0.001, {"dispatch": 0.001}, queue_depth=i)
    snap = rec.snapshot()
    assert len(snap["recent"]) == 4  # bounded
    assert snap["slow_ticks_total"] == 0 and snap["last_slow"] is None

    # A sampled span inside the slow tick must appear in the dump.
    tracing.configure(sample_rate=1)
    t0 = time.monotonic()
    tracing.record_span("game.handle", t0 + 0.01, 0.02, 77, 1)
    rec.record(t0, 0.08, {"dispatch": 0.07, "aoi": 0.01}, queue_depth=9)
    snap = rec.snapshot()
    assert snap["slow_ticks_total"] == 1
    dump = snap["last_slow"]
    assert dump["tick"]["total_ms"] == 80.0
    assert dump["budget_ms"] == 50.0
    assert any(s["name"] == "game.handle" for s in dump["spans"])
    assert dump["recent_ticks"]  # ring included


def test_flight_recorder_zero_budget_never_dumps():
    rec = tracing.FlightRecorder(capacity=4, slow_budget=0.0)
    rec.record(time.monotonic(), 99.0, {})
    assert rec.snapshot()["last_slow"] is None


def test_phase_tracer_commit_returns_attribution():
    from goworld_tpu.telemetry.metrics import Registry
    from goworld_tpu import telemetry

    tracer = telemetry.PhaseTracer("xyz_phase_seconds", ("a",),
                                   registry=Registry())
    assert tracer.commit() is None  # no begin
    tracer.begin()
    time.sleep(0.002)
    tracer.mark("a")
    t0, total, phases = tracer.commit()
    assert total >= phases["a"] > 0
    assert t0 <= time.monotonic()


# --- config / knobs -----------------------------------------------------------


def test_telemetry_and_log_config_validation():
    from goworld_tpu.config.read_config import (
        GoWorldConfig, LogConfig, TelemetryConfig, _validate)

    cfg = GoWorldConfig()
    cfg.telemetry = TelemetryConfig(trace_sample_rate=-1)
    with pytest.raises(ValueError, match="trace_sample_rate"):
        _validate(cfg)
    cfg.telemetry = TelemetryConfig()
    cfg.log = LogConfig(format="yaml")
    with pytest.raises(ValueError, match="format"):
        _validate(cfg)
    cfg.log = LogConfig(format="json")
    _validate(cfg)  # fine


def test_gwlog_json_format_injects_trace_id(tmp_path):
    from goworld_tpu.utils import gwlog

    logfile = tmp_path / "j.log"
    gwlog.setup(level="info", logfile=str(logfile), stderr=False, fmt="json")
    try:
        tracing.configure(sample_rate=1)
        gwlog.infof("outside span %d", 1)
        scope = tracing.root_scope("logged")
        with scope:
            gwlog.infof("inside span %d", 2)
        lines = [json.loads(ln) for ln in
                 logfile.read_text().strip().splitlines()]
        out = next(ln for ln in lines if ln["msg"] == "outside span 1")
        ins = next(ln for ln in lines if ln["msg"] == "inside span 2")
        assert "trace_id" not in out
        assert ins["trace_id"] == f"{scope.ctx.trace_id:016x}"
        assert ins["level"] == "info" and ins["source"]
    finally:
        gwlog.setup()  # restore the default text handlers


# --- debug-http endpoints -----------------------------------------------------


def _fetch(port, path):
    import urllib.request

    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=5
    ) as r:
        return r.status, r.read()


def test_trace_flight_healthz_endpoints():
    from goworld_tpu.dispatcher.service import DispatcherService
    from goworld_tpu.utils.debug_http import DebugHTTPServer

    tracing.configure(sample_rate=1)
    tracing.record_span("unit.span", time.monotonic(), 0.001, 42, 7)
    rec = tracing.FlightRecorder(capacity=4, slow_budget=0.0)
    rec.record(time.monotonic(), 0.002, {"dispatch": 0.002}, queue_depth=0)
    tracing.set_flight_recorder(rec)

    async def run():
        svc = DispatcherService(9, desired_games=1, desired_gates=1)
        await svc.start()
        srv = DebugHTTPServer("127.0.0.1", 0)
        await srv.start()
        try:
            status, body = await asyncio.to_thread(
                _fetch, srv.port, "/healthz")
            health = json.loads(body)
            assert status == 200
            from goworld_tpu.proto.msgtypes import PROTO_VERSION

            assert health["kind"] == "dispatcher" and health["id"] == 9
            assert health["proto_version"] == PROTO_VERSION
            assert "games" in health and "uptime_s" in health

            status, body = await asyncio.to_thread(
                _fetch, srv.port, "/trace")
            chrome = json.loads(body)
            assert status == 200
            names = [e.get("name") for e in chrome["traceEvents"]]
            assert "process_name" in names and "unit.span" in names
            xev = next(e for e in chrome["traceEvents"]
                       if e.get("name") == "unit.span")
            assert xev["ph"] == "X" and xev["dur"] >= 0.1
            assert xev["args"]["trace_id"] == f"{42:016x}"

            status, body = await asyncio.to_thread(
                _fetch, srv.port, "/trace?raw=1")
            raw = json.loads(body)
            assert raw["spans"] and raw["process"]

            status, body = await asyncio.to_thread(
                _fetch, srv.port, "/flight")
            flight = json.loads(body)
            assert flight["recent"][0]["phases_ms"]["dispatch"] == 2.0
        finally:
            await srv.stop()
            await svc.stop()
        # provider unregistered at stop: /healthz must not call into a
        # stopped service (fresh server, no provider)
        srv2 = DebugHTTPServer("127.0.0.1", 0)
        await srv2.start()
        try:
            _, body = await asyncio.to_thread(_fetch, srv2.port, "/healthz")
            assert "kind" not in json.loads(body)
        finally:
            await srv2.stop()

    asyncio.run(run())


# --- tracecat merge -----------------------------------------------------------


def test_tracecat_merge_and_summary():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "tracecat", _REPO / "tools" / "tracecat.py")
    tracecat = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tracecat)

    t = time.time()
    gate = [{"name": "gate.client_rpc", "ts": t, "dur": 0.01,
             "trace": 5, "span": 1, "parent": 0}]
    disp = [{"name": "dispatcher.route", "ts": t + 0.001, "dur": 0.002,
             "trace": 5, "span": 2, "parent": 1},
            {"name": "dispatcher.queue_dwell", "ts": t + 0.001,
             "dur": 0.001, "trace": 5, "span": 3, "parent": 2}]
    game = [{"name": "game.handle", "ts": t + 0.004, "dur": 0.003,
             "trace": 5, "span": 4, "parent": 2},
            {"name": "other.span", "ts": t, "dur": 0.001,
             "trace": 9, "span": 5, "parent": 0}]
    merged = tracecat.merge(
        [("gate1", gate), ("dispatcher1", disp), ("game1", game)])
    events = merged["traceEvents"]
    metas = [e for e in events if e["ph"] == "M"]
    assert {m["args"]["name"] for m in metas} == {
        "gate1", "dispatcher1", "game1"}
    assert len({m["pid"] for m in metas}) == 3  # distinct pids
    xs = [e for e in events if e["ph"] == "X"]
    assert len(xs) == 5
    # filter to one trace keeps only its tree
    only5 = tracecat.merge(
        [("gate1", gate), ("dispatcher1", disp), ("game1", game)],
        trace_id=5)
    assert all(e["args"]["trace_id"] == f"{5:016x}"
               for e in only5["traceEvents"] if e["ph"] == "X")
    summary = tracecat.trace_summary(
        [("gate1", gate), ("dispatcher1", disp), ("game1", game)])
    five = summary[f"{5:016x}"]
    assert five["processes"] == ["dispatcher1", "game1", "gate1"]
    assert five["roots"] == ["gate.client_rpc"]


# --- cross-process propagation over a real cluster ----------------------------


def _trace_index(spans):
    by_trace = {}
    for s in spans:
        by_trace.setdefault(s["trace"], []).append(s)
    return by_trace


def test_propagation_smoke_across_cluster(tmp_path):
    """A sampled client RPC produces ONE trace id whose spans cover gate
    ingress, dispatcher routing (with queue-dwell as its own span), game
    handling, and the fan-out back to the gate — the acceptance tree,
    driven over real localhost TCP links."""
    from goworld_tpu.chaos.harness import ChaosCluster

    async def run():
        cluster = ChaosCluster(str(tmp_path), n_dispatchers=1, n_bots=2)
        await cluster.start()
        try:
            tracing.configure(sample_rate=1)  # after start: trace all
            await cluster.assert_rpc_roundtrip()
            await asyncio.sleep(0.2)  # let fan-out spans land
        finally:
            tracing.configure(sample_rate=0)
            await cluster.stop()

    asyncio.run(run())
    full = []
    for t, spans in _trace_index(tracing.snapshot()).items():
        names = {s["name"] for s in spans}
        if {"gate.client_rpc", "dispatcher.route", "dispatcher.queue_dwell",
                "game.handle", "gate.client_fanout"} <= names:
            full.append((t, spans))
    assert full, "no trace spanned gate→dispatcher→game→gate"
    # parenting is a tree: dispatcher.route parents onto the gate RPC span
    t, spans = full[0]
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    gate_rpc = by_name["gate.client_rpc"][0]
    assert any(s["parent"] == gate_rpc["span"]
               for s in by_name["dispatcher.route"])
    assert gate_rpc["args"]["method"] == "Ping_Client"


def test_trace_survives_dispatcher_restart(tmp_path):
    """Satellite: a sampled RPC issued while its dispatcher is DOWN parks
    (trailer included) in the gate's replay ring, replays after the
    reconnect handshake, and finishes as ONE consistent trace id with the
    game's handling spans — the outage is visible as the gap before the
    dispatcher's routing span, not as a lost trace."""
    from goworld_tpu.chaos.harness import ChaosCluster

    mid_traces: dict = {}

    async def run():
        from goworld_tpu.common import hash_entity_id

        cluster = ChaosCluster(str(tmp_path), n_dispatchers=2, n_bots=2)
        await cluster.start()
        try:
            tracing.configure(sample_rate=1)
            await cluster.assert_rpc_roundtrip()
            # Deterministic victim: the dispatcher that routes bot 0's
            # avatar — its mid-outage RPC MUST take the replay-ring path.
            probe_eid = cluster.bots[0].player.id
            victim = hash_entity_id(probe_eid) % cluster.n_dispatchers
            n_before = len(tracing.snapshot())
            await cluster.kill_dispatcher(victim)
            # Mid-outage pings: every bot's RPC head-samples at 1/1.
            cluster._ping_seq += 1
            mid = cluster._ping_seq
            for b in cluster.bots:
                b.player.call_server("Ping_Client", mid)
            await asyncio.sleep(0.2)
            # The gate-side root span of the buffered RPC exists already;
            # the server side cannot (its dispatcher is dead).
            for s in tracing.snapshot()[n_before:]:
                if (s["name"] == "gate.client_rpc"
                        and s["args"].get("eid") == probe_eid):
                    mid_traces[s["trace"]] = s
            assert mid_traces, "bot 0's mid-outage RPC was not sampled"
            assert len(cluster.gates[0].cluster._mgrs[victim].ring), (
                "mid-outage send did not buffer in the replay ring")
            await cluster.restart_dispatcher(victim)
            await cluster._wait(cluster.links_up, 10.0,
                                "links never reconnected")
            await cluster._wait(
                lambda: all(mid in cluster._pongs[b.name]
                            for b in cluster.bots),
                10.0, "mid-outage pings were lost")
            await asyncio.sleep(0.2)
        finally:
            tracing.configure(sample_rate=0)
            await cluster.stop()

    asyncio.run(run())
    by_trace = _trace_index(tracing.snapshot())
    served = [
        t for t in mid_traces
        if any(s["name"] == "game.handle" for s in by_trace.get(t, []))
    ]
    assert served, (
        "no mid-outage trace reached the game with its id intact "
        f"(mid traces: {[hex(t) for t in mid_traces]})")
    # The replayed packet's dispatcher dwell is recorded, not silent.
    t = served[0]
    assert any(s["name"] == "dispatcher.queue_dwell"
               for s in by_trace[t])


# --- multi-process tracecat soak (slow) ---------------------------------------


@pytest.mark.slow
def test_tracecat_merges_live_cli_cluster(tmp_path):
    """Acceptance: a REAL 1 dispatcher + 1 game + 1 gate cluster (separate
    processes via the ops CLI) with a strict bot produces, through
    tools/tracecat.py, a Perfetto-loadable merged file containing at least
    one client-RPC span tree spanning all three processes with dispatcher
    dwell as its own span."""
    import os
    import socket
    import subprocess
    import sys

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    d = str(tmp_path)
    ports = {k: free_port() for k in
             ("disp", "gate", "h_disp", "h_game", "h_gate")}
    ini = f"""\
[deployment]
dispatchers = 1
games = 1
gates = 1

[dispatcher1]
port = {ports['disp']}
http_addr = 127.0.0.1:{ports['h_disp']}

[game1]
boot_entity = Account
save_interval = 600
http_addr = 127.0.0.1:{ports['h_game']}

[gate1]
port = {ports['gate']}
heartbeat_timeout = 30
http_addr = 127.0.0.1:{ports['h_gate']}

[storage]
type = filesystem
directory = {d}/es

[kvdb]
type = sqlite
directory = {d}/kv

[telemetry]
trace_sample_rate = 1
"""
    with open(os.path.join(d, "goworld.ini"), "w") as f:
        f.write(ini)
    env = dict(os.environ, PYTHONPATH=str(_REPO), JAX_PLATFORMS="cpu")

    def cli(*args, timeout=120):
        return subprocess.run(
            [sys.executable, "-m", "goworld_tpu.cli", *args],
            cwd=d, env=env, capture_output=True, text=True, timeout=timeout)

    async def drive_bot():
        from goworld_tpu.client import ClientBot

        bot = ClientBot(name="tracebot", strict=True,
                        heartbeat_interval=1.0)
        reports = []
        bot.rpc_handlers[(None, "OnLogin")] = lambda e, ok: None
        bot.rpc_handlers[(None, "OnEnterSpace")] = lambda e, kind: None
        bot.rpc_handlers[(None, "OnReportGame")] = (
            lambda e, *a: reports.append(a))
        await bot.connect("127.0.0.1", ports["gate"])
        acct = await bot.wait_player(timeout=15)
        acct.call_server("Login_Client", "trace_user", "123456")
        for _ in range(1500):
            if bot.player is not None and bot.player.typename == "Avatar":
                break
            await asyncio.sleep(0.01)
        assert bot.player.typename == "Avatar"
        for i in range(10):  # clean RPC round trips, all sampled (rate 1)
            bot.player.call_server("ReportGame_Client")
            await asyncio.sleep(0.05)
        for _ in range(500):
            if len(reports) >= 10:
                break
            await asyncio.sleep(0.01)
        assert len(reports) >= 10, f"only {len(reports)} reports came back"
        assert not bot.errors, bot.errors[:5]
        await bot.close()

    r = cli("start", "examples.test_game")
    try:
        assert r.returncode == 0, r.stdout + r.stderr
        asyncio.run(drive_bot())
        out = os.path.join(d, "merged_trace.json")
        rc = subprocess.run(
            [sys.executable, str(_REPO / "tools" / "tracecat.py"),
             "-configfile", os.path.join(d, "goworld.ini"), "-o", out],
            cwd=d, env=env, capture_output=True, text=True, timeout=60)
        assert rc.returncode == 0, rc.stdout + rc.stderr
        summary = json.loads(rc.stdout.strip().splitlines()[-1])
        assert summary["cross_process_traces"] >= 1, summary
        merged = json.loads(open(out).read())
        events = merged["traceEvents"]
        pids = {e["pid"] for e in events if e["ph"] == "M"}
        assert len(pids) == 3  # all three processes present
        xs = [e for e in events if e["ph"] == "X"]
        by_trace: dict = {}
        for e in xs:
            by_trace.setdefault(e["args"]["trace_id"], set()).add(
                (e["pid"], e["name"]))
        spanning = [
            t for t, rows in by_trace.items()
            if {n for _, n in rows} >= {
                "gate.client_rpc", "dispatcher.route",
                "dispatcher.queue_dwell", "game.handle"}
            and len({p for p, _ in rows}) >= 3
        ]
        assert spanning, "no RPC span tree crosses all three processes"
    finally:
        cli("stop", "examples.test_game")
        cli("kill", "examples.test_game")
