"""Dispatcher routing tests: two fake games + one fake gate in-process.

Mirrors the reference's testing approach for the dispatcher (SURVEY.md §4.3):
multi-process behavior exercised over real sockets on localhost.
"""

import asyncio

import pytest

from goworld_tpu.common import gen_client_id, gen_entity_id
from goworld_tpu.dispatcher import DispatcherService
from goworld_tpu.dispatchercluster.cluster import ClusterClient
from goworld_tpu.netutil.packet import Packet
from goworld_tpu.proto.msgtypes import MsgType


class FakePeer:
    """A game or gate endpoint: records every packet it receives."""

    def __init__(self):
        self.received = []
        self.event = asyncio.Event()

    def on_packet(self, index, msgtype, packet):
        self.received.append((msgtype, packet))
        self.event.set()

    async def expect(self, msgtype, timeout=5.0):
        deadline = asyncio.get_running_loop().time() + timeout
        while True:
            for i, (mt, pkt) in enumerate(self.received):
                if mt == msgtype:
                    del self.received[i]
                    return pkt
            remaining = deadline - asyncio.get_running_loop().time()
            assert remaining > 0, f"timed out waiting for {msgtype}"
            self.event.clear()
            try:
                await asyncio.wait_for(self.event.wait(), remaining)
            except asyncio.TimeoutError:
                pass


def make_game_cluster(addr, gameid, peer, entity_ids=(),
                      is_reconnect=False, is_restore=False):
    def handshake(index, proxy):
        proxy.send_set_game_id(
            gameid, is_reconnect, is_restore, False, list(entity_ids)
        )

    return ClusterClient([addr], handshake, peer.on_packet)


def make_gate_cluster(addr, gateid, peer):
    def handshake(index, proxy):
        proxy.send_set_gate_id(gateid)

    return ClusterClient([addr], handshake, peer.on_packet)


async def _cluster(desired_games=2, desired_gates=1):
    disp = DispatcherService(1, desired_games=desired_games, desired_gates=desired_gates)
    await disp.start()
    addr = ("127.0.0.1", disp.port)

    game1, game2, gate1 = FakePeer(), FakePeer(), FakePeer()
    c1 = make_game_cluster(addr, 1, game1)
    c2 = make_game_cluster(addr, 2, game2)
    cg = make_gate_cluster(addr, 1, gate1)
    for c in (c1, c2, cg):
        c.start()
        await c.wait_connected()
    # Let the dispatcher's logic loop drain all handshakes before tests send
    # traffic (the dispatcher drops packets for unregistered peers, as the
    # reference does).
    while not disp.deployment_ready:
        await asyncio.sleep(0.01)
    await asyncio.sleep(0.05)
    return disp, (c1, game1), (c2, game2), (cg, gate1)


async def _teardown(disp, *clusters):
    for c in clusters:
        await c.stop()
    await disp.stop()


def test_handshake_ack_and_deployment_ready():
    async def run():
        disp, (c1, game1), (c2, game2), (cg, gate1) = await _cluster()
        pkt = await game1.expect(MsgType.SET_GAME_ID_ACK)
        ack = pkt.read_data()
        assert 1 in ack["online_games"]
        # Barrier: 2 games + 1 gate connected → ready broadcast to games.
        await game1.expect(MsgType.NOTIFY_DEPLOYMENT_READY)
        await game2.expect(MsgType.NOTIFY_DEPLOYMENT_READY)
        assert disp.deployment_ready
        await _teardown(disp, c1, c2, cg)

    asyncio.run(run())


def test_entity_routing_and_blocking():
    async def run():
        disp, (c1, game1), (c2, game2), (cg, gate1) = await _cluster()
        eid = gen_entity_id()
        # Game 1 owns the entity.
        c1.select(0).send_notify_create_entity(eid)
        # Route a call from game 2 → must arrive at game 1.
        c2.select(0).send_call_entity_method(eid, "Hello", (42,))
        pkt = await game1.expect(MsgType.CALL_ENTITY_METHOD)
        assert pkt.read_entity_id() == eid
        assert pkt.read_varstr() == "Hello"
        assert pkt.read_args() == [42]

        # Migrate: MIGRATE_REQUEST blocks the entity; calls are buffered.
        c1.select(0).send_migrate_request(eid, gen_entity_id(), 2)
        await game1.expect(MsgType.MIGRATE_REQUEST_ACK)
        c2.select(0).send_call_entity_method(eid, "WhileBlocked", ())
        await asyncio.sleep(0.05)
        assert not any(mt == MsgType.CALL_ENTITY_METHOD for mt, _ in game1.received)
        # REAL_MIGRATE to game 2 → table flips, buffered call flushes to game 2.
        c1.select(0).send_real_migrate(eid, 2, {"type": "T", "attrs": {}})
        await game2.expect(MsgType.REAL_MIGRATE)
        pkt = await game2.expect(MsgType.CALL_ENTITY_METHOD)
        assert pkt.read_entity_id() == eid
        assert pkt.read_varstr() == "WhileBlocked"
        await _teardown(disp, c1, c2, cg)

    asyncio.run(run())


def test_gate_redirect_and_filtered_broadcast():
    async def run():
        disp, (c1, game1), (c2, game2), (cg, gate1) = await _cluster()
        eid, cid = gen_entity_id(), gen_client_id()
        # Redirect-range message routes to gate 1 by prefix.
        c1.select(0).send_call_entity_method_on_client(1, cid, eid, "Ping", ())
        pkt = await gate1.expect(MsgType.CALL_ENTITY_METHOD_ON_CLIENT)
        assert pkt.read_uint16() == 1
        assert pkt.read_client_id() == cid
        # Gate-handled broadcast reaches all gates.
        from goworld_tpu.proto.msgtypes import FilterOp

        c1.select(0).send_call_filtered_client_proxies(FilterOp.EQ, "lv", "3", "M", ())
        await gate1.expect(MsgType.CALL_FILTERED_CLIENTS)
        await _teardown(disp, c1, c2, cg)

    asyncio.run(run())


def test_client_connect_chooses_boot_game():
    async def run():
        disp, (c1, game1), (c2, game2), (cg, gate1) = await _cluster()
        cid, boot_eid = gen_client_id(), gen_entity_id()
        cg.select(0).send_notify_client_connected(cid, 1, boot_eid)
        # One of the two games gets the boot notify.
        done = asyncio.gather(
            game1.expect(MsgType.NOTIFY_CLIENT_CONNECTED, timeout=2),
            game2.expect(MsgType.NOTIFY_CLIENT_CONNECTED, timeout=2),
            return_exceptions=True,
        )
        results = await done
        oks = [r for r in results if isinstance(r, Packet)]
        assert len(oks) == 1
        # Entity table now routes the boot entity.
        assert disp.entities[boot_eid].gameid in (1, 2)
        await _teardown(disp, c1, c2, cg)

    asyncio.run(run())


def test_position_sync_aggregation():
    async def run():
        disp, (c1, game1), (c2, game2), (cg, gate1) = await _cluster()
        e1, e2 = gen_entity_id(), gen_entity_id()
        c1.select(0).send_notify_create_entity(e1)
        c2.select(0).send_notify_create_entity(e2)
        await asyncio.sleep(0.05)
        from goworld_tpu.proto.conn import pack_sync_record

        records = pack_sync_record(e1, 1, 2, 3, 0.5) + pack_sync_record(e2, 4, 5, 6, 0.7)
        cg.select(0).send_sync_position_yaw_from_client(records)
        # Tick loop regroups per target game.
        p1 = await game1.expect(MsgType.SYNC_POSITION_YAW_FROM_CLIENT)
        p2 = await game2.expect(MsgType.SYNC_POSITION_YAW_FROM_CLIENT)
        from goworld_tpu.proto.conn import unpack_sync_records

        assert unpack_sync_records(p1.payload)[0][0] == e1
        assert unpack_sync_records(p2.payload)[0][0] == e2
        await _teardown(disp, c1, c2, cg)

    asyncio.run(run())


def test_kvreg_replication():
    async def run():
        disp, (c1, game1), (c2, game2), (cg, gate1) = await _cluster()
        c1.select(0).send_kvreg_register("Service/1", "game1", False)
        pkt = await game2.expect(MsgType.KVREG_REGISTER)
        assert pkt.read_varstr() == "Service/1"
        assert pkt.read_varstr() == "game1"
        assert disp.kvreg["Service/1"] == "game1"
        # Non-forced duplicate is ignored.
        c2.select(0).send_kvreg_register("Service/1", "game2", False)
        await asyncio.sleep(0.05)
        assert disp.kvreg["Service/1"] == "game1"
        await _teardown(disp, c1, c2, cg)

    asyncio.run(run())


def test_reconnect_rejects_moved_entities():
    async def run():
        disp = DispatcherService(1, desired_games=2, desired_gates=0)
        await disp.start()
        addr = ("127.0.0.1", disp.port)
        eid = gen_entity_id()
        game1, game2 = FakePeer(), FakePeer()
        c1 = make_game_cluster(addr, 1, game1)
        c1.start()
        await c1.wait_connected()
        c1.select(0).send_notify_create_entity(eid)
        await asyncio.sleep(0.05)
        # Game 2 claims the same entity in its handshake → rejected.
        c2 = make_game_cluster(addr, 2, game2, entity_ids=[eid])
        c2.start()
        await c2.wait_connected()
        pkt = await game2.expect(MsgType.SET_GAME_ID_ACK)
        ack = pkt.read_data()
        assert ack["rejected"] == [eid]
        await _teardown(disp, c1, c2)

    asyncio.run(run())


def test_dispatcher_restart_recovery():
    """Elastic recovery (SURVEY.md §5.3): the dispatcher process dies and a
    fresh one binds the same port; games' reconnect loops re-handshake with
    their entity lists, the routing table rebuilds, and entity-routed calls
    flow again — without the games restarting."""

    async def run():
        disp = DispatcherService(1, desired_games=1, desired_gates=0)
        await disp.start()
        port = disp.port
        addr = ("127.0.0.1", port)

        eid = gen_entity_id()
        game1 = FakePeer()
        c1 = make_game_cluster(addr, 1, game1, entity_ids=[eid])
        c1.start()
        await c1.wait_connected()
        await game1.expect(MsgType.SET_GAME_ID_ACK)

        # Route an entity call through the dispatcher (loops back to game1).
        def call(tag: str):
            p = Packet()
            p.append_entity_id(eid)
            p.append_varstr(tag)
            p.append_args(())
            c1.select(0).send(MsgType.CALL_ENTITY_METHOD, p)

        call("Before")
        pkt = await game1.expect(MsgType.CALL_ENTITY_METHOD)
        assert pkt.read_entity_id() == eid

        # The dispatcher dies. The game stays up; its conn manager retries.
        await disp.stop()
        await asyncio.sleep(0.1)
        disp2 = DispatcherService(1, desired_games=1, desired_gates=0)
        for _ in range(50):  # the old socket may linger briefly
            try:
                await disp2.start(port=port)
                break
            except OSError:
                await asyncio.sleep(0.1)
        else:
            raise AssertionError("could not rebind dispatcher port")

        # Reconnect + re-handshake (entity list) happens automatically.
        await game1.expect(MsgType.SET_GAME_ID_ACK, timeout=10)
        call("After")
        pkt = await game1.expect(MsgType.CALL_ENTITY_METHOD, timeout=10)
        assert pkt.read_entity_id() == eid
        assert pkt.read_varstr() == "After"

        await _teardown(disp2, c1)

    asyncio.run(run())


def test_unplanned_game_death_cleanup(monkeypatch):
    """Failure detection (SURVEY.md §5.3, DispatcherService.go:592-640): a
    game dying WITHOUT the freeze handshake gets a short reconnect-grace
    window (PR 3 deviation — a link blip is steady-state with buffered
    links), after which it loses its routing entries, the survivors get
    NOTIFY_GAME_DISCONNECTED, and calls to the dead game's entities are
    dropped (buffered briefly, never delivered) instead of buffered
    forever."""
    from goworld_tpu import consts

    monkeypatch.setattr(consts, "DISPATCHER_RECONNECT_BUFFER_WINDOW", 0.3)

    async def run():
        disp = DispatcherService(1, desired_games=2, desired_gates=0)
        await disp.start()
        addr = ("127.0.0.1", disp.port)
        game1, game2 = FakePeer(), FakePeer()
        c1 = make_game_cluster(addr, 1, game1)
        c2 = make_game_cluster(addr, 2, game2)
        for c in (c1, c2):
            c.start()
            await c.wait_connected()
        eid = gen_entity_id()
        c1.select(0).send_notify_create_entity(eid)
        await asyncio.sleep(0.05)
        assert disp.entities[eid].gameid == 1

        # game1 dies abruptly (no freeze handshake).
        await c1.stop()
        await game2.expect(MsgType.NOTIFY_GAME_DISCONNECTED, timeout=10)
        assert eid not in disp.entities  # routes erased

        # Calls to the dead entity drop (unknown entity), not buffer.
        c2.select(0).send_call_entity_method(eid, "Ghost", ())
        await asyncio.sleep(0.1)
        assert not any(mt == MsgType.CALL_ENTITY_METHOD for mt, _ in game2.received)
        await _teardown(disp, c2)

    asyncio.run(run())


def test_entity_pending_queue_bound_drops_overflow(monkeypatch):
    """The per-entity pending queue is BOUNDED during a migrate window
    (reference consts.go:32 caps it at 1000; DispatcherService.go:34-80):
    overflow packets drop, and unblocking flushes exactly the buffered
    prefix in order."""
    from goworld_tpu import consts

    monkeypatch.setattr(consts, "ENTITY_PENDING_PACKET_QUEUE_MAX_LEN", 5)

    async def run():
        disp, (c1, game1), (c2, game2), (cg, gate1) = await _cluster()
        eid = gen_entity_id()
        c1.select(0).send_notify_create_entity(eid)
        # Open a migrate window: calls to eid now buffer (cap 5).
        c1.select(0).send_migrate_request(eid, gen_entity_id(), 2)
        await game1.expect(MsgType.MIGRATE_REQUEST_ACK)
        for i in range(9):
            c2.select(0).send_call_entity_method(eid, f"M{i}", ())
        await asyncio.sleep(0.1)
        assert not any(
            mt == MsgType.CALL_ENTITY_METHOD for mt, _ in game1.received
        )
        # Complete the migration to game 2: exactly the first 5 flush, in
        # order; the overflow (M5..M8) was dropped at the bound.
        c1.select(0).send_real_migrate(eid, 2, {"type": "T", "attrs": {}})
        await game2.expect(MsgType.REAL_MIGRATE)
        names = []
        for _ in range(5):
            pkt = await game2.expect(MsgType.CALL_ENTITY_METHOD)
            assert pkt.read_entity_id() == eid
            names.append(pkt.read_varstr())
        assert names == [f"M{i}" for i in range(5)]
        await asyncio.sleep(0.1)
        assert not any(
            mt == MsgType.CALL_ENTITY_METHOD for mt, _ in game2.received
        ), "overflow packets beyond the bound must be dropped"
        await _teardown(disp, c1, c2, cg)

    asyncio.run(run())


def test_sweep_dead_frozen_games(monkeypatch):
    """A game that dies WHILE FROZEN and never comes back (the reload
    window lapses): the sweep must clean it up like any dead game —
    buffered packets dropped, routes erased, NOTIFY_GAME_DISCONNECTED to
    the survivors (dispatcher/service.py _sweep_dead_frozen_games)."""
    from goworld_tpu import consts

    monkeypatch.setattr(consts, "DISPATCHER_FREEZE_GAME_TIMEOUT", 0.3)

    async def run():
        disp, (c1, game1), (c2, game2), (cg, gate1) = await _cluster()
        eid = gen_entity_id()
        c1.select(0).send_notify_create_entity(eid)
        c1.select(0).send_start_freeze_game()
        await game1.expect(MsgType.START_FREEZE_GAME_ACK)
        await c1.stop()  # the game dies mid-reload and never restores
        # Calls buffer while the freeze window holds...
        c2.select(0).send_call_entity_method(eid, "WhileFrozen", ())
        await asyncio.sleep(0.05)
        assert disp.games[1].pending, "freeze window should buffer"
        # ...until the window lapses: swept like an unplanned game death.
        await game2.expect(MsgType.NOTIFY_GAME_DISCONNECTED, timeout=10)
        assert eid not in disp.entities
        assert not disp.games[1].pending
        await _teardown(disp, c2, cg)

    asyncio.run(run())


@pytest.mark.parametrize("acked", [False, True],
                         ids=["target_died", "target_acked"])
def test_space_data_written_to_a_dying_target_bounces_home(monkeypatch,
                                                           acked):
    """SPACE_MIGRATE_DATA that the dispatcher already wrote to the
    target's link is the space's last copy until the receiver acks: a
    target that dies before acking (its write dies with it) and stays
    gone past the grace window gets the payload bounced home to the
    donor; after the ack, the same death bounces nothing."""
    from goworld_tpu import consts

    monkeypatch.setattr(consts, "DISPATCHER_RECONNECT_BUFFER_WINDOW", 0.3)

    async def run():
        disp, (c1, game1), (c2, game2), (cg, gate1) = await _cluster()
        sid = gen_entity_id()
        c1.select(0).send_notify_create_entity(sid)
        c1.select(0).send_space_migrate_prepare(sid, 2, [])
        await game1.expect(MsgType.SPACE_MIGRATE_PREPARE_ACK)
        c1.select(0).send_space_migrate_data(
            sid, 2, {"members": {}}, source_game=1)
        await game2.expect(MsgType.SPACE_MIGRATE_DATA)
        if acked:
            c2.select(0).send_space_migrate_ack(sid, 2)
            for _ in range(100):
                if sid not in disp._space_handoffs:
                    break
                await asyncio.sleep(0.01)
        await c2.stop()  # the target dies; the grace window lapses
        await game1.expect(MsgType.NOTIFY_GAME_DISCONNECTED, timeout=10)
        bounced = [pkt for mt, pkt in game1.received
                   if mt == MsgType.SPACE_MIGRATE_DATA]
        if acked:
            assert bounced == []
        else:
            assert [pkt.read_entity_id() for pkt in bounced] == [sid]
            assert disp.entities[sid].gameid == 1
        assert not disp._space_in_flight
        await _teardown(disp, c1, cg)

    asyncio.run(run())


def test_dispatcher_kills_silent_peer(monkeypatch):
    """Dispatcher-side liveness: a registered peer that stops sending
    (half-open link — here a raw socket that handshakes then goes mute,
    with client-side heartbeats suppressed) is closed once silent past
    peer_heartbeat_timeout, converting the stall into a normal disconnect."""

    async def run():
        disp = DispatcherService(1, desired_games=1, desired_gates=0,
                                 peer_heartbeat_timeout=0.4)
        await disp.start()
        import asyncio as aio

        from goworld_tpu.netutil.packet_conn import PacketConnection
        from goworld_tpu.proto.conn import GoWorldConnection

        reader, writer = await aio.open_connection("127.0.0.1", disp.port)
        proxy = GoWorldConnection(PacketConnection(reader, writer))
        proxy.send_set_game_id(1, False, False, False, [])
        for _ in range(200):
            if disp.games.get(1) is not None and disp.games[1].connected:
                break
            await aio.sleep(0.01)
        assert disp.games[1].connected
        # Mute peer: never sends again. The dispatcher must close the link
        # within ~2 heartbeat intervals, NOT wait on the OS.
        for _ in range(500):
            if not disp.games[1].connected:
                break
            await aio.sleep(0.01)
        assert not disp.games[1].connected, (
            "silent peer was never killed by the heartbeat sweep")
        proxy.close()
        await disp.stop()

    asyncio.run(run())


def test_replay_ring_buffers_and_replays_across_restart(monkeypatch):
    """The drop-on-down stub is gone: entity calls sent WHILE the
    dispatcher is down buffer in the replay ring and land, in order,
    after the reconnect handshake — and the drop counter does not move."""
    from goworld_tpu.chaos import dropped_packet_count

    async def run():
        disp = DispatcherService(1, desired_games=1, desired_gates=0)
        await disp.start()
        port = disp.port
        eid = gen_entity_id()
        game1 = FakePeer()
        c1 = make_game_cluster(("127.0.0.1", port), 1, game1,
                               entity_ids=[eid])
        c1.start()
        await c1.wait_connected()
        await game1.expect(MsgType.SET_GAME_ID_ACK)
        drops0 = dropped_packet_count()

        await disp.stop()
        await asyncio.sleep(0.1)
        # Sends while DOWN: ring-buffered, not dropped.
        for i in range(5):
            c1.select(0).send_call_entity_method(eid, f"Buffered{i}", ())
        assert len(c1._mgrs[0].ring) >= 5

        disp2 = DispatcherService(1, desired_games=1, desired_gates=0)
        for _ in range(50):
            try:
                await disp2.start(port=port)
                break
            except OSError:
                await asyncio.sleep(0.1)
        await game1.expect(MsgType.SET_GAME_ID_ACK, timeout=10)
        names = []
        for _ in range(5):
            pkt = await game1.expect(MsgType.CALL_ENTITY_METHOD, timeout=10)
            assert pkt.read_entity_id() == eid
            names.append(pkt.read_varstr())
        assert names == [f"Buffered{i}" for i in range(5)]
        assert dropped_packet_count() == drops0
        await _teardown(disp2, c1)

    asyncio.run(run())


def test_replay_ring_overflow_drops_oldest(monkeypatch):
    """At the byte cap the ring evicts its OLDEST packets (freshest state
    wins) and counts them on cluster_dropped_packets_total{overflow}."""
    from goworld_tpu import telemetry
    from goworld_tpu.dispatchercluster.cluster import _ReplayRing

    ring = _ReplayRing(cap=100)
    c = telemetry.counter("cluster_dropped_packets_total",
                          labelnames=("reason",)).labels("overflow")
    base = c.value
    for i in range(10):
        ring.push(MsgType.CALL_ENTITY_METHOD, bytes([i]) * 30)  # 30 B each
    assert ring.nbytes <= 100
    assert c.value - base == 7  # 10 pushed, 3 fit under 100 B
    kept = [payload[0] for _, payload in ring.drain()]
    assert kept == [7, 8, 9]  # the newest survive
    # A single packet larger than the whole cap can never be buffered.
    over = telemetry.counter("cluster_dropped_packets_total",
                             labelnames=("reason",)).labels("oversize")
    b0 = over.value
    ring.push(MsgType.CALL_ENTITY_METHOD, b"x" * 101)
    assert over.value - b0 == 1 and len(ring) == 0


def test_wait_connected_timeout_names_the_dispatcher():
    """Satellite: the wait_connected timeout is configurable (not the old
    hardcoded 10.0) and the error names the unreachable dispatcher's
    index and address."""

    async def run():
        from goworld_tpu.dispatchercluster.cluster import ClusterClient

        c = ClusterClient(
            [("127.0.0.1", 1)], lambda i, p: None, lambda i, m, p: None,
            wait_connected_timeout=0.2)
        c.start()
        try:
            with pytest.raises(TimeoutError) as ei:
                await c.wait_connected()
            msg = str(ei.value)
            assert "dispatcher 0" in msg and "127.0.0.1:1" in msg
        finally:
            await c.stop()

    asyncio.run(run())


def test_game_pending_queue_bound_while_frozen(monkeypatch):
    """Packets for a FROZEN game buffer up to the per-game bound
    (reference consts.go:30, 1e6) and the overflow drops; reconnecting
    with -restore flushes the buffered prefix."""
    from goworld_tpu import consts

    monkeypatch.setattr(consts, "GAME_PENDING_PACKET_QUEUE_MAX_LEN", 4)

    async def run():
        disp, (c1, game1), (c2, game2), (cg, gate1) = await _cluster()
        eid = gen_entity_id()
        c1.select(0).send_notify_create_entity(eid)
        # Freeze game 1, then sever its connection (reload window).
        c1.select(0).send_start_freeze_game()
        await game1.expect(MsgType.START_FREEZE_GAME_ACK)
        await c1.stop()
        await asyncio.sleep(0.1)
        for i in range(7):
            c2.select(0).send_call_entity_method(eid, f"F{i}", ())
        await asyncio.sleep(0.1)
        # Game 1 comes back with -restore and its entity list.
        game1b = FakePeer()
        c1b = make_game_cluster(
            ("127.0.0.1", disp.port), 1, game1b, [eid],
            is_reconnect=True, is_restore=True,
        )
        c1b.start()
        await c1b.wait_connected()
        names = []
        for _ in range(4):
            pkt = await game1b.expect(MsgType.CALL_ENTITY_METHOD)
            assert pkt.read_entity_id() == eid
            names.append(pkt.read_varstr())
        assert names == [f"F{i}" for i in range(4)]
        await asyncio.sleep(0.1)
        assert not any(
            mt == MsgType.CALL_ENTITY_METHOD for mt, _ in game1b.received
        ), "overflow past the frozen-game bound must be dropped"
        await _teardown(disp, c1b, c2, cg)

    asyncio.run(run())


# --- batch-routed fan-out path (ISSUE 6) -------------------------------------


def _legacy_sync_demux(entities, data: bytes) -> dict[int, bytes]:
    """The pre-ISSUE-6 per-record routing loop, verbatim (the oracle the
    vectorized demux must match): slice 32 B at a time, look up each
    record's entity, skip unknown/unrouted, append per target game."""
    from goworld_tpu.proto.conn import SYNC_RECORD_SIZE

    pending: dict[int, bytearray] = {}
    for off in range(0, (len(data) // SYNC_RECORD_SIZE) * SYNC_RECORD_SIZE,
                     SYNC_RECORD_SIZE):
        record = data[off:off + SYNC_RECORD_SIZE]
        eid = record[:16].decode("ascii")
        info = entities.get(eid)
        if info is None or info.gameid == 0:
            continue
        pending.setdefault(info.gameid, bytearray()).extend(record)
    return {gid: bytes(buf) for gid, buf in pending.items()}


def test_sync_demux_parity_oracle():
    """Parity oracle (ISSUE 6 satellite): the vectorized structured-array
    demux in _handle_sync_position_yaw_from_client must produce exactly
    the legacy per-record loop's per-game buffers — same bytes, same
    order, same unknown/unrouted drops — on randomized record streams
    (duplicate eids, interleaved destinations, unknown entities)."""
    import random

    from goworld_tpu.proto.conn import pack_sync_record

    rng = random.Random(0xF0)

    async def run():
        svc = DispatcherService(71, sync_flush_bytes=0)  # tick-only flush
        routed = [gen_entity_id() for _ in range(40)]
        for i, eid in enumerate(routed):
            svc._entity(eid).gameid = (1, 2, 3, 7)[i % 4]
        unrouted = [gen_entity_id() for _ in range(6)]
        for eid in unrouted:
            svc._entity(eid).gameid = 0  # known but not yet routed
        unknown = [gen_entity_id() for _ in range(6)]
        pool = routed + unrouted + unknown
        for _trial in range(25):
            k = rng.randrange(1, 120)
            stream = b"".join(
                pack_sync_record(rng.choice(pool), rng.random(),
                                 rng.random(), rng.random(), rng.random())
                for _ in range(k))
            expected = _legacy_sync_demux(svc.entities, stream)
            svc._pending_syncs.clear()
            svc._handle_sync_position_yaw_from_client(None, Packet(stream))
            got = {gid: bytes(buf)
                   for gid, buf in svc._pending_syncs.items()}
            assert got == expected, f"demux diverged at k={k}"

    asyncio.run(run())


def test_sync_demux_partial_tail_ignored():
    """A trailing partial record (malformed sender) is dropped whole —
    never forwarded as a truncated record."""
    from goworld_tpu.proto.conn import pack_sync_record

    async def run():
        svc = DispatcherService(72, sync_flush_bytes=0)
        eid = gen_entity_id()
        svc._entity(eid).gameid = 1
        stream = pack_sync_record(eid, 1, 2, 3, 4) + b"\x00" * 7
        svc._handle_sync_position_yaw_from_client(None, Packet(stream))
        assert bytes(svc._pending_syncs[1]) == stream[:32]

    asyncio.run(run())


class _RecordingProxy:
    """Minimal connected GoWorldConnection stand-in for routing tests."""

    closed = False

    def __init__(self):
        self.sent = []
        self.corks = 0
        self.uncorks = 0

    def send(self, msgtype, packet):
        self.sent.append((int(msgtype), packet.payload))

    def cork(self):
        self.corks += 1

    def uncork(self):
        self.uncorks += 1

    def close(self):
        self.closed = True


def test_sync_demux_size_triggered_flush():
    """A burst that fills a game's aggregation buffer past
    sync_flush_bytes flushes to that game IMMEDIATELY instead of waiting
    out the 5 ms tick (ISSUE 6: a burst never sits a full tick)."""
    from goworld_tpu.proto.conn import pack_sync_record

    async def run():
        svc = DispatcherService(73, sync_flush_bytes=128)  # 4 records
        proxy = _RecordingProxy()
        svc._game(1).proxy = proxy
        eid = gen_entity_id()
        svc._entity(eid).gameid = 1
        stream = b"".join(
            pack_sync_record(eid, i, 0, 0, 0) for i in range(5))
        svc._handle_sync_position_yaw_from_client(None, Packet(stream))
        # 5 records (160 B) >= 128 B trigger: flushed NOW, buffer cleared.
        assert [mt for mt, _ in proxy.sent] == [
            int(MsgType.SYNC_POSITION_YAW_FROM_CLIENT)]
        assert proxy.sent[0][1] == stream
        assert 1 not in svc._pending_syncs
        # Below the trigger: aggregates for the tick flush, nothing sent.
        small = pack_sync_record(eid, 9, 0, 0, 0)
        svc._handle_sync_position_yaw_from_client(None, Packet(small))
        assert len(proxy.sent) == 1
        assert bytes(svc._pending_syncs[1]) == small

    asyncio.run(run())


def test_redirect_routing_drop_and_grace_buffer_mid_batch():
    """Gate-redirect routing through the REAL batched logic loop: the
    gateid header is parsed once (no re-parse round trip), an unknown
    gateid drops, and a gate whose link dies MID-BATCH buffers the rest
    of the batch in its reconnect-grace window — with the batch's cork/
    uncork sweep surviving the dead link."""

    async def run():
        svc = DispatcherService(74)
        proxy = _RecordingProxy()
        gt = svc._gate(3)
        gt.proxy = proxy
        svc._proxy_gates[proxy] = 3

        def redirect(gateid, label):
            p = Packet()
            p.append_uint16(gateid)
            p.append_client_id(gen_client_id())
            p.append_bytes(label)
            return p

        task = asyncio.get_running_loop().create_task(svc._logic_loop())
        # One batch: deliver, unknown-drop, link death, then two more
        # packets that must land in the reconnect-grace buffer.
        svc._queue.put_nowait((None, MsgType.CALL_ENTITY_METHOD_ON_CLIENT,
                               redirect(3, b"live")))
        svc._queue.put_nowait((None, MsgType.CALL_ENTITY_METHOD_ON_CLIENT,
                               redirect(9, b"unknown-gate")))
        svc._queue.put_nowait((proxy, -1, None))  # disconnect sentinel
        svc._queue.put_nowait((None, MsgType.CALL_ENTITY_METHOD_ON_CLIENT,
                               redirect(3, b"graced-1")))
        svc._queue.put_nowait((None, MsgType.CALL_ENTITY_METHOD_ON_CLIENT,
                               redirect(3, b"graced-2")))
        for _ in range(100):
            if len(gt.pending) == 2:
                break
            await asyncio.sleep(0.01)
        task.cancel()
        assert [payload[18:] for _, payload in proxy.sent] == [b"live"]
        assert [p.payload[18:] for _, p in gt.pending] == [
            b"graced-1", b"graced-2"]
        import time

        assert gt.blocked(time.monotonic())
        # The batch corked the then-connected gate link and uncorked it
        # even though the link died mid-batch.
        assert proxy.corks == 1 and proxy.uncorks == 1

    asyncio.run(run())


# --- uds cluster transport (ISSUE 6) -----------------------------------------


def test_uds_transport_end_to_end_and_reconnect_replay(tmp_path):
    """[cluster] transport = uds smoke: the dispatcher serves a Unix-
    domain listener beside TCP, a gate/game cluster dials the socket path,
    the handshake + entity routing work unchanged, and a dispatcher
    restart REPLAYS ring-buffered sends over the re-dialed socket exactly
    like TCP (same framing, same replay rings)."""
    from goworld_tpu.chaos import dropped_packet_count
    from goworld_tpu.dispatchercluster.cluster import uds_path_for

    uds_dir = str(tmp_path)

    async def run():
        disp = DispatcherService(1, desired_games=1, desired_gates=0)
        await disp.start(uds_dir=uds_dir)
        assert disp.uds_path == uds_path_for(disp.port, uds_dir)
        port = disp.port
        eid = gen_entity_id()
        game1 = FakePeer()
        c1 = make_game_cluster(disp.uds_path, 1, game1, entity_ids=[eid])
        c1.start()
        await c1.wait_connected()
        ack = await game1.expect(MsgType.SET_GAME_ID_ACK)
        assert ack.read_data()["online_games"] == [1]
        # Route an RPC over the unix socket.
        c1.select(0).send_call_entity_method(eid, "OverUds", ())
        pkt = await game1.expect(MsgType.CALL_ENTITY_METHOD)
        assert pkt.read_entity_id() == eid
        drops0 = dropped_packet_count()

        import os

        await disp.stop()
        assert not os.path.exists(disp.uds_path)
        await asyncio.sleep(0.1)
        for i in range(3):
            c1.select(0).send_call_entity_method(eid, f"Buffered{i}", ())
        assert len(c1._mgrs[0].ring) >= 3

        disp2 = DispatcherService(1, desired_games=1, desired_gates=0)
        for _ in range(50):
            try:
                await disp2.start(port=port, uds_dir=uds_dir)
                break
            except OSError:
                await asyncio.sleep(0.1)
        await game1.expect(MsgType.SET_GAME_ID_ACK, timeout=10)
        names = []
        for _ in range(3):
            pkt = await game1.expect(MsgType.CALL_ENTITY_METHOD, timeout=10)
            assert pkt.read_entity_id() == eid
            names.append(pkt.read_varstr())
        assert names == [f"Buffered{i}" for i in range(3)]
        assert dropped_packet_count() == drops0
        await _teardown(disp2, c1)

    asyncio.run(run())


def test_route_span_record_count():
    """dispatcher.route spans carry a ``records`` attribute for sync
    packets (records-per-packet amortization on /trace); non-sync types
    carry none."""
    up = Packet(b"x" * (2 * 32))  # two 32 B client->server records
    assert DispatcherService._record_count(
        MsgType.SYNC_POSITION_YAW_FROM_CLIENT, up) == 2
    down = Packet(b"\x01\x00" + b"y" * (3 * 48))  # gateid + three blocks
    assert DispatcherService._record_count(
        MsgType.SYNC_POSITION_YAW_ON_CLIENTS, down) == 3
    assert DispatcherService._record_count(
        MsgType.CALL_ENTITY_METHOD, up) is None
