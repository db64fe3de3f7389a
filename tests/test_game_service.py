"""GameService integration: dispatcher + one game + a protocol-level fake
gate, all over real localhost sockets (the reference's localhost-cluster test
approach, SURVEY.md §4.3).

Multi-game flows (cross-game migration, freeze across processes) are covered
by the subprocess e2e harness; entity_manager state is per-process global, so
one process hosts exactly one game — same as the reference.
"""

import asyncio

import pytest

from goworld_tpu.config.read_config import (
    AOIConfig,
    DeploymentConfig,
    DispatcherConfig,
    GameConfig,
    GoWorldConfig,
    StorageConfig,
    KVDBConfig,
)
from goworld_tpu.common import gen_client_id, gen_entity_id
from goworld_tpu.dispatcher import DispatcherService
from goworld_tpu.dispatchercluster.cluster import ClusterClient
from goworld_tpu.entity import entity_manager as em
from goworld_tpu.entity.entity import Entity
from goworld_tpu.entity.space import Space
from goworld_tpu.game import GameService
from goworld_tpu.proto.msgtypes import MsgType
from goworld_tpu.utils import post
from tests.test_dispatcher import FakePeer, make_gate_cluster


class BootAccount(Entity):
    logins = []

    @classmethod
    def describe_entity_type(cls, desc):
        desc.define_attr("name", "Client")

    def on_client_connected(self):
        self.attrs.set("name", "fresh")

    def Login_Client(self, username):
        BootAccount.logins.append((self.id, username))
        self.attrs.set("name", username)


class TSpace(Space):
    pass


@pytest.fixture
def clean_entities(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    em.cleanup_for_tests()
    BootAccount.logins = []
    from goworld_tpu import kvreg, storage, kvdb

    kvreg.clear_for_tests()
    yield
    storage.set_backend(None)
    kvdb.set_backend(None)
    em.cleanup_for_tests()
    post.clear()


def make_cfg(disp_port: int, tmp_path, boot="BootAccount") -> GoWorldConfig:
    cfg = GoWorldConfig()
    cfg.deployment = DeploymentConfig(desired_games=1, desired_gates=1, desired_dispatchers=1)
    cfg.dispatchers = {1: DispatcherConfig(port=disp_port)}
    cfg.games = {1: GameConfig(boot_entity=boot, save_interval=0.0, position_sync_interval=0.02)}
    cfg.storage = StorageConfig(type="filesystem", directory=str(tmp_path / "es"))
    cfg.kvdb = KVDBConfig(type="filesystem", directory=str(tmp_path / "kv"))
    return cfg


async def start_stack(tmp_path, boot="BootAccount"):
    disp = DispatcherService(1, desired_games=1, desired_gates=1)
    await disp.start()
    cfg = make_cfg(disp.port, tmp_path, boot)
    em.register_space(TSpace)
    em.register_entity(BootAccount)
    svc = GameService(1, cfg, restore=False)
    task = asyncio.get_running_loop().create_task(svc.run_async())
    gate_peer = FakePeer()
    cg = make_gate_cluster(("127.0.0.1", disp.port), 1, gate_peer)
    cg.start()
    await cg.wait_connected()
    for _ in range(500):
        if svc.deployment_ready:
            break
        await asyncio.sleep(0.01)
    assert svc.deployment_ready
    return disp, svc, task, cg, gate_peer


async def stop_stack(disp, svc, task, cg):
    svc.terminate()
    await asyncio.wait_for(task, timeout=10)
    await cg.stop()
    await disp.stop()


def test_boot_entity_and_client_rpc(clean_entities, tmp_path):
    async def run():
        disp, svc, task, cg, gate_peer = await start_stack(tmp_path)
        cid, boot_eid = gen_client_id(), gen_entity_id()
        cg.select(0).send_notify_client_connected(cid, 1, boot_eid)
        # Gate sees the player-create for the boot entity.
        pkt = await gate_peer.expect(MsgType.CREATE_ENTITY_ON_CLIENT)
        assert pkt.read_uint16() == 1
        assert pkt.read_client_id() == cid
        assert pkt.read_bool() is True  # is_player
        assert pkt.read_entity_id() == boot_eid
        assert pkt.read_varstr() == "BootAccount"
        # Attr change streamed on client attach (set in on_client_connected).
        await gate_peer.expect(MsgType.NOTIFY_MAP_ATTR_CHANGE_ON_CLIENT)
        # Client calls an owner-only method through the dispatcher.
        cg.select(0).send_call_entity_method_from_client(boot_eid, "Login_Client", ("alice",), cid)
        for _ in range(200):
            if BootAccount.logins:
                break
            await asyncio.sleep(0.01)
        assert BootAccount.logins == [(boot_eid, "alice")]
        await stop_stack(disp, svc, task, cg)

    asyncio.run(run())


def test_client_disconnect_detaches(clean_entities, tmp_path):
    async def run():
        disp, svc, task, cg, gate_peer = await start_stack(tmp_path)
        cid, boot_eid = gen_client_id(), gen_entity_id()
        cg.select(0).send_notify_client_connected(cid, 1, boot_eid)
        await gate_peer.expect(MsgType.CREATE_ENTITY_ON_CLIENT)
        cg.select(0).send_notify_client_disconnected(cid, boot_eid)
        for _ in range(200):
            e = em.get_entity(boot_eid)
            if e is not None and e.client is None:
                break
            await asyncio.sleep(0.01)
        assert em.get_entity(boot_eid).client is None
        await stop_stack(disp, svc, task, cg)

    asyncio.run(run())


def test_terminate_saves_persistent_entities(clean_entities, tmp_path):
    async def run():
        disp, svc, task, cg, gate_peer = await start_stack(tmp_path)
        # Entity state persists across terminate via storage.
        from goworld_tpu import storage

        class P(Entity):
            @classmethod
            def describe_entity_type(cls, desc):
                desc.define_attr("gold", "Persistent")

        em.register_entity(P)
        e = em.create_entity_locally("P")
        e.attrs.set("gold", 99)
        eid = e.id
        await stop_stack(disp, svc, task, cg)
        assert storage.get_backend().read("P", eid) == {"gold": 99}

    asyncio.run(run())


def test_freeze_and_restore_round_trip(clean_entities, tmp_path):
    async def run():
        disp, svc, task, cg, gate_peer = await start_stack(tmp_path)

        class F(Entity):
            @classmethod
            def describe_entity_type(cls, desc):
                desc.define_attr("hp", "Client")

        em.register_entity(F)
        e = em.create_entity_locally("F")
        e.attrs.set("hp", 42)
        eid = e.id
        # SIGHUP path: freeze writes game1_freezed.dat and exits code 2.
        svc.start_freeze()
        rc = await asyncio.wait_for(task, timeout=10)
        assert rc == 2
        import os

        assert os.path.exists("game1_freezed.dat")
        # Simulate process restart: wipe in-memory state, re-register types.
        em.cleanup_for_tests()
        em.register_space(TSpace)
        em.register_entity(BootAccount)
        em.register_entity(F)
        cfg = make_cfg(disp.port, tmp_path)
        svc2 = GameService(1, cfg, restore=True)
        task2 = asyncio.get_running_loop().create_task(svc2.run_async())
        for _ in range(500):
            if svc2.deployment_ready:
                break
            await asyncio.sleep(0.01)
        e2 = em.get_entity(eid)
        assert e2 is not None and e2.attrs.get("hp") == 42
        assert em.get_nil_space() is not None
        await stop_stack(disp, svc2, task2, cg)

    asyncio.run(run())


def test_freeze_fence_is_immediate(clean_entities, tmp_path, monkeypatch):
    """The freeze fence is deterministic (ADVICE r4): once every
    dispatcher's ack is processed, per-connection FIFO proves all
    pre-block packets have landed — the game must freeze immediately, NOT
    sit out a quiescent window (the ack itself used to reset the quiet
    clock, making the window a hard floor). The window is monkeypatched
    UP to 2 s so the pass band is an order of magnitude, not 20 ms."""
    import time as _time

    from goworld_tpu import consts

    monkeypatch.setattr(consts, "FREEZE_QUIESCENT_WINDOW", 2.0)

    async def run():
        disp, svc, task, cg, gate_peer = await start_stack(tmp_path)
        t0 = _time.monotonic()
        svc.start_freeze()
        rc = await asyncio.wait_for(task, timeout=10)
        elapsed = _time.monotonic() - t0
        assert rc == 2
        assert elapsed < 1.0, (
            f"freeze took {elapsed:.3f}s — quiescent-window wait is back?"
        )
        await cg.stop()
        await disp.stop()

    asyncio.run(run())


def test_freeze_falls_back_when_a_dispatcher_never_acks(
    clean_entities, tmp_path, monkeypatch
):
    """A dead dispatcher must not wedge the freeze forever: after
    FREEZE_ACK_TIMEOUT with acks missing, the game falls back to the
    quiescent-window freeze (safety net)."""
    from goworld_tpu import consts
    from goworld_tpu.config.read_config import DispatcherConfig

    monkeypatch.setattr(consts, "FREEZE_ACK_TIMEOUT", 0.4)
    monkeypatch.setattr(consts, "FREEZE_DRAIN_CAP", 0.5)

    async def run():
        disp, svc, task, cg, gate_peer = await start_stack(tmp_path)
        # Phantom second dispatcher in the config: its ack can never
        # arrive, so the deterministic fence cannot complete.
        svc.cfg.dispatchers[2] = DispatcherConfig(port=1)
        svc.start_freeze()
        rc = await asyncio.wait_for(task, timeout=10)
        assert rc == 2  # froze anyway, via the safety net
        import os

        assert os.path.exists("game1_freezed.dat")
        await cg.stop()
        await disp.stop()

    asyncio.run(run())


def test_handshake_entity_list_filtered_per_dispatcher(clean_entities, tmp_path):
    """Each dispatcher's SET_GAME_ID must carry ONLY the entity ids it owns
    by hash (the reference's GetEntityIDsForDispatcher contract,
    DispatcherConnMgr.go:79). Sending the full list seeds stale entries on
    non-owner dispatchers; after a migration (which updates only the
    owner), the next restore's reconciliation on a non-owner REJECTS the
    entity and the game destroys it — live avatars vanished in the
    double-reload soak before this was fixed (round 4)."""
    from goworld_tpu.common import hash_entity_id

    cfg = make_cfg(0, tmp_path)
    cfg.deployment.desired_dispatchers = 3
    cfg.dispatchers = {i: DispatcherConfig(port=14000 + i) for i in (1, 2, 3)}
    svc = GameService(1, cfg, restore=False)

    class CaptureProxy:
        def __init__(self):
            self.calls = []

        def send_set_game_id(self, gameid, is_reconnect, is_restore,
                             is_ban_boot_entity, entity_ids):
            self.calls.append(list(entity_ids))

    em.register_space(TSpace)
    em.register_entity(BootAccount)
    em.create_nil_space(1)
    eids = [em.create_entity_locally("BootAccount").id for _ in range(40)]
    all_ids = set(em.entities().keys())

    per_index = []
    for index in range(3):
        proxy = CaptureProxy()
        svc._handshake(index, proxy)
        (sent,) = proxy.calls
        per_index.append(set(sent))
        for eid in sent:
            assert hash_entity_id(eid) % 3 == index, (eid, index)
    # Disjoint partition covering EVERY local entity (incl. the nil space).
    assert per_index[0] | per_index[1] | per_index[2] == all_ids
    assert not (per_index[0] & per_index[1])
    assert not (per_index[1] & per_index[2])
    assert not (per_index[0] & per_index[2])
    assert len(eids) == 40  # sanity: the partition had real members


@pytest.mark.parametrize("where", ["aoi", "game"])
def test_tpu_platform_without_a_tpu_fails_at_start(clean_entities, tmp_path,
                                                    where):
    """``[aoi] platform = tpu`` (or the game's ``aoi_platform = tpu``)
    refuses to start a game on a host without a TPU, instead of running
    its AOI elsewhere."""
    cfg = make_cfg(0, tmp_path)
    cfg.aoi = AOIConfig(backend="tpu", max_entities=256)
    if where == "aoi":
        cfg.aoi.platform = "tpu"
    else:
        cfg.games[1].aoi_platform = "tpu"
    svc = GameService(1, cfg, restore=False)
    with pytest.raises(RuntimeError, match="no TPU was found"):
        asyncio.run(svc.run_async())
