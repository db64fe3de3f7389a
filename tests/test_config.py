"""Config parsing tests (reference: engine/config/config_test.go parses the
sample ini)."""

import textwrap

import pytest

from goworld_tpu.config import read_config


SAMPLE = textwrap.dedent(
    """
    [deployment]
    dispatchers = 2
    games = 2
    gates = 2

    [dispatcher_common]
    host = 127.0.0.1

    [dispatcher1]
    port = 14001

    [dispatcher2]
    port = 14002

    [game_common]
    boot_entity = Account
    save_interval = 600

    [game1]
    aoi_platform = tpu
    [game2]
    log_level = debug
    aoi_platform = cpu

    [gate_common]
    host = 127.0.0.1
    compress_connection = true

    [gate1]
    port = 15001

    [gate2]
    port = 15002
    compress_connection = false

    [storage]
    type = filesystem
    directory = /tmp/teststorage
    circuit_failure_threshold = 4
    circuit_cooldown = 2.5
    retry_base_interval = 0.5
    retry_max_interval = 20
    deferred_bytes_cap = 1048576

    [kvdb]
    type = filesystem
    directory = /tmp/testkvdb

    [aoi]
    backend = xzlist
    max_entities = 4096

    [cluster]
    down_buffer_bytes = 4194304
    peer_heartbeat_timeout = 6
    wait_connected_timeout = 20
    reconnect_max_interval = 8
    transport = uds
    uds_dir = /tmp/gwt-test-uds
    sync_flush_bytes = 65536

    [rebalance]
    enabled = true
    driver_dispatcher = 2
    interval = 0.5
    report_interval = 0.25
    stale_after = 2.5
    min_entity_delta = 6
    max_moves_per_round = 3
    migrate_timeout = 4.5
    cooldown = 7

    [client]
    rpc_timeout = 9.5

    [sync]
    tier_cadences = 1, 4, 16
    quantize_bits = 7
    keyframe_interval = 48
    near_ratio = 0.4
    far_ratio = 0.9
    retier_interval = 6

    [scenario]
    seed = 7
    default_engine = sharded
    ticks_scale = 0.5
    """
)


@pytest.fixture()
def cfg(tmp_path):
    p = tmp_path / "goworld.ini"
    p.write_text(SAMPLE)
    read_config.set_config_file(str(p))
    yield read_config.get()
    read_config.set_config_file(None)


def test_deployment(cfg):
    assert cfg.deployment.desired_dispatchers == 2
    assert cfg.deployment.desired_games == 2
    assert cfg.deployment.desired_gates == 2


def test_common_inheritance(cfg):
    assert cfg.games[1].boot_entity == "Account"
    assert cfg.games[1].save_interval == 600
    assert cfg.games[2].log_level == "debug"
    assert cfg.games[1].log_level == "info"
    assert cfg.gates[1].compress_connection is True
    assert cfg.gates[2].compress_connection is False


def test_addrs(cfg):
    assert cfg.dispatchers[1].addr == ("127.0.0.1", 14001)
    assert cfg.gates[2].addr == ("127.0.0.1", 15002)


def test_storage_kvdb_aoi(cfg):
    assert cfg.storage.directory == "/tmp/teststorage"
    assert cfg.kvdb.type == "filesystem"
    assert cfg.aoi.backend == "xzlist"
    assert cfg.aoi.max_entities == 4096
    assert cfg.aoi.delivery == "pipelined"  # default


def test_aoi_delivery_knob(cfg, tmp_path):
    """[aoi] delivery parses and validates (pipelined | sync only)."""
    good = SAMPLE.replace("backend = xzlist",
                          "backend = xzlist\ndelivery = sync")
    p = tmp_path / "sync.ini"
    p.write_text(good)
    read_config.set_config_file(str(p))
    try:
        assert read_config.get().aoi.delivery == "sync"
    finally:
        read_config.set_config_file(None)
    bad = SAMPLE.replace("backend = xzlist",
                         "backend = xzlist\ndelivery = later")
    p = tmp_path / "bad_delivery.ini"
    p.write_text(bad)
    read_config.set_config_file(str(p))
    try:
        with pytest.raises(ValueError, match="delivery"):
            read_config.get()
    finally:
        read_config.set_config_file(None)
    # sync + multihost is a wedge factory (a dead peer stalls every
    # survivor's loop inside a collective) — must be rejected up front.
    mh = SAMPLE.replace(
        "backend = xzlist",
        "backend = tpu\ndelivery = sync\n"
        "multihost_coordinator = 127.0.0.1:18890",
    )
    p = tmp_path / "sync_multihost.ini"
    p.write_text(mh)
    read_config.set_config_file(str(p))
    try:
        with pytest.raises(ValueError, match="multihost"):
            read_config.get()
    finally:
        read_config.set_config_file(None)


def test_aoi_fuse_logic_knob(cfg, tmp_path):
    """[aoi] fuse_logic parses (default off) — ISSUE 12."""
    assert cfg.aoi.fuse_logic is False  # default
    on = SAMPLE.replace("backend = xzlist",
                        "backend = xzlist\nfuse_logic = true")
    p = tmp_path / "fuse.ini"
    p.write_text(on)
    read_config.set_config_file(str(p))
    try:
        assert read_config.get().aoi.fuse_logic is True
    finally:
        read_config.set_config_file(None)


def test_aoi_strip_placement_and_pallas_strip_cols(cfg, tmp_path):
    """[aoi] strip_placement / pallas_strip_cols parse and validate
    (ISSUE 15: the Pallas strip tier's placement + slab-width knobs)."""
    assert cfg.aoi.strip_placement == "topology"  # default
    assert cfg.aoi.pallas_strip_cols == 0  # default: derive
    good = SAMPLE.replace(
        "backend = xzlist",
        "backend = xzlist\nstrip_placement = ring\npallas_strip_cols = 24",
    )
    p = tmp_path / "strips.ini"
    p.write_text(good)
    read_config.set_config_file(str(p))
    try:
        got = read_config.get().aoi
        assert got.strip_placement == "ring"
        assert got.pallas_strip_cols == 24
    finally:
        read_config.set_config_file(None)
    bad = SAMPLE.replace("backend = xzlist",
                         "backend = xzlist\nstrip_placement = nearest")
    p = tmp_path / "bad_placement.ini"
    p.write_text(bad)
    read_config.set_config_file(str(p))
    try:
        with pytest.raises(ValueError, match="strip_placement"):
            read_config.get()
    finally:
        read_config.set_config_file(None)
    neg = SAMPLE.replace("backend = xzlist",
                         "backend = xzlist\npallas_strip_cols = -3")
    p = tmp_path / "bad_cols.ini"
    p.write_text(neg)
    read_config.set_config_file(str(p))
    try:
        with pytest.raises(ValueError, match="pallas_strip_cols"):
            read_config.get()
    finally:
        read_config.set_config_file(None)


def test_aoi_pallas_inkernel_drain(cfg, tmp_path):
    """[aoi] pallas_inkernel_drain parses (ISSUE 19 leg b: the kill
    switch that pins the Pallas tier's drain/table stage back to the
    XLA path).  Defaults ON; any non-truthy spelling turns it off."""
    assert cfg.aoi.pallas_inkernel_drain is True  # default
    off = SAMPLE.replace("backend = xzlist",
                         "backend = xzlist\npallas_inkernel_drain = false")
    p = tmp_path / "drain_off.ini"
    p.write_text(off)
    read_config.set_config_file(str(p))
    try:
        assert read_config.get().aoi.pallas_inkernel_drain is False
    finally:
        read_config.set_config_file(None)
    on = SAMPLE.replace("backend = xzlist",
                        "backend = xzlist\npallas_inkernel_drain = yes")
    p = tmp_path / "drain_on.ini"
    p.write_text(on)
    read_config.set_config_file(str(p))
    try:
        assert read_config.get().aoi.pallas_inkernel_drain is True
    finally:
        read_config.set_config_file(None)


def test_per_game_aoi_platform(cfg, tmp_path):
    """One game may ride the chip while the rest force CPU (single-client
    TPU transports); invalid values fail loudly like [aoi] platform."""
    assert cfg.games[1].aoi_platform == "tpu"
    assert cfg.games[2].aoi_platform == "cpu"
    bad = SAMPLE.replace("aoi_platform = tpu", "aoi_platform = gpu")
    p = tmp_path / "badplat.ini"
    p.write_text(bad)
    read_config.set_config_file(str(p))
    try:
        with pytest.raises(ValueError, match="aoi_platform"):
            read_config.get()
    finally:
        read_config.set_config_file(None)


def test_cluster_and_storage_resilience_knobs(cfg):
    """[cluster] link-resilience and [storage] circuit knobs parse (PR 3)."""
    assert cfg.cluster.down_buffer_bytes == 4 * 1024 * 1024
    assert cfg.cluster.peer_heartbeat_timeout == 6.0
    assert cfg.cluster.wait_connected_timeout == 20.0
    assert cfg.cluster.reconnect_max_interval == 8.0
    assert cfg.storage.circuit_failure_threshold == 4
    assert cfg.storage.circuit_cooldown == 2.5
    assert cfg.storage.retry_base_interval == 0.5
    assert cfg.storage.retry_max_interval == 20.0
    assert cfg.storage.deferred_bytes_cap == 1048576


def test_cluster_transport_and_flush_knobs(cfg):
    """[cluster] transport/uds_dir/sync_flush_bytes parse (ISSUE 6), and
    dispatcher_addrs resolves socket paths from the configured ports."""
    from goworld_tpu.dispatchercluster.cluster import (
        dispatcher_addrs,
        uds_path_for,
    )

    assert cfg.cluster.transport == "uds"
    assert cfg.cluster.uds_dir == "/tmp/gwt-test-uds"
    assert cfg.cluster.sync_flush_bytes == 65536
    addrs = dispatcher_addrs(cfg)
    assert addrs == [
        uds_path_for(d.port, "/tmp/gwt-test-uds")
        for _, d in sorted(cfg.dispatchers.items())
    ]
    assert all(isinstance(a, str) and a.endswith(".sock") for a in addrs)
    # tcp (the default) keeps plain (host, port) tuples.
    cfg.cluster.transport = "tcp"
    assert dispatcher_addrs(cfg) == [
        d.addr for _, d in sorted(cfg.dispatchers.items())]


def test_rebalance_and_client_sections(cfg):
    rb = cfg.rebalance
    assert rb.enabled is True
    assert rb.driver_dispatcher == 2
    assert rb.interval == 0.5
    assert rb.report_interval == 0.25
    assert rb.stale_after == 2.5
    assert rb.min_entity_delta == 6
    assert rb.max_moves_per_round == 3
    assert rb.migrate_timeout == 4.5
    assert rb.cooldown == 7.0
    assert cfg.client.rpc_timeout == 9.5


def test_sync_section(cfg):
    """[sync] adaptive per-client sync knobs (ISSUE 14) parse with
    exact types; defaults preserve the legacy full-rate path."""
    sy = cfg.sync
    assert sy.tier_cadences == (1, 4, 16)
    assert sy.quantize_bits == 7
    assert sy.keyframe_interval == 48
    assert sy.near_ratio == 0.4 and sy.far_ratio == 0.9
    assert sy.retier_interval == 6


def test_sync_defaults_when_absent(tmp_path):
    p = tmp_path / "g.ini"
    p.write_text("[deployment]\ndispatchers = 1\ngames = 1\ngates = 1\n"
                 "[dispatcher1]\nport = 14001\n")
    read_config.set_config_file(str(p))
    try:
        sy = read_config.get().sync
        assert sy.tier_cadences == (1,)
        assert sy.quantize_bits == 0
    finally:
        read_config.set_config_file(None)


@pytest.mark.parametrize("body,msg", [
    ("tier_cadences = 2, 4", "starting at 1"),
    ("tier_cadences = 1, 4, 4", "strictly ascending"),
    ("quantize_bits = 15", "quantize_bits"),
    ("keyframe_interval = 1", "keyframe_interval"),
    ("near_ratio = 0.9\nfar_ratio = 0.5", "near_ratio"),
    ("retier_interval = 0", "retier_interval"),
])
def test_sync_validation_rejects(tmp_path, body, msg):
    p = tmp_path / "g.ini"
    p.write_text("[deployment]\ndispatchers = 1\ngames = 1\ngates = 1\n"
                 "[dispatcher1]\nport = 14001\n[sync]\n" + body + "\n")
    read_config.set_config_file(str(p))
    try:
        with pytest.raises(ValueError, match=msg):
            read_config.get()
    finally:
        read_config.set_config_file(None)


def test_scenario_section(cfg):
    """[scenario] ad-hoc scenario-run knobs (ISSUE 16) parse with exact
    types; tests pass run_scenario's arguments and never read them."""
    sc = cfg.scenario
    assert sc.seed == 7
    assert sc.default_engine == "sharded"
    assert sc.ticks_scale == 0.5


def test_scenario_defaults_when_absent(tmp_path):
    p = tmp_path / "g.ini"
    p.write_text("[deployment]\ndispatchers = 1\ngames = 1\ngates = 1\n"
                 "[dispatcher1]\nport = 14001\n")
    read_config.set_config_file(str(p))
    try:
        sc = read_config.get().scenario
        assert sc.seed == -1  # negative = the registry's fixed seed
        assert sc.default_engine == "batched"
        assert sc.ticks_scale == 1.0
    finally:
        read_config.set_config_file(None)


@pytest.mark.parametrize("body,msg", [
    ("default_engine = pallas", "default_engine"),
    ("ticks_scale = 0", "ticks_scale"),
    ("ticks_scale = 200", "ticks_scale"),
])
def test_scenario_validation_rejects(tmp_path, body, msg):
    p = tmp_path / "g.ini"
    p.write_text("[deployment]\ndispatchers = 1\ngames = 1\ngates = 1\n"
                 "[dispatcher1]\nport = 14001\n[scenario]\n" + body + "\n")
    read_config.set_config_file(str(p))
    try:
        with pytest.raises(ValueError, match=msg):
            read_config.get()
    finally:
        read_config.set_config_file(None)


def test_rebalance_defaults_when_absent(tmp_path):
    p = tmp_path / "min.ini"
    p.write_text("[deployment]\ndispatchers = 1\n")
    read_config.set_config_file(str(p))
    try:
        cfg = read_config.get()
        assert cfg.rebalance.enabled is False
        assert cfg.rebalance.migrate_timeout == 5.0
        assert cfg.client.rpc_timeout == 5.0
    finally:
        read_config.set_config_file(None)


def test_cluster_knob_validation(tmp_path):
    """Nonsense resilience knobs fail loudly at load, not at 3 am."""
    for old, bad in (
        ("wait_connected_timeout = 20", "wait_connected_timeout = 0"),
        ("down_buffer_bytes = 4194304", "down_buffer_bytes = -1"),
        ("circuit_failure_threshold = 4", "circuit_failure_threshold = 0"),
        ("retry_max_interval = 20", "retry_max_interval = 0.1"),
        ("transport = uds", "transport = shm"),
        ("sync_flush_bytes = 65536", "sync_flush_bytes = -1"),
        ("interval = 0.5", "interval = 0"),
        ("stale_after = 2.5", "stale_after = 0.1"),
        ("min_entity_delta = 6", "min_entity_delta = 0"),
        ("migrate_timeout = 4.5", "migrate_timeout = 0"),
        ("driver_dispatcher = 2", "driver_dispatcher = 9"),
        ("rpc_timeout = 9.5", "rpc_timeout = 0"),
    ):
        assert old in SAMPLE
        p = tmp_path / "bad.ini"
        p.write_text(SAMPLE.replace(old, bad))
        read_config.set_config_file(str(p))
        try:
            with pytest.raises(ValueError):
                read_config.get()
        finally:
            read_config.set_config_file(None)


def test_duplicate_addr_rejected(tmp_path):
    bad = SAMPLE.replace("port = 14002", "port = 14001")
    p = tmp_path / "bad.ini"
    p.write_text(bad)
    read_config.set_config_file(str(p))
    try:
        with pytest.raises(ValueError):
            read_config.get()
    finally:
        read_config.set_config_file(None)
