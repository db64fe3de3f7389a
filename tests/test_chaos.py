"""Chaos scenarios as tier-1 gates (ISSUE 3 acceptance).

Every test drives a REAL in-process cluster (N dispatchers + game + gate
over localhost TCP, strict protocol bots) through goworld_tpu.chaos and
asserts the scenario's own invariants: zero bot errors, zero entity loss,
recovery within the deadline. The short scenarios run in default tier-1
(each a few seconds); the full combined soak is marked ``slow``.

Run just these with ``pytest -m chaos``.
"""

from __future__ import annotations

import asyncio

import pytest

from goworld_tpu.chaos import (
    ChaosCluster,
    scenario_battle_royale_freeze_restore,
    scenario_battle_royale_keyframe_storm,
    scenario_battle_royale_kill_game,
    scenario_dispatcher_restart,
    scenario_game_kill_recreate,
    scenario_gate_kill_reconnect,
    scenario_paused_dispatcher,
    scenario_service_outage_dispatcher_restart,
    scenario_severed_link,
    scenario_storage_outage,
)

pytestmark = pytest.mark.chaos

# Fast-recovery knobs shared by the tier-1 scenarios: aggressive heartbeat
# + reconnect so each test stays in the seconds range, and a storage
# circuit tuned to open within ~0.2 s of a dead backend.
FAST_STORAGE = dict(
    retry_base_interval=0.05, retry_max_interval=0.2,
    circuit_failure_threshold=3, circuit_cooldown=0.3,
)


def _run(scenario_fn, n_dispatchers=2, n_bots=12, **cluster_kw):
    async def run():
        cluster = ChaosCluster(
            cluster_kw.pop("run_dir"), n_dispatchers=n_dispatchers,
            n_bots=n_bots, storage_knobs=FAST_STORAGE, **cluster_kw)
        await cluster.start()
        try:
            return await scenario_fn(cluster)
        finally:
            await cluster.stop()

    return asyncio.run(run())


def test_dispatcher_kill_restart_smoke(tmp_path):
    """THE acceptance scenario: kill + restart one dispatcher (of 2) under
    12 strict bots — zero bot errors, zero dropped-packet increments at
    the default down_buffer_bytes, zero entity loss, pings issued DURING
    the outage delivered after the reconnect replay."""
    r = _run(scenario_dispatcher_restart, run_dir=str(tmp_path))
    assert r["bot_errors"] == 0
    assert r["dropped"] == 0
    assert r["recovery_s"] < 10.0


def test_dispatcher_kill_restart_smoke_uds(tmp_path):
    """ISSUE 6 tier-1 UDS smoke: the SAME kill+restart scenario over the
    uds cluster transport — crash, ring replay over the re-dialed unix
    socket, recovery — must behave identically to TCP (zero bot errors,
    zero drops, mid-outage pings delivered)."""
    r = _run(scenario_dispatcher_restart, run_dir=str(tmp_path),
             transport="uds")
    assert r["bot_errors"] == 0
    assert r["dropped"] == 0
    assert r["recovery_s"] < 10.0


def test_severed_link_recovers(tmp_path):
    """A game↔dispatcher socket aborted mid-tick (RST, not clean close)
    reconnects and replays within the deadline."""
    r = _run(scenario_severed_link, run_dir=str(tmp_path))
    assert r["bot_errors"] == 0
    assert r["dropped"] == 0
    assert r["recovery_s"] < 10.0


def test_paused_dispatcher_liveness_kill(tmp_path):
    """A dispatcher stalled past the heartbeat deadline with sockets OPEN
    (the half-open case liveness heartbeats exist for): peers must detect
    the silence and abort the links, and traffic must recover on resume."""
    r = _run(scenario_paused_dispatcher, run_dir=str(tmp_path),
             peer_heartbeat_timeout=0.6)
    assert r["bot_errors"] == 0
    # Detection must land near the configured deadline, not the OS's
    # multi-minute TCP timeout.
    assert r["detect_s"] < 5.0


def test_game_kill_recreate(tmp_path):
    """ISSUE 10: crash the game under live strict bots and recreate it
    cold — the dispatcher purges the dead incarnation's entity routes at
    the cold-boot handshake, clients reconnect onto fresh avatars, the
    census returns to exactly n_bots with full AOI interest, zero strict
    errors throughout."""
    r = _run(scenario_game_kill_recreate, run_dir=str(tmp_path))
    assert r["bot_errors"] == 0
    assert r["recovery_s"] < 20.0


def test_gate_kill_reconnect(tmp_path):
    """ISSUE 10: crash the gate — every client socket dies. The fresh
    replacement's generation-scoped detach despawns the dead
    incarnation's avatars (never the reconnecting clients' new ones, no
    matter the broadcast ordering), and the reconnect wave lands with no
    cross-client misroute (strict bots would flag one)."""
    r = _run(scenario_gate_kill_reconnect, run_dir=str(tmp_path))
    assert r["bot_errors"] == 0
    assert r["recovery_s"] < 20.0


@pytest.mark.slow
def test_migrate_during_dispatcher_restart_uds(tmp_path):
    """The ROADMAP-named scenario on the uds transport (the tcp variant
    runs in default tier-1 as part of the multigame floor gate): a batch
    of commanded migrations crosses a dispatcher kill+restart — each must
    complete (replay-ring flush) or roll back, census conserved, every
    bot answered."""
    from goworld_tpu.chaos.multigame import run_multigame

    r = run_multigame(str(tmp_path), n_bots=12, transport="uds",
                      with_restart_phase=True)
    assert r["bot_errors"] == 0
    assert r["zero_loss"] is True
    phase = r["dispatcher_restart_phase"]
    assert phase["zero_loss"] is True
    assert phase["bot_errors"] == 0
    assert (phase["migrations_done"]
            + phase["migrations_rolled_back"]) >= 0


def test_battle_royale_kill_game(tmp_path):
    """ISSUE 16: the battle-royale scenario (the SAME zone math the bench
    engines run) driving live avatars through real AOI, crossed with a
    game kill+recreate mid-collapse.  The scenario itself asserts the
    mass leave wave (scatter dissolves every edge), the mass enter wave
    (endgame restores full mutual interest on the reconnected fleet),
    census == n_bots, zero strict-bot errors, and an alert-free
    re-converged /cluster view."""
    r = _run(scenario_battle_royale_kill_game, run_dir=str(tmp_path))
    assert r["bot_errors"] == 0
    assert r["recovery_s"] < 20.0
    assert r["endgame_edges"] == 12 * 11
    assert r["cluster_view_converge_s"] < 20.0


def test_battle_royale_freeze_restore(tmp_path):
    """ISSUE 16: the battle-royale collapse crossed with the SIGHUP
    freeze→restore reload.  The scenario asserts rc 2, then that the
    RESTORED fleet is the same one — eids, positions and the pings slab
    column conserved bit-for-bit — before resuming the collapse to full
    endgame interest with the bots connected throughout; census
    conserved, zero strict errors, /cluster alert-free."""
    r = _run(scenario_battle_royale_freeze_restore, run_dir=str(tmp_path))
    assert r["bot_errors"] == 0
    assert r["recovery_s"] < 20.0
    assert r["endgame_edges"] == 12 * 11
    assert r["cluster_view_converge_s"] < 20.0


def test_storage_outage_circuit(tmp_path):
    """A storage backend failing writes opens the circuit (worker stays
    live: reads still served), and every deferred save lands once the
    backend heals."""
    r = _run(scenario_storage_outage, run_dir=str(tmp_path))
    assert r["lost_saves"] == 0
    assert r["recovery_s"] < 10.0


def test_battle_royale_keyframe_storm(tmp_path):
    """ISSUE 18: enter-wave keyframe storms under the delta sync plane.
    Two scatter→collapse waves; each must force at least one new_pair
    keyframe per re-formed interest edge (counter lockstep with the edge
    census), with zero strict-bot errors — a delta record arriving before
    its pair's keyframe would be flagged from the wire."""
    r = _run(scenario_battle_royale_keyframe_storm, run_dir=str(tmp_path),
             sync_knobs=dict(tier_cadences=(1, 4), quantize_bits=7))
    assert r["bot_errors"] == 0
    assert r["waves"] == 2
    for kf in r["keyframes_per_wave"]:
        assert kf >= r["edges_per_wave"]


def test_service_outage_under_dispatcher_restart(tmp_path):
    """ISSUE 18 catalog cross: service-heavy shard-routed saves while the
    storage backend fails writes AND a dispatcher restarts — the circuit
    opens (never wedges), mid-cross pings replay after the reconnect, the
    shard-receipt trajectory stays exactly-once, and every deferred save
    lands after the heal: zero lost documents, zero bot errors."""
    r = _run(scenario_service_outage_dispatcher_restart,
             run_dir=str(tmp_path))
    assert r["bot_errors"] == 0
    assert r["lost_saves"] == 0
    assert r["failed_writes"] >= 3  # past the breaker threshold
    assert r["recovery_s"] < 15.0


def test_multigame_spaces_kill_crosses(tmp_path):
    """ISSUE 18 acceptance: the 3-game whole-space chaos run. Receivers
    boot ARENA-LESS so the sharded planner service can only balance by
    whole-space handoffs; the three kill crosses then hit the protocol in
    its windows — receiver killed mid-PREPARE (donor space unfreezes in
    place or bounces home, outcome counted aborted/rolled_back/timeout,
    never done), donor killed mid-COMMIT (the routed payload is the
    space's one live copy and must be restored on the receiver), and the
    planner HOST killed after evacuation (kvreg purge → a survivor
    re-claims the shard and resumes rebalancing). Census conserved and
    zero strict-bot errors throughout; the fleet ends balanced."""
    from goworld_tpu.chaos.multigame import run_multigame_spaces

    r = run_multigame_spaces(str(tmp_path), n_bots=12, n_games=3,
                             transport="tcp")
    assert r["bot_errors"] == 0
    assert r["zero_loss"] is True
    phases = r["phases"]
    assert set(phases) == {"kill_receiver_mid_prepare",
                           "kill_donor_mid_commit", "kill_planner_host"}
    for name, p in phases.items():
        assert p["bot_errors"] == 0, name
        assert p["zero_loss"] is True, name
    # Mid-PREPARE: the donor's outcome counters must classify the wreck
    # as a failure (aborted/rolled_back/timeout) — never a false "done".
    assert phases["kill_receiver_mid_prepare"]["donor_outcomes_failed"] >= 1
    # Mid-COMMIT: the space landed whole on the receiver.
    assert phases["kill_donor_mid_commit"]["moved_members"] > 0
    # Planner failover: a DIFFERENT live game claimed the shard, and its
    # own gauge agreed with the kvreg claim.
    ph = phases["kill_planner_host"]
    assert ph["new_host"] != ph["old_host"]
    assert ph["new_host_gauge"] == 1.0
    assert sum(r["census_final"]) == 12


@pytest.mark.parametrize("fail", [None, "uds"], ids=["clean", "uds_red"])
def test_chaos_main_summarizes_and_gates(monkeypatch, capsys, fail):
    """``python -m goworld_tpu.chaos``: one suite run and one multigame
    restart phase per transport, the phase appended to that transport's
    scenarios, the loaded config's ``[slo]`` passed through, one JSON
    summary printed, and exit code 1 exactly when some scenario failed
    (a multigame exception counts as a failed scenario)."""
    import json

    from goworld_tpu.chaos import __main__ as chaos_main

    calls = []

    def fake_run_chaos(run_dir, transport="tcp", slo=None):
        calls.append(("suite", transport, slo))
        return {"scenarios": [{"scenario": "dispatcher_restart"}],
                "failures": [], "passed": 1, "transport": transport}

    def fake_run_multigame(run_dir, n_bots, transport, with_restart_phase):
        calls.append(("multigame", transport, with_restart_phase))
        if transport == fail:
            raise AssertionError("zero-loss clause broken")
        return {"dispatcher_restart_phase": {
            "scenario": "migrate_during_dispatcher_restart"}}

    monkeypatch.setattr(chaos_main, "run_chaos", fake_run_chaos)
    monkeypatch.setattr(chaos_main, "run_multigame", fake_run_multigame)
    rc = chaos_main.main()
    slo = chaos_main.read_config.get().slo
    assert calls == [("suite", "tcp", slo), ("multigame", "tcp", True),
                     ("suite", "uds", slo), ("multigame", "uds", True)]
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(out) == {"tcp", "uds"}
    assert [s["scenario"] for s in out["tcp"]["scenarios"]] == [
        "dispatcher_restart", "migrate_during_dispatcher_restart"]
    assert out["tcp"]["passed"] == 2
    if fail is None:
        assert rc == 0
        assert not out["uds"]["failures"]
    else:
        assert rc == 1
        [failure] = out["uds"]["failures"]
        assert failure["scenario"] == "migrate_during_dispatcher_restart"
        assert "zero-loss" in failure["error"]


@pytest.mark.slow
def test_full_chaos_soak(tmp_path):
    """All scenarios back to back over ONE cluster — state carried across
    faults (the ``python -m goworld_tpu.chaos`` shape, with more
    dispatchers)."""

    async def run():
        cluster = ChaosCluster(str(tmp_path), n_dispatchers=3, n_bots=16,
                               storage_knobs=FAST_STORAGE)
        await cluster.start()
        try:
            results = [
                await scenario_dispatcher_restart(cluster, victim=1),
                await scenario_severed_link(cluster, victim=2),
                await scenario_paused_dispatcher(cluster, victim=0),
                await scenario_storage_outage(cluster),
                # A second restart of a DIFFERENT dispatcher after all the
                # other faults: recovery must not depend on fresh state.
                await scenario_dispatcher_restart(cluster, victim=0),
            ]
        finally:
            await cluster.stop()
        return results

    results = asyncio.run(run())
    assert len(results) == 5
    assert all(r.get("bot_errors", 0) == 0 for r in results)
