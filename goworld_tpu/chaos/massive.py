"""Thousands of real client sockets on one cluster, under tiered sync.

1008 client sockets, in 4 bot-fleet subprocesses of 252 bots each
(``chaos/botfleet.py``), across 2 in-process gates, one dispatcher, one
game and one AOI space. Avatars sit on a 42 x 24 grid at 55-unit spacing
with a 100-unit AOI radius, so each interior avatar watches 8 neighbours
(4 at 55 units in the middle cadence tier, 4 at 77.8 in the far tier
under the ``[sync]`` settings below), and every avatar jitters in
lockstep each sync interval (pairwise distances stay constant, so the
approach-rate rule never reclassifies a pair).

One run measures two phases over the same live cluster and the same
movement: "full", the full-rate/full-precision path, then "tiered",
cadence tiers plus quantized deltas; ``bytes_reduction`` is the ratio of
their bytes per client per second. A gate-kill reconnect storm then
rides the same cluster: gate 2 stops, its 504 clients re-dial gate 1,
and recovery is judged from the aggregated collector view (census
conserved at the bot count, no alert) plus the fleets' strict decode (a
reconnected client must be served a keyframe before any delta).

tests/test_telemetry.py::test_fanout_massive_floor_gate holds the
result to its clauses.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import tempfile
import time

import numpy as np

from goworld_tpu.telemetry import sentinel

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

MASSIVE_CONFIG = {
    "bots": 1008, "gates": 2, "fleets": 4, "cols": 42,
    "spacing": 55.0, "aoi_distance": 100.0, "sync_interval": 0.1,
    "measure_s": 4.0, "windows": 2, "settle_s": 2.0,
    "tier_cadences": (1, 8, 32), "quantize_bits": 7,
    "keyframe_interval": 64, "near_ratio": 0.5, "far_ratio": 0.8,
}


def run_fanout_massive() -> dict:
    """Boot the cluster and the fleets, run the full and tiered phases
    and the reconnect storm, and return what each phase measured plus
    ``bot_errors`` and ``steady_state_retraces`` over the run."""
    c = MASSIVE_CONFIG

    async def run() -> dict:
        from goworld_tpu.config.read_config import (
            AOIConfig,
            DeploymentConfig,
            DispatcherConfig,
            GameConfig,
            GateConfig,
            GoWorldConfig,
            KVDBConfig,
            StorageConfig,
        )
        from goworld_tpu.dispatcher import DispatcherService
        from goworld_tpu.entity import entity_manager as em
        from goworld_tpu.entity.entity import Entity
        from goworld_tpu.entity.slabs import SyncTuning
        from goworld_tpu.entity.space import Space
        from goworld_tpu.entity.vector import Vector3
        from goworld_tpu.game import GameService
        from goworld_tpu.gate import GateService

        n_bots = c["bots"]
        n_gates = c["gates"]
        holder: dict = {"arena": None, "joined": 0, "move": False}

        class MassSpace(Space):
            def on_space_created(self):
                if self.kind == 1:
                    self.enable_aoi(c["aoi_distance"])
                    holder["arena"] = self

        class MassAvatar(Entity):
            @classmethod
            def describe_entity_type(cls, desc):
                desc.set_use_aoi(True, c["aoi_distance"])
                desc.define_attr("accum", "Column")
                desc.define_attr("phase", "Column")

            def on_client_connected(self):
                arena = holder["arena"]
                if arena is not None:
                    i = holder["joined"]
                    holder["joined"] += 1
                    x = c["spacing"] * (i % c["cols"])
                    z = c["spacing"] * (i // c["cols"])
                    self.enter_space(arena.id, Vector3(x, 0.0, z))

            def on_client_disconnected(self):
                # Reconnect-storm hygiene: an orphaned boot avatar dies
                # so the census re-converges at the bot count.
                self.destroy()

            @classmethod
            def on_tick_batch(cls, view):
                if not holder["move"]:
                    return
                accum = view.col("accum") + view.dt
                if accum.max(initial=0.0) < c["sync_interval"]:
                    view.set_col("accum", accum)
                    return
                view.set_col(
                    "accum",
                    np.minimum(accum - c["sync_interval"],
                               c["sync_interval"]))
                phase = 1.0 - view.col("phase")
                view.set_col("phase", phase)
                # Lockstep jitter: every avatar's x moves by the SAME
                # half-unit each beat, so pairwise distances stay
                # constant and tier classification is stationary.
                view.set_position_yaw(x=np.floor(view.x) + 0.5 * phase)

        async def fleet_spawn(ports: list[int], bots: int):
            return await asyncio.create_subprocess_exec(
                sys.executable, "-m", "goworld_tpu.chaos.botfleet",
                "--gates", ",".join(str(p) for p in ports),
                "--bots", str(bots), "--stagger-ms", "3",
                stdin=asyncio.subprocess.PIPE,
                stdout=asyncio.subprocess.PIPE,
                stderr=asyncio.subprocess.DEVNULL,
                cwd=_REPO,
            )

        async def fleet_read(proc) -> dict:
            line = await asyncio.wait_for(proc.stdout.readline(), 60)
            if not line:
                raise RuntimeError("bot fleet died (empty stdout)")
            return json.loads(line)

        async def fleet_cmd(proc, cmd: str) -> dict:
            proc.stdin.write(
                (json.dumps({"cmd": cmd}) + "\n").encode())
            await proc.stdin.drain()
            return await fleet_read(proc)

        async def fleets_report(procs) -> dict:
            reports = []
            for p in procs:
                reports.append(await fleet_cmd(p, "report"))
            return {
                k: sum(r[k] for r in reports)
                for k in ("bots", "alive", "players", "entities",
                          "keyframes", "deltas", "records",
                          "sync_bytes", "sync_packets", "errors")
            } | {"error_samples": [s for r in reports
                                   for s in r["error_samples"]][:5]}

        async def measure(procs, seconds: float, windows: int) -> dict:
            best = None
            for _ in range(windows):
                a = await fleets_report(procs)
                t0 = time.perf_counter()
                await asyncio.sleep(seconds)
                dt = time.perf_counter() - t0
                b = await fleets_report(procs)
                w = {
                    "records_per_s": (b["records"] - a["records"]) / dt,
                    "keyframes_per_s":
                        (b["keyframes"] - a["keyframes"]) / dt,
                    "deltas_per_s": (b["deltas"] - a["deltas"]) / dt,
                    "bytes_per_client_s":
                        (b["sync_bytes"] - a["sync_bytes"]) / dt / n_bots,
                }
                if best is None or w["records_per_s"] > best["records_per_s"]:
                    best = w
                best["errors"] = b["errors"]
            return {k: round(v, 1) for k, v in best.items()}

        em.cleanup_for_tests()
        tmp = tempfile.TemporaryDirectory(prefix="gw_massive_")
        disp = game = game_task = None
        gates: list = []
        procs: list = []
        try:
            em.register_space(MassSpace)
            em.register_entity(MassAvatar)
            disp = DispatcherService(1, desired_games=1,
                                     desired_gates=n_gates)
            await disp.start()
            cfg = GoWorldConfig()
            cfg.deployment = DeploymentConfig(
                desired_games=1, desired_gates=n_gates,
                desired_dispatchers=1)
            cfg.dispatchers = {1: DispatcherConfig(port=disp.port)}
            cfg.games = {1: GameConfig(
                boot_entity="MassAvatar", save_interval=0.0,
                position_sync_interval=c["sync_interval"])}
            cfg.gates = {
                g: GateConfig(port=0, heartbeat_timeout=0.0)
                for g in range(1, n_gates + 1)
            }
            cfg.aoi = AOIConfig(backend="xzlist")  # host pipeline only
            cfg.storage = StorageConfig(
                type="filesystem", directory=tmp.name + "/es")
            cfg.kvdb = KVDBConfig(
                type="filesystem", directory=tmp.name + "/kv")
            game = GameService(1, cfg, restore=False)
            game_task = asyncio.get_running_loop().create_task(
                game.run_async())
            for g in range(1, n_gates + 1):
                gate = GateService(g, cfg)
                await gate.start()
                gates.append(gate)
            for _ in range(1000):
                if game.deployment_ready:
                    break
                await asyncio.sleep(0.01)
            assert game.deployment_ready, "cluster never became ready"
            em.create_space_locally(1)
            assert holder["arena"] is not None

            ports = [g.port for g in gates]
            per_fleet = n_bots // c["fleets"]
            assert per_fleet * c["fleets"] == n_bots
            for _ in range(c["fleets"]):
                procs.append(await fleet_spawn(ports, per_fleet))
            for p in procs:
                ready = await asyncio.wait_for(fleet_read(p), 180)
                assert ready.get("ready") == per_fleet, ready
            # Boot convergence: every bot owns a player and the interest
            # graph has stabilized (edge count unchanged for a second).
            slabs = em.runtime.slabs
            stable_since = None
            last_edges = -1
            deadline = time.monotonic() + 180
            while time.monotonic() < deadline:
                r = await fleets_report(procs)
                edges = slabs.edge_count()
                if r["players"] == n_bots and edges == last_edges:
                    if stable_since is None:
                        stable_since = time.monotonic()
                    elif time.monotonic() - stable_since > 1.0:
                        break
                else:
                    stable_since = None
                last_edges = edges
                await asyncio.sleep(0.25)
            else:
                raise AssertionError(
                    f"massive boot never converged: {r} edges={last_edges}")
            out: dict = {
                "clients": n_bots,
                "gates": n_gates,
                "fleets": c["fleets"],
                "edges": int(slabs.edge_count()),
                "entities": len(em.entities()) - 1,  # minus the space
            }

            # Phase 1: the full-rate/full-precision path.
            slabs.configure_sync(SyncTuning())
            holder["move"] = True
            await asyncio.sleep(c["settle_s"])
            out["full"] = await measure(
                procs, c["measure_s"], c["windows"])
            # Phase 2: cadence tiers + quantized deltas. Baselines
            # re-establish via one keyframe wave.
            slabs.configure_sync(SyncTuning(
                tier_cadences=c["tier_cadences"],
                quantize_bits=c["quantize_bits"],
                keyframe_interval=c["keyframe_interval"],
                near_ratio=c["near_ratio"], far_ratio=c["far_ratio"],
            ))
            await asyncio.sleep(c["settle_s"])
            out["tiered"] = await measure(
                procs, c["measure_s"], c["windows"])
            fb = out["full"]["bytes_per_client_s"]
            tb = out["tiered"]["bytes_per_client_s"]
            out["bytes_per_client_s"] = tb
            out["full_equiv_bytes_per_client_s"] = fb
            out["bytes_reduction"] = round(fb / max(tb, 1e-9), 2)
            out["records_reduction"] = round(
                out["full"]["records_per_s"]
                / max(out["tiered"]["records_per_s"], 1e-9), 2)
            out["tier_edges"] = {
                str(t): int(n) for t, n in enumerate(
                    np.bincount(
                        slabs._e_tier[:slabs.edge_count()],
                        minlength=len(c["tier_cadences"])).tolist())
            }

            # Movement stays ON through the storm: reconnected clients
            # must decode the live stream (keyframes first).
            out["reconnect_storm"] = await _massive_storm(
                c, disp, game, gates, procs, fleets_report, fleet_cmd,
                n_bots)
            holder["move"] = False
            r = await fleets_report(procs)
            out["bot_errors"] = r["errors"]
            out["bot_error_samples"] = r["error_samples"]
            return out
        finally:
            for p in procs:
                try:
                    p.stdin.close()
                except Exception:
                    pass
            for p in procs:
                try:
                    await asyncio.wait_for(p.wait(), 10)
                except Exception:
                    p.kill()
            for gate in gates:
                try:
                    await gate.stop()
                except Exception:
                    pass
            if game is not None:
                game.terminate()
                try:
                    await asyncio.wait_for(game_task, timeout=15)
                except Exception:
                    pass
            if disp is not None:
                await disp.stop()
            from goworld_tpu import kvdb, storage

            storage.set_backend(None)
            kvdb.set_backend(None)
            em.cleanup_for_tests()
            tmp.cleanup()

    retraces0 = sentinel.steady_state_retraces()
    out = asyncio.run(run())
    out["steady_state_retraces"] = int(
        sentinel.steady_state_retraces() - retraces0)
    return out


async def _massive_storm(c, disp, game, gates, procs, fleets_report,
                         fleet_cmd, n_bots: int) -> dict:
    """Gate-kill + reconnect storm at the massive client count, judged
    from the AGGREGATED collector view like every other chaos scenario:
    stop gate 2, re-dial its clients against gate 1, then poll an
    in-process ClusterCollector over the LIVE services until every
    surviving process reports, the client census is conserved at the bot
    count, and no alert remains. The fleets' strict decode carries the
    adaptive-sync assertion: a reconnected client must see a
    full-precision keyframe before any delta (stale-baseline renders
    count as bot errors)."""
    from goworld_tpu.telemetry.collector import ClusterCollector

    t0 = time.monotonic()
    errors_before = (await fleets_report(procs))["errors"]
    await gates[1].stop()
    gates.pop(1)
    # Re-dial storm: every dead bot walks the gate list and lands on the
    # survivor (fleet-side logic; 504 reconnects here).
    reconnected = 0
    for p in procs:
        r = await fleet_cmd(p, "reconnect_dead")
        reconnected += r["reconnected"]
        assert r["failed"] == 0, r

    async def disp_fetch() -> dict:
        return {"health": disp._health(), "metrics": {}}

    async def game_fetch() -> dict:
        return {"health": game._health(), "metrics": {}}

    async def gate_fetch() -> dict:
        return {"health": gates[0]._health(), "metrics": {}}

    coll = ClusterCollector(
        [("dispatcher1", disp_fetch), ("game1", game_fetch),
         ("gate1", gate_fetch)], interval=0.05)
    deadline = time.monotonic() + 60
    last = None
    converged = None
    while time.monotonic() < deadline:
        await coll.poll_once()
        summary = coll.view()["summary"]
        census = summary["census"]
        r = await fleets_report(procs)
        if (summary["reporting"] == summary["expected"]
                and not summary["alerts"]
                and census["clients_conserved"]
                and census["gate_clients"] == n_bots
                and r["players"] == n_bots):
            converged = time.monotonic() - t0
            break
        last = summary
        await asyncio.sleep(0.2)
    if converged is None:
        raise AssertionError(
            f"massive reconnect storm never converged: {last}")
    # Post-storm movement: reconnected clients must decode cleanly
    # (keyframes first — the forced-keyframe rule under test).
    await asyncio.sleep(max(1.0, 10 * c["sync_interval"]))
    r = await fleets_report(procs)
    return {
        "reconnected": reconnected,
        "converge_s": round(converged, 3),
        "bot_errors": r["errors"] - errors_before,
        "census_clients": n_bots,
    }
