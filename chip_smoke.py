#!/usr/bin/env python3
"""Prove, on one TPU chip, that the AOI engine and the served cluster run
there and agree with the plain reference.

    python chip_smoke.py             # engine phase + served phase, one chip
    python chip_smoke.py --chips 4   # the 4-chip spatial engine only

Every phase prints one JSON line of its own; the last stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failure exits non-zero with the reason on stderr and prints no result.

One process per chip: this parent never imports JAX. Each chip phase runs
in a child of its own (``--phase engine|chips4``), one after the other, and
fails before any work when ``jax.devices()[0]`` is not a TPU. The served
phase's only chip holder is the cluster's ``game1``.

Phases:

- engine: ``NeighborEngine`` (backend ``auto`` must resolve to ``pallas``)
  at the ``__graft_entry__`` headline size, 102,400 slots over 4 spaces,
  through an enter storm (paged), steady ticks (the dual-launch fast path)
  and a despawn + teleport tick (the exact two-launch path). Each tick's
  sorted enter/leave pairs must equal the ``jnp`` engine's on the CPU
  device, with ``dropped == 0``.
- served: the reference CI's deployment (``TRAVIS_INI`` of
  tests/test_stress.py: 3 dispatchers x 3 games x 3 gates, TLS and
  compression on) started through the ops CLI, ``game1`` on the chip,
  games 2-3 on the CPU, 200 strict bots for two runs across one reload.
- chips4: ``SpatialShardedNeighborEngine`` (Pallas, in-kernel drain on)
  over the 4 chips of the host, tick by tick equal to the single-chip
  engine on device 0.

Times printed are information, not metrics.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

# The __graft_entry__.entry() headline config.
HEADLINE = dict(capacity=102400, cell_size=300.0, grid_x=44, grid_z=44,
                space_slots=4, cell_capacity=128, max_events=262144)
N_SPACES = 4
RADIUS = 100.0
WALK = 3.0  # random-walk step (units per tick, per axis sigma)
N_STEADY = 5
SEED = 0
CHURN = 0.01  # share of entities despawned, and teleported, on the last tick
# Active share of the slots: the 25% row slack goworld.ini.sample asks of
# [aoi] max_entities, without which spatial strips overflow their rows.
POPULATION = 0.8
BOTS = 200
BOT_SECONDS = 60.0  # per run; the CI runs 2 x 300 s


class SmokeError(RuntimeError):
    pass


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


# --- the seeded world --------------------------------------------------------


class World:
    """``POPULATION`` of the slots active, uniform over the world in
    ``N_SPACES`` spaces, moved by a seeded random walk clipped to the world
    (no torus wrap, so steady displacements stay a few units)."""

    def __init__(self, params):
        n = params.capacity
        self.rng = np.random.default_rng(SEED)
        self.extent = np.array([params.grid_x, params.grid_z],
                               np.float32) * params.cell_size
        self.pos = (self.rng.random((n, 2)) * self.extent).astype(np.float32)
        self.active = np.arange(n) < int(n * POPULATION)
        self.space = (np.arange(n) % N_SPACES).astype(np.int32)
        self.radius = np.full(n, RADIUS, np.float32)

    def inputs(self) -> tuple:
        return (self.pos.copy(), self.active.copy(), self.space.copy(),
                self.radius.copy())

    def step(self) -> None:
        moved = self.pos + self.rng.normal(0, WALK, self.pos.shape)
        self.pos = np.clip(moved, 0, self.extent * (1 - 1e-6)).astype(
            np.float32)

    def churn(self) -> None:
        live = np.flatnonzero(self.active)
        k = max(1, int(len(live) * CHURN))
        idx = self.rng.permutation(live)
        self.active[idx[:k]] = False
        self.pos[idx[k:2 * k]] = (
            self.rng.random((k, 2)) * self.extent).astype(np.float32)


def schedule(world: World, n_steady: int = N_STEADY):
    """(label, inputs) per tick: the enter storm, ``n_steady`` walk ticks,
    then one despawn + teleport tick."""
    yield "storm", world.inputs()
    for _ in range(n_steady):
        world.step()
        yield "steady", world.inputs()
    world.step()
    world.churn()
    yield "despawn_teleport", world.inputs()


def canon(pairs) -> np.ndarray:
    a = np.asarray(pairs).reshape(-1, 2)
    return a[np.lexsort((a[:, 1], a[:, 0]))]


def check_tick(tick: int, label: str, got, want) -> None:
    (e, l, d), (re, rl, rd) = got, want
    for name, a, b in (("enters", e, re), ("leaves", l, rl)):
        if not np.array_equal(canon(a), canon(b)):
            raise SmokeError(
                f"tick {tick} ({label}): {name} differ from the reference "
                f"({len(a)} vs {len(b)} pairs)")
    if d or rd:
        raise SmokeError(f"tick {tick} ({label}): dropped {d} (reference "
                         f"{rd}); the headline world must drop nothing")


# --- chip phases (run in a child; importable for the CPU rehearsal) -----------


@contextlib.contextmanager
def compile_seconds():
    """A running sum ([0]) of XLA backend-compile seconds in this process
    while the block runs."""
    import jax

    total = [0.0]

    def on(event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            total[0] += secs

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        yield total
    finally:
        jax.monitoring.unregister_event_duration_listener(on)


def engine_phase(params, backend: str = "auto",
                 n_steady: int = N_STEADY) -> dict:
    """The single-chip engine against the ``jnp`` engine on the CPU
    device, tick by tick."""
    import jax

    from goworld_tpu.ops import NeighborEngine

    eng = NeighborEngine(params, backend=backend)
    want = "pallas" if backend == "auto" else backend
    if eng.backend != want:
        raise SmokeError(f"backend {backend!r} resolved to {eng.backend!r}, "
                         f"not {want!r}")
    ref_device = jax.devices("cpu")[0]
    with jax.default_device(ref_device):
        ref = NeighborEngine(params, backend="jnp")
        ref.reset()
    eng.reset()
    ticks = []
    with compile_seconds() as compile_s:
        for t, (label, inputs) in enumerate(
                schedule(World(params), n_steady)):
            c0, t0 = compile_s[0], time.perf_counter()
            got = eng.step(*inputs)
            wall = time.perf_counter() - t0
            r0 = time.perf_counter()
            with jax.default_device(ref_device):
                want_ev = ref.step(*inputs)
            check_tick(t, label, got, want_ev)
            ticks.append({"tick": t, "kind": label, "enters": len(got[0]),
                          "leaves": len(got[1]), "dropped": got[2],
                          "wall_s": wall, "compile_s": compile_s[0] - c0,
                          "reference_s": time.perf_counter() - r0})
    return {"phase": "engine", "ok": True, "backend": eng.backend,
            "capacity": params.capacity,
            "active": int(params.capacity * POPULATION), "spaces": N_SPACES,
            "compared": "every tick, all pairs, vs jnp on "
                        f"{ref_device.platform}",
            "compile_s": compile_s[0], "ticks": ticks}


def chips4_phase(params, devices, backend: str = "pallas",
                 n_steady: int = N_STEADY,
                 prewarm_fallback: bool = True) -> dict:
    """The spatially sharded engine over ``devices`` (Pallas, in-kernel
    drain on) against the single-device engine on ``devices[0]``."""
    import jax

    from goworld_tpu import telemetry
    from goworld_tpu.ops import NeighborEngine
    from goworld_tpu.parallel import make_mesh
    from goworld_tpu.parallel.spatial import SpatialShardedNeighborEngine

    n_dev = len(devices)
    spatial = SpatialShardedNeighborEngine(
        params, make_mesh(devices=list(devices)), backend=backend,
        inkernel_drain=True, prewarm_fallback=prewarm_fallback)
    if spatial.drain_inline <= 0:
        raise SmokeError("the in-kernel drain is off")
    with jax.default_device(devices[0]):
        single = NeighborEngine(params, backend=backend)
        single.reset()
    spatial.reset()
    ticks = []
    with compile_seconds() as compile_s:
        for t, (label, inputs) in enumerate(
                schedule(World(params), n_steady)):
            c0, t0 = compile_s[0], time.perf_counter()
            got = spatial.step(*inputs)
            wall = time.perf_counter() - t0
            with jax.default_device(devices[0]):
                want = single.step(*inputs)
            check_tick(t, label, got, want)
            ticks.append({"tick": t, "kind": label, "enters": len(got[0]),
                          "leaves": len(got[1]), "mode": spatial.last_mode,
                          "fast": spatial.last_fast_tick, "wall_s": wall,
                          "compile_s": compile_s[0] - c0})
    for i, arr in enumerate(spatial._state):
        held = {shard.device for shard in arr.addressable_shards}
        if len(held) != n_dev:
            raise SmokeError(f"state array {i} sits on {len(held)} devices, "
                             f"not {n_dev}")
    halo = telemetry.family("aoi_link_bytes_total")
    halo_bytes = sum(c.value for labels, c in halo.children()
                     if labels[0] == "halo")
    return {"phase": f"chips{n_dev}", "ok": True, "backend": backend,
            "drain_inline": spatial.drain_inline,
            "capacity": params.capacity,
            "compared": f"every tick, all pairs, vs NeighborEngine on "
                        f"{devices[0].platform}:{devices[0].id}",
            "state_shards_on_distinct_devices": n_dev,
            "aoi_link_bytes_total_halo": halo_bytes,
            "fallback_ticks": spatial.total_fallbacks,
            "compile_s": compile_s[0], "ticks": ticks}


def run_child(phase: str) -> int:
    """One chip phase in this process: refuse a non-TPU device first."""
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"  # the reference's device
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SmokeError(f"no TPU: jax.devices()[0] is {dev.platform!r}")
    from goworld_tpu.game.service import apply_compilation_cache
    from goworld_tpu.ops import NeighborParams

    apply_compilation_cache("auto")
    params = NeighborParams(**HEADLINE)
    t0 = time.perf_counter()
    if phase == "engine":
        rec = engine_phase(params)
    else:
        tpus = jax.devices("tpu")
        if len(tpus) < 4:
            raise SmokeError(f"--chips 4 needs 4 TPU chips, found {len(tpus)}")
        rec = chips4_phase(params, tpus[:4])
    rec["seconds"] = time.perf_counter() - t0
    rec["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(jax.devices())}
    emit(rec)
    return 0


# --- the served phase (this process stays off JAX) -----------------------------


def _cli(run_dir: str, *args: str, timeout: float = 600.0) -> None:
    r = subprocess.run(
        [sys.executable, "-m", "goworld_tpu.cli", *args], cwd=run_dir,
        env=dict(os.environ, PYTHONPATH=REPO), capture_output=True,
        text=True, timeout=timeout)
    if r.returncode != 0:
        raise SmokeError(f"cli {' '.join(args)} failed (rc {r.returncode}):"
                         f"\n{r.stdout[-2000:]}\n{r.stderr[-2000:]}")


def _maps(run_dir: str, name: str) -> str:
    with open(os.path.join(run_dir, f"{name}.pid")) as f:
        pid = int(f.read().split()[0])
    with open(f"/proc/{pid}/maps") as f:
        return f.read()


def _aoi_engine(port: int) -> dict:
    import urllib.request

    with urllib.request.urlopen(f"http://127.0.0.1:{port}/vars",
                                timeout=30) as r:
        info = json.loads(r.read())["AOIEngine"]
    if not isinstance(info, dict):
        raise SmokeError(f"AOIEngine probe failed: {info}")
    return info


def _check_chip_holders(run_dir: str) -> None:
    """Dispatchers and gates never load JAX; only game1 loads libtpu."""
    for kind in ("dispatcher", "gate"):
        for i in (1, 2, 3):
            if "jaxlib" in _maps(run_dir, f"{kind}{i}"):
                raise SmokeError(f"{kind}{i} loaded JAX")
    for i in (1, 2, 3):
        has = "libtpu" in _maps(run_dir, f"game{i}")
        if has != (i == 1):
            raise SmokeError(f"game{i} libtpu loaded: {has}")


def served_phase() -> dict:
    import asyncio

    sys.path.insert(0, os.path.join(REPO, "tests"))
    from test_stress import TRAVIS_INI, free_port

    from goworld_tpu.client.bot_runner import format_report, run_fleet

    t_start = time.perf_counter()
    run_dir = os.path.join(REPO, "chiprun_out", "chip_smoke_served")
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", os.path.join(run_dir, "rsa.key"),
         "-out", os.path.join(run_dir, "rsa.crt"),
         "-days", "1", "-subj", "/CN=localhost"],
        check=True, capture_output=True)
    ports = {k: free_port() for k in ("disp1", "disp2", "disp3",
                                      "gate1", "gate2", "gate3")}
    http = {i: free_port() for i in (1, 2, 3)}
    ini = TRAVIS_INI.format(dir=run_dir, **ports)
    for i in (1, 2, 3):
        plat = "tpu" if i == 1 else "cpu"
        ini = ini.replace(f"[game{i}]\n", f"[game{i}]\naoi_platform = {plat}"
                          f"\nhttp_addr = 127.0.0.1:{http[i]}\n")
    ini += "\n[aoi]\nbackend = tpu\n"
    with open(os.path.join(run_dir, "goworld.ini"), "w") as f:
        f.write(ini)
    gates = [("127.0.0.1", ports[f"gate{i}"]) for i in (1, 2, 3)]

    def fleet(seed: int) -> dict:
        report = asyncio.run(run_fleet(
            BOTS, gates, BOT_SECONDS, strict=True, compress=True, tls=True,
            seed=seed, thing_timeout=20.0))
        if report["errors"]:
            raise SmokeError(f"bot run {seed} had errors:\n"
                             + format_report(report))
        return report

    started = False
    try:
        t0 = time.perf_counter()
        _cli(run_dir, "start", "examples.test_game")
        started = True
        start_s = time.perf_counter() - t0
        _check_chip_holders(run_dir)
        platforms = {f"game{i}": _aoi_engine(http[i])["platform"]
                     for i in (1, 2, 3)}
        if platforms["game1"] != "tpu":
            raise SmokeError(f"game1 runs its AOI on {platforms['game1']}")
        r1 = fleet(42)
        t0 = time.perf_counter()
        _cli(run_dir, "reload", "examples.test_game")
        reload_s = time.perf_counter() - t0
        _check_chip_holders(run_dir)
        restored = _aoi_engine(http[1])
        if (restored["platform"] != "tpu"
                or restored["compile_cache_hits"] <= 0):
            raise SmokeError(f"restored game1 compiled afresh: {restored}")
        r2 = fleet(43)
        game1 = _aoi_engine(http[1])
        if (game1["platform"] != "tpu"
                or game1["engine"] != "NeighborEngine"
                or game1["backend"] != "pallas"
                or game1["jit_launches"] <= 0
                or game1["steady_state_retraces"] != 0):
            raise SmokeError(f"game1 AOI engine after the runs: {game1}")
        _cli(run_dir, "stop", "examples.test_game")
        started = False
    finally:
        if started:
            subprocess.run(
                [sys.executable, "-m", "goworld_tpu.cli", "kill",
                 "examples.test_game"], cwd=run_dir,
                env=dict(os.environ, PYTHONPATH=REPO), capture_output=True,
                timeout=120)
    return {"phase": "served", "ok": True,
            "deployment": "3 dispatchers x 3 games x 3 gates, TLS + "
                          "compression (tests/test_stress.py TRAVIS_INI)",
            "platforms": platforms, "bots": BOTS,
            "cut": f"2 x {BOT_SECONDS:g} s strict runs across one reload "
                   f"(the CI runs 2 x 300 s)",
            "bot_errors": len(r1["errors"]) + len(r2["errors"]),
            "things_done": [sum(a["count"] for a in r["things"].values())
                            for r in (r1, r2)],
            "game1_after_reload": restored, "game1_final": game1,
            "start_s": start_s, "reload_s": reload_s,
            "seconds": time.perf_counter() - t_start}


# --- driver --------------------------------------------------------------------


def _run_phase_child(phase: str) -> dict:
    """Run one chip phase in a child, relay its lines, return its record."""
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--phase", phase],
        cwd=REPO, stdout=subprocess.PIPE, text=True)
    lines = r.stdout.strip().splitlines()
    for line in lines:
        print(line, flush=True)
    if r.returncode != 0 or not lines:
        raise SmokeError(f"{phase} phase failed (rc {r.returncode})")
    return json.loads(lines[-1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--phase", choices=("engine", "chips4"),
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    try:
        if args.phase:
            return run_child(args.phase)
        rec = _run_phase_child("chips4" if args.chips == 4 else "engine")
        device = rec["device"]
        if args.chips == 1:
            emit(served_phase())
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
