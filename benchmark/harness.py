"""One run of one cell: set-up, the measured window, the check, the metrics.

Everything a cell needs is found by name from ``BENCHMARK.json``: the
workload names its configuration (a file of sizes and ``[aoi]`` keys) and
its traffic mix (``traffic/<name>.json``); each metric is read by
``metrics/<name>.py``, a module with ``read(run) -> float | None``.

The entry the window drives is the engine pair the game's
``BatchAOIService`` calls each tick, in its pipelined order: tick t+1 is
dispatched (``step_async``) before tick t is collected (``collect``), one
tick in flight, closed loop. The engine is built from the configuration's
``[aoi]`` keys through ``params_from_config``, with the arguments
``BatchAOIService._build_engine`` passes.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import logging
import os
import resource
import shutil
import statistics
import sys
import tempfile
import threading
import time

import numpy as np

from benchmark import reference, workcount
from benchmark import trace as trace_mod
from benchmark.world import World, load_json, load_traffic

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
WARM_TICKS = 3  # traffic ticks after the storm, before the window
CHECK_TICKS = 6  # window ticks compared with the reference, at most
# Entity-ticks the reference recomputes in a run (six ticks of 102,400
# entities, ~4 s on the host): larger worlds compare fewer ticks, at
# least 2, so the check stays shorter than the window.
CHECK_ENTITY_TICKS = 6 * 102_400
TRACE_SECONDS = 10.0  # longest traced window
FALLBACK_PREWARM_THREAD = "aoi-spatial-fallback"

clock = time.perf_counter


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# --- the cell ----------------------------------------------------------------


def load_cell(workload: str, bench_path: str | None = None) -> dict:
    """The workload's entry, its configuration and traffic, and the
    metrics that apply to it, all from ``BENCHMARK.json``. Files are
    found beside it: configurations by their ``file``, traffic mixes as
    ``<paths[0]>/traffic/<name>.json``."""
    bench_path = bench_path or os.path.join(ROOT, "BENCHMARK.json")
    bench = load_json(bench_path)
    root = os.path.dirname(os.path.abspath(bench_path))
    wl = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise KeyError(f"no workload {workload!r} in {bench_path}")
    entry = next(c for c in bench["configs"] if c["name"] == wl["config"])

    def applies(m):
        return workload in m.get("workloads", [workload])

    return {
        "workload": wl,
        "config": load_json(os.path.join(root, entry["file"])),
        "traffic": load_traffic(wl["traffic"],
                                os.path.join(root, bench["paths"][0])),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def read_metric(name: str, run: dict):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


# --- the chip, the cache, the engine -------------------------------------------


def require_chips(n: int) -> list:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: jax.devices()[0] is {devs[0].platform!r}")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs


def configure_cache(enabled: bool = True) -> None:
    """JAX's persistent compilation cache at one fixed path inside the
    checkout, every program cached (none takes long to load).

    The window runs with it off: a program that compiles inside the
    window (the single-chip pager slices each page's device array to its
    data-dependent length, one new program per length) would otherwise
    load more of those programs from disk on each later run in the same
    checkout, and the cell's numbers would drift from run to run."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", enabled)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # No eviction: it needs a side file per entry, and an entry without
    # one (written by a process that did not evict) fails every write.
    jax.config.update("jax_compilation_cache_max_size", -1)
    compilation_cache.reset_cache()


class CompileLog(logging.Handler):
    """Backend compile seconds, and the modules that hit or missed the
    persistent cache, while installed."""

    def __init__(self) -> None:
        super().__init__(logging.DEBUG)
        self.compile_s = 0.0
        self.compiled: list = []  # program names, in compile order
        self.hits: list = []
        self.misses: list = []

    def emit(self, record: logging.LogRecord) -> None:
        if record.levelno >= logging.WARNING:
            log(f"{record.name}: {record.getMessage()}")
        msg = record.getMessage()
        name = msg.split("'")[1] if "'" in msg else msg
        if msg.startswith("Persistent compilation cache hit"):
            self.hits.append(name)
        elif msg.startswith("PERSISTENT COMPILATION CACHE MISS"):
            self.misses.append(name)

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiled.append(kw.get("fun_name", "?"))

    def __enter__(self):
        import jax

        lg = logging.getLogger("jax._src.compiler")
        self._saved = (lg.level, lg.propagate)
        lg.setLevel(logging.DEBUG)
        lg.propagate = False  # the debug records stay here
        lg.addHandler(self)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        return self

    def __exit__(self, *exc) -> None:
        import jax

        lg = logging.getLogger("jax._src.compiler")
        lg.removeHandler(self)
        lg.setLevel(self._saved[0])
        lg.propagate = self._saved[1]
        jax.monitoring.unregister_event_duration_listener(self._on_duration)


def build_engine(config: dict, backend: str | None = None):
    """The engine a game builds from this ``[aoi]`` section: params by
    ``params_from_config``, engine as ``BatchAOIService._build_engine``.
    ``backend`` (tests only) replaces the engine's "auto" resolution."""
    from goworld_tpu.config.read_config import AOIConfig
    from goworld_tpu.entity.aoi.batched import params_from_config

    aoi = AOIConfig(**config["aoi"])
    params = params_from_config(aoi)
    kw = {"backend": backend} if backend else {}
    if aoi.mesh_shards > 1:
        from goworld_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(aoi.mesh_shards)
        if aoi.shard_mode == "spatial":
            from goworld_tpu.parallel.spatial import (
                SpatialShardedNeighborEngine,
            )

            return SpatialShardedNeighborEngine(
                params, mesh, strip_cols=aoi.pallas_strip_cols or None,
                placement=aoi.strip_placement,
                inkernel_drain=aoi.pallas_inkernel_drain, **kw)
        from goworld_tpu.parallel.mesh import ShardedNeighborEngine

        return ShardedNeighborEngine(params, mesh, **kw)
    from goworld_tpu.ops.neighbor import NeighborEngine

    return NeighborEngine(params, **kw)


def peaks_for(kind: str) -> dict:
    """The chip's published peaks (``peaks.json``); a kind missing there
    is an error, never a default."""
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return peaks[kind]


def program_counters(engine) -> dict:
    """The program's own counters: drain launches (the pages), fallback
    ticks, steady-state retraces."""
    from goworld_tpu.telemetry import sentinel
    from goworld_tpu.telemetry.metrics import REGISTRY

    fam = REGISTRY.family("jit_launches_total")
    drains = sum(c.value for labels, c in fam.children()
                 if "drain" in labels[0]) if fam is not None else 0.0
    return {"drain_launches": drains,
            "fallbacks": getattr(engine, "total_fallbacks", 0),
            "retraces": sentinel.steady_state_retraces()}


# --- the tick loop -------------------------------------------------------------


def drive(engine, world: World, stop, annotate) -> tuple[list, float, float]:
    """Closed loop, one tick in flight: dispatch tick t+1, then collect
    tick t, then make tick t+2's inputs while t+1 computes. ``stop(n,
    elapsed)`` is asked after each collect. Returns the ticks, the loop's
    start and the time it stopped (the last tick is collected after)."""
    ticks: list = []
    t0 = clock()
    pending = None
    ep = world.advance()
    gen_s = clock() - t0
    while True:
        rec = {"epoch": ep, "generate_s": gen_s, "t_in": clock()}
        with annotate("bench.dispatch"):
            pend = engine.step_async(*ep.arrays(), meta_dirty=ep.meta_dirty)
        rec["dispatch_s"] = clock() - rec["t_in"]
        rec["mode"] = getattr(engine, "last_mode", "single")
        if pending is not None:
            _collect(*pending, annotate)
        pending = (rec, pend)
        ticks.append(rec)
        if stop(len(ticks), clock() - t0):
            break
        g0 = clock()
        with annotate("bench.generate"):
            ep = world.advance()
        gen_s = clock() - g0
    t_close = clock()
    _collect(*pending, annotate)
    return ticks, t0, t_close


def slowest_periods(ticks: list, n: int = 3) -> str:
    """The ``n`` longest gaps between two dispatches, each split into the
    tick's dispatch, the collect of the tick before and the making of the
    next tick's inputs (ms)."""
    gaps = [(ticks[i + 1]["t_in"] - t["t_in"], i)
            for i, t in enumerate(ticks[:-1])]
    out = []
    for gap, i in sorted(gaps, reverse=True)[:n]:
        coll = ticks[i - 1]["collect_s"] if i else 0.0
        out.append(f"#{i} {gap * 1e3:.1f} (dispatch "
                   f"{ticks[i]['dispatch_s'] * 1e3:.1f}, collect "
                   f"{coll * 1e3:.1f}, generate "
                   f"{ticks[i + 1]['generate_s'] * 1e3:.1f})")
    return ", ".join(out)


def _collect(rec: dict, pend, annotate) -> None:
    c0 = clock()
    with annotate("bench.collect"):
        enters, leaves, dropped = pend.collect()
    t = clock()
    rec.update(collect_s=t - c0, t_out=t, latency_s=t - rec["t_in"],
               enters=enters, leaves=leaves, dropped=int(dropped),
               active=int(np.count_nonzero(rec["epoch"].active)))


def _no_annotation(name: str):
    return contextlib.nullcontext()


def establish(engine, cell: dict, seed: int) -> tuple[World, list]:
    """Reset the engine, make the seed's world, and bring the engine to
    steady state as a game would: the enter storm (every entity enters;
    paged), then ``WARM_TICKS`` traffic ticks, which also compile or load
    each program the window runs. Returns the world and the warm ticks."""
    engine.reset()
    world = World(cell["config"], cell["traffic"], engine.params.capacity,
                  seed)
    s0 = clock()
    e, lv, d = engine.step_async(*world.epoch().arrays(),
                                 meta_dirty=True).collect()
    log(f"setup: storm tick {len(e)} enters, {len(lv)} leaves, dropped "
        f"{d}, {clock() - s0:.3f} s")
    warm, _, _ = drive(engine, world, lambda n, _: n >= WARM_TICKS,
                       _no_annotation)
    for t in threading.enumerate():
        if t.name == FALLBACK_PREWARM_THREAD:
            t.join()
    return world, warm


# --- the check -------------------------------------------------------------------


def sample(ticks: list, seed: int, entities: int) -> list:
    """The window ticks the check compares, drawn from the seed: the tick
    with most events, every fallback tick (up to 2), then others up to
    ``CHECK_ENTITY_TICKS / entities`` (2 to ``CHECK_TICKS``) in all."""
    n = len(ticks)
    want = min(CHECK_TICKS, max(2, round(CHECK_ENTITY_TICKS / entities)))
    counts = [len(t["enters"]) + len(t["leaves"]) for t in ticks]
    pick = [int(np.argmax(counts))]
    fb = [i for i, t in enumerate(ticks) if t["mode"].startswith("fallback")]
    for i in fb[:2]:
        if i not in pick:
            pick.append(i)
    rng = np.random.default_rng([seed, 1])
    for i in rng.permutation(n):
        if len(pick) >= min(want, n):
            break
        if int(i) not in pick:
            pick.append(int(i))
    return sorted(pick)


def check(ticks: list, before, capacity: int, pick: list,
          keys: dict | None = None) -> dict:
    """Compare the ticks ``pick`` with the reference (float32, as the
    configuration states), and every tick's ``dropped``. ``before`` is the
    epoch the first window tick diffed against; ``keys`` may carry the
    reference's pairs per epoch from an earlier check of the same ticks."""
    keys = {} if keys is None else keys

    def valid(i):
        if i not in keys:
            ep = before if i < 0 else ticks[i]["epoch"]
            keys[i] = reference.interest_keys(ep.pos, ep.active, ep.space,
                                              ep.radius)
        return keys[i]

    missing = extra = pairs = 0
    failed = set()
    t0 = clock()
    for i in pick:
        want_e, want_l = reference.events(valid(i - 1), valid(i))
        t = ticks[i]
        m1, x1 = reference.mismatch(reference.pair_keys(t["enters"], capacity),
                                    want_e)
        m2, x2 = reference.mismatch(reference.pair_keys(t["leaves"], capacity),
                                    want_l)
        missing += m1 + m2
        extra += x1 + x2
        pairs += len(want_e) + len(want_l)
        if m1 + m2 + x1 + x2:
            failed.add(i)
    dropped = sum(t["dropped"] for t in ticks)
    failed |= {i for i, t in enumerate(ticks) if t["dropped"]}
    return {"missing_pairs": missing, "extra_pairs": extra,
            "dropped_entities": dropped, "failed": len(failed),
            "compared_ticks": pick, "compared_pairs": pairs,
            "modes": sorted({ticks[i]["mode"] for i in pick}),
            "reference_s": clock() - t0}


# Both pair counts are exact comparisons; "no active entity is hidden"
# (dropped 0) is a guarantee the configurations state.
LIMITS = {"missing_pairs": 0, "extra_pairs": 0, "dropped_entities": 0}


# --- one run ---------------------------------------------------------------------


def run(workload: str, seed: int, seconds: float, traced: bool,
        process_start: float, bench_path: str | None = None,
        backend: str | None = None, need_tpu: bool = True,
        trace_dir: str | None = None) -> dict:
    """One run of one cell; returns the result line's object. ``backend``
    and ``need_tpu=False`` are for the CPU tests only."""
    cell = load_cell(workload, bench_path)
    wl, cfg = cell["workload"], cell["config"]
    import jax

    devices = require_chips(wl["chips"]) if need_tpu else jax.devices()
    configure_cache()
    with CompileLog() as clog:
        engine = build_engine(cfg, backend)
        params = engine.params
        world, warm = establish(engine, cell, seed)
        setup_compile = (clog.compile_s, len(clog.compiled))
        before = warm[-1]["epoch"]
        counters0 = program_counters(engine)
        setup_s = clock() - process_start
        log(f"setup: {setup_s:.3f} s; compiled {setup_compile[1]} programs "
            f"in {setup_compile[0]:.3f} s; persistent cache hits "
            f"{len(clog.hits)} {sorted(set(clog.hits))}, misses "
            f"{len(clog.misses)} {sorted(set(clog.misses))}; warm ticks "
            + ", ".join(f"{t['latency_s']:.4f}" for t in warm))
        window_s = min(seconds, TRACE_SECONDS) if traced else seconds
        tdir = None
        if traced:
            tdir = trace_dir or tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
            annotate = jax.profiler.TraceAnnotation
        else:
            annotate = _no_annotation
        configure_cache(enabled=False)
        ru0, load0 = resource.getrusage(resource.RUSAGE_SELF), os.getloadavg()
        gc0 = gc.get_stats()[2]["collections"]
        with annotate(trace_mod.WINDOW):
            ticks, t_start, t_close = drive(
                engine, world, lambda n, el: el >= window_s, annotate)
        ru1, load1 = resource.getrusage(resource.RUSAGE_SELF), os.getloadavg()
        gc1 = gc.get_stats()[2]["collections"]
        if traced:
            jax.profiler.stop_trace()
        configure_cache()
        counters1 = program_counters(engine)
        new = clog.compiled[setup_compile[1]:]
        window_compiles = (len(new), clog.compile_s - setup_compile[0],
                           sorted(set(new)))
    used = devices[:wl["chips"]] if need_tpu else devices[:1]
    stats = [d.memory_stats() or {} for d in used]
    memory_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    lat = [t["latency_s"] for t in ticks]
    in_window = [t for t in ticks if t["t_out"] <= t_close]
    log(f"window: {len(ticks)} ticks dispatched, {len(in_window)} collected "
        f"in {t_close - t_start:.3f} s; latency median "
        f"{statistics.median(lat) * 1e3:.3f} ms, p90 "
        f"{np.percentile(lat, 90) * 1e3:.3f} ms over {len(lat)} ticks; "
        f"events/tick median "
        f"{statistics.median(len(t['enters']) for t in ticks)} enters, "
        f"{statistics.median(len(t['leaves']) for t in ticks)} leaves; modes "
        f"{sorted({t['mode'] for t in ticks})}")
    log(f"window: longest tick periods {slowest_periods(ticks)}; host "
        f"cpu {ru1.ru_utime + ru1.ru_stime - ru0.ru_utime - ru0.ru_stime:.3f}"
        f" s, involuntary switches {ru1.ru_nivcsw - ru0.ru_nivcsw}, major "
        f"faults {ru1.ru_majflt - ru0.ru_majflt}, full collections "
        f"{gc1 - gc0}, load {load0[0]:.2f} -> {load1[0]:.2f}")
    log(f"window: steady-state retraces {counters0['retraces']:.0f} -> "
        f"{counters1['retraces']:.0f}; backend compiles in window "
        f"{window_compiles[0]} ({window_compiles[1]:.3f} s, "
        f"{window_compiles[2]}); drain launches "
        f"{counters1['drain_launches'] - counters0['drain_launches']:.0f}; "
        f"fallback ticks {counters1['fallbacks'] - counters0['fallbacks']}")
    capacity = params.capacity
    kind = used[0].device_kind
    reduced = None
    if traced:
        reduced = trace_mod.reduce(trace_mod.load(trace_mod.find_xplane(tdir)))
        if trace_dir is None:
            shutil.rmtree(tdir, ignore_errors=True)
        for chip, c in sorted(reduced["chips"].items()):
            log(f"trace: chip {chip}: " + ", ".join(
                f"{k} {v:.6f}" for k, v in c.items()))
    # The program's state goes before the reference runs.
    engine = world = None
    gc.collect()
    run_rec = {
        "workload": wl, "config": cfg, "chips": wl["chips"],
        "params": {"capacity": capacity, "max_events": params.max_events,
                   "cell_size": params.cell_size},
        "setup_s": setup_s, "ticks": ticks,
        "window": {"start": t_start, "close": t_close,
                   "seconds": t_close - t_start},
        "counters": {k: counters1[k] - counters0[k] for k in counters1},
        "trace": reduced, "device_kind": kind,
        "peaks": peaks_for(kind) if traced else None,
    }
    if traced:
        prev = before
        work = []
        for t in ticks:
            ep = t["epoch"]
            work.append(workcount.tick_work(
                (prev.pos, prev.active, prev.space),
                (ep.pos, ep.active, ep.space), params.cell_size))
            prev = ep
        run_rec["work"] = work
    chk = check(ticks, before, capacity,
                sample(ticks, seed, cfg["entities"]))
    wanted = cell["per_layer"] if traced else cell["end_to_end"]
    metrics = {}
    for m in wanted:
        v = read_metric(m["name"], run_rec)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": used[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    result = {"correct": all(chk[k] <= lim for k, lim in LIMITS.items()),
              "attempted": len(ticks), "failed": chk["failed"],
              "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = trace_mod.mean(reduced["chips"], "busy_s")
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = reduced["breakdown"]
    log(f"check: compared ticks {chk['compared_ticks']} ({chk['modes']}), "
        f"{chk['compared_pairs']} reference pairs, reference "
        f"{chk['reference_s']:.3f} s")
    result["checks"] = {k: {"value": chk[k], "limit": lim}
                        for k, lim in LIMITS.items()}
    for k, lim in LIMITS.items():
        log(f"check {k} = {chk[k]} (limit {lim})")
    return result
