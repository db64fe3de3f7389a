"""The harness end to end on the CPU at a tiny size, the Pallas kernels in
interpret mode: set-up, the closed-loop window, the check against the
reference, the metric readers and the result line. Also: the command
refuses a machine without a TPU."""

import json
import os
import shutil
import subprocess
import sys
import time

import jax
import pytest

from benchmark import harness
from conftest import ROOT, TINY_CELLS

SEED = 2**31 + 12345  # seeds may pass 32 signed bits


def run_tiny(bench, workload, seconds=1.5, traced=False,
             backend="pallas_interpret"):
    return harness.run(workload, SEED, seconds, traced, time.perf_counter(),
                       bench_path=bench, backend=backend, need_tpu=False)


def check_result(r, names):
    assert list(r)[-1] == "checks"
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 2
    assert set(r["metrics"]) == set(names)
    for m in r["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert {c["limit"] for c in r["checks"].values()} == {0}
    assert r["device"]["platform"] == "cpu"
    json.dumps(r)


@pytest.mark.parametrize("workload", [c[0] for c in TINY_CELLS])
def test_run_end_to_end(tiny, workload):
    check_result(run_tiny(tiny, workload), ["entity_updates_per_s",
                                            "event_latency_p90_ms", "setup_s"])


def cpu_load(path):
    """The CPU trace as the reduction's format: the XLA ops of the CPU
    client's threads stand in for a device's op line."""
    data = jax.profiler.ProfileData.from_file(path)
    ops, host = [], []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                ev = (float(e.start_ns), float(e.duration_ns))
                if e.name.startswith("bench."):
                    host.append((e.name, *ev))
                elif "CpuClient" in line.name and any(
                        k == "hlo_op" for k, _ in e.stats):
                    ops.append((*harness.trace_mod.kind_of(e.name), *ev))
    return {"devices": {0: ops}, "host": host}


def test_traced_run_reads_per_layer_metrics(tiny, monkeypatch):
    monkeypatch.setattr(harness.trace_mod, "load", cpu_load)
    monkeypatch.setattr(harness, "peaks_for", lambda kind: {
        "flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    # Every reader runs; the event kernel's two find nothing to read under
    # the jnp backend, which has no kernel.
    r = run_tiny(tiny, "tiny_4chip.walk", backend="jnp", traced=True)
    assert r["correct"] is True
    assert {"tick_median_ms", "dispatch_ms", "collect_ms", "pages_per_tick",
            "device_idle_share", "xla_ops_ms", "halo_collective_ms",
            "fallback_tick_share"} <= set(r["metrics"])
    assert 0 < r["metrics"]["device_idle_share"]["value"] < 100
    assert r["metrics"]["fallback_tick_share"]["value"] == 0
    assert 0 < r["device"]["busy_s"] <= r["device"]["window_s"]
    assert r["breakdown"]["device_ops"] and r["breakdown"]["idle_gaps"]


def command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "open_world_100k.walk", "--seed", "5", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_command_refuses_a_machine_without_tpu():
    r = command(ROOT)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_command_fails_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark")
    r = command(str(tmp_path))
    assert r.returncode != 0 and r.stdout.strip() == ""
