"""The benchmark's own tests run on the CPU: JAX on four virtual devices
(for the 4-chip path), Pallas kernels in interpret mode."""

import json
import os
import shutil
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


import pytest  # noqa: E402

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")
# A churn mix, so the CPU tests also drive the exact path and the meta
# uploads that no cell's traffic reaches yet.
CHURN = {"move_share": 1.0, "step": 10.0, "despawn_share": 0.01,
         "free_ticks": 2, "teleport_share": 0.01}
# (cell, configuration, traffic) of the tiny benchmark.
TINY_CELLS = [("tiny_1chip.walk", "tiny_1chip", "walk"),
              ("tiny_1chip.churn", "tiny_1chip", "churn"),
              ("tiny_4chip.walk", "tiny_4chip", "walk")]


@pytest.fixture(autouse=True, scope="session")
def _compile_cache(tmp_path_factory):
    """The CPU tests keep their compile cache out of the checkout's."""
    from benchmark import harness

    saved = harness.CACHE_DIR
    harness.CACHE_DIR = str(tmp_path_factory.mktemp("jax_cache"))
    yield
    harness.CACHE_DIR = saved


@pytest.fixture(scope="session")
def tiny(tmp_path_factory):
    """The path of a BENCHMARK.json that is the real one with its
    configurations and cells swapped for tiny ones. Its metrics are the
    real file's, each applied to every tiny cell, and every reader in
    ``benchmark/metrics`` the real file does not name is added, so each
    reader runs."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    d = tmp_path_factory.mktemp("tiny_bench")
    traffic = d / "bench" / "traffic"
    traffic.mkdir(parents=True)
    shutil.copy(os.path.join(ROOT, "benchmark", "traffic", "walk.json"),
                traffic)
    (traffic / "churn.json").write_text(json.dumps(CHURN))
    named = set()
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
        named.add(m["name"])
    for f in sorted(os.listdir(os.path.join(ROOT, "benchmark", "metrics"))):
        name = f[:-3]
        if f.endswith(".py") and name not in named:
            bench["per_layer"].append({"name": name, "unit": "-"})
    bench["paths"] = ["bench"]
    bench["configs"] = [{"name": c, "file": os.path.join(TINY, f"{c}.json")}
                        for c in sorted({c for _, c, _ in TINY_CELLS})]
    bench["workloads"] = [
        {"name": n, "config": c, "traffic": t,
         "chips": 4 if c == "tiny_4chip" else 1}
        for n, c, t in TINY_CELLS]
    path = d / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return str(path)
