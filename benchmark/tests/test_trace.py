"""The trace reduction on a synthetic trace whose answers are known, and
on a sample recorded from a real v5e run."""

import json
import os

import pytest

from benchmark import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def synthetic():
    # Window 0-100 ns on the host; chip 0 runs a loop op (10-40) holding a
    # kernel (12-20) and a fusion (25-30), then a collective (50-60) and
    # an op straddling the window's end (90-120); chip 1 idles.
    host = [("bench.window", 0.0, 100.0), ("bench.dispatch", 0.0, 9.0),
            ("bench.collect", 40.0, 50.0)]
    chip0 = [trace.kind_of(name) + (s, d) for name, s, d in [
        ("%while.3 = (s32[]) while(%t)", 10.0, 30.0),
        ('%branch_1_fun.1 = s32[4] custom-call(%a), custom_call_target='
         '"tpu_custom_call"', 12.0, 8.0),
        ("%fusion.7 = s32[4] fusion(%b), kind=kLoop", 25.0, 5.0),
        ("%collective-permute-done.1 = f32[4] collective-permute-done(%c)",
         50.0, 10.0),
        ("%fusion.9 = s32[4] fusion(%d)", 90.0, 30.0),
        ("%fusion.1 = s32[4] fusion(%e)", -20.0, 10.0)]]
    return {"devices": {0: chip0, 1: [("%fusion.2", "other", 0.0, 5.0)]},
            "host": host}


def test_reduce_synthetic():
    r = trace.reduce(synthetic())
    c0 = r["chips"][0]
    assert c0["window_s"] == pytest.approx(100e-9)
    assert c0["busy_s"] == pytest.approx((30 + 10 + 10) * 1e-9)
    assert c0["kernel_s"] == pytest.approx(8e-9)
    assert c0["collective_s"] == pytest.approx(10e-9)
    # The loop op's self time (30 - 8 - 5) plus both fusions.
    assert c0["other_s"] == pytest.approx((17 + 5 + 10) * 1e-9)
    assert r["chips"][1]["busy_s"] == pytest.approx(5e-9)
    gaps = dict(r["breakdown"]["idle_gaps"])
    # Chip 0 idles 0-10 (9 in dispatch), 40-50 and 60-90 (in collect);
    # chip 1 idles 5-100: 5-9 dispatch, 40-90 collect, the rest other.
    assert gaps["bench.dispatch"] == pytest.approx((9 + 4) / 2 * 1e-9)
    assert gaps["bench.collect"] == pytest.approx((40 + 50) / 2 * 1e-9)
    assert gaps["host:other"] == pytest.approx((1 + 41) / 2 * 1e-9)
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["%branch_1_fun.1"] == pytest.approx(4e-9)


def test_kind_of():
    assert trace.kind_of("%all-reduce.5 = f32[] all-reduce(%x)") == (
        "%all-reduce.5", "collective")
    assert trace.kind_of("%fusion.2 = f32[] fusion(%all-reduce.5)") == (
        "%fusion.2", "other")


def test_reduce_needs_a_window():
    t = synthetic()
    t["host"] = t["host"][1:]
    with pytest.raises(ValueError):
        trace.reduce(t)


def recorded():
    with open(os.path.join(DATA, "v5e_roam_trace.json")) as f:
        t = json.load(f)
    return {"devices": {int(k): [tuple(e) for e in v]
                        for k, v in t["devices"].items()},
            "host": [tuple(e) for e in t["host"]]}


def test_reduce_recorded_v5e_trace():
    """200 ms of a real traced window (102,400 entities, a quarter of
    them stepping, grid 44, on one v5e):
    the device is busy but for ~55 us, four launches of the event kernel,
    the bsearch drain's two fusions on top, no collective."""
    t = recorded()
    r = trace.reduce(t)
    c = r["chips"][0]
    assert r["window_s"] == pytest.approx(0.2)
    assert c["busy_s"] == pytest.approx(0.199945587)
    assert c["kernel_s"] == pytest.approx(0.013878984)
    assert c["collective_s"] == 0.0
    assert c["kernel_s"] + c["other_s"] == pytest.approx(c["busy_s"], rel=1e-6)
    assert sum(e[1] == "kernel" for e in t["devices"][0]) == 4
    ops = [name for name, _ in r["breakdown"]["device_ops"]]
    assert ops[:3] == ["%fusion.239", "%fusion.241", "%branch_1_fun.1"]
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(c["window_s"] - c["busy_s"])
    assert max(gaps, key=gaps.get) == "bench.collect"
