"""The stage reduction (``benchmark/stages.py``) on a synthetic trace whose
answers are known, and on samples recorded from real v5e runs."""

import json
import os

import pytest

from benchmark import stages, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SCOPES = {"aoi.table", "aoi.feats", "aoi.guard", "aoi.gather", "aoi.drain",
          "aoi.pack"}


def synthetic():
    # Window 0-100 ns. The dispatch (0-9) holds the engine's upload (1-3)
    # and launch (3-8); the collect (40-90) its wait (40-85) and readback
    # (85-89). Chip 0 runs a drain loop (10-40) holding the kernel (12-20)
    # and a table fusion (25-30), a halo collective (50-60) and a pack op
    # straddling the window's end (90-120); chip 1 runs one gather op.
    host = [("bench.window", 0.0, 100.0), ("bench.dispatch", 0.0, 9.0),
            ("aoi.upload", 1.0, 2.0), ("aoi.launch", 3.0, 5.0),
            ("bench.collect", 40.0, 50.0), ("aoi.wait", 40.0, 45.0),
            ("aoi.readback", 85.0, 4.0)]
    chip0 = [trace.kind_of(name) + (s, d, st) for name, s, d, st in [
        ("%while.3 = (s32[]) while(%t)", 10.0, 30.0, "aoi.drain"),
        ('%aoi_event_kernel.1 = s32[4] custom-call(%a), custom_call_target='
         '"tpu_custom_call"', 12.0, 8.0, "unscoped"),
        ("%fusion.7 = s32[4] fusion(%b), kind=kLoop", 25.0, 5.0, "aoi.table"),
        ("%collective-permute-done.1 = f32[4] collective-permute-done(%c)",
         50.0, 10.0, "aoi.halo"),
        ("%fusion.9 = s32[4] fusion(%d)", 90.0, 30.0, "aoi.pack")]]
    return {"devices": {0: chip0,
                        1: [("%fusion.2", "other", 0.0, 5.0, "aoi.gather")]},
            "host": host}


def plain_chips(t):
    """``trace.reduce``'s chip numbers of a trace, stages left out."""
    return trace.reduce({"devices": {c: [e[:4] for e in ev]
                                     for c, ev in t["devices"].items()},
                         "host": t["host"]})["chips"]


def without_stages(chips):
    return {chip: {k: v for k, v in c.items() if k != "stages"}
            for chip, c in chips.items()}


def test_reduce_synthetic():
    t = synthetic()
    r = stages.reduce(t)
    assert without_stages(r["chips"]) == plain_chips(t)
    st = r["chips"][0]["stages"]
    # The loop's self time (30 - 8 - 5) is the drain's; the pack op is cut
    # at the window's end.
    assert st == pytest.approx({"aoi.drain": 17e-9, "kernel": 8e-9,
                                "aoi.table": 5e-9, "collective": 10e-9,
                                "aoi.pack": 10e-9})
    c0 = r["chips"][0]
    assert sum(v for k, v in st.items() if k.startswith("aoi.")) == \
        pytest.approx(c0["other_s"])
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["aoi.drain/%while.3"] == pytest.approx(17e-9 / 2)
    assert ops["kernel/%aoi_event_kernel.1"] == pytest.approx(4e-9)
    # Chip 0 idles 0-10, 40-50, 60-90; chip 1 idles 5-100. Each piece
    # goes to the innermost span over it.
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert gaps == pytest.approx({
        "bench.dispatch": 3 / 2 * 1e-9, "aoi.upload": 2 / 2 * 1e-9,
        "aoi.launch": 8 / 2 * 1e-9, "host:other": 42 / 2 * 1e-9,
        "aoi.wait": 80 / 2 * 1e-9, "aoi.readback": 8 / 2 * 1e-9,
        "bench.collect": 2 / 2 * 1e-9})
    per = stages.per_tick_ms(t, r)
    assert per["ticks"] == 1
    assert per["host"]["aoi.wait"] == pytest.approx(45e-6)
    assert per["stages"]["aoi.drain"] == pytest.approx(17e-6 / 2)


def test_innermost_cuts_a_child_at_its_parent_end():
    segs = stages._innermost([("bench.collect", 0.0, 10.0),
                              ("aoi.wait", 2.0, 20.0),
                              ("bench.generate", 12.0, 3.0)])
    assert segs == [(0.0, 2.0, "bench.collect"), (2.0, 10.0, "aoi.wait"),
                    (12.0, 15.0, "bench.generate")]


def test_stage_of():
    assert stages.stage_of("jit(aoi_step)/cond/branch_1_fun/aoi.gather/"
                           "gather") == "aoi.gather"
    assert stages.stage_of("jit(aoi_step)/aoi_event_kernel") == "unscoped"


def recorded(name):
    with open(os.path.join(DATA, name)) as f:
        t = json.load(f)
    return {"devices": {int(k): [tuple(e) for e in v]
                        for k, v in t["devices"].items()},
            "host": [tuple(e) for e in t["host"]]}


def test_reduce_unscoped_recorded_trace_keeps_the_chip_numbers():
    """The roam sample predates the scopes: every op is unscoped or the
    kernel, and the chip numbers are those of ``trace.reduce``."""
    t = recorded("v5e_roam_trace.json")
    r = stages.reduce(t)
    c = r["chips"][0]
    assert without_stages(r["chips"]) == plain_chips(t)
    assert set(c["stages"]) == {"unscoped", "kernel"}
    assert c["stages"]["unscoped"] == pytest.approx(c["other_s"])
    assert c["stages"]["kernel"] == pytest.approx(c["kernel_s"])


def test_reduce_scoped_recorded_trace():
    """200 ms of a traced walk window (102,400 entities on one v5e) with
    the step's scopes: the stages cover every op, the six scopes take all
    but ~2% of the time outside the kernel, the drain leads, and the idle
    gaps fall inside the engine's wait."""
    t = recorded("v5e_walk_scoped_trace.json")
    r = stages.reduce(t)
    c = r["chips"][0]
    assert without_stages(r["chips"]) == plain_chips(t)
    st = c["stages"]
    assert sum(st.values()) == pytest.approx(c["other_s"] + c["kernel_s"])
    assert set(st) == SCOPES | {"kernel", "unscoped"}
    assert st["unscoped"] < 0.1 * c["other_s"]
    assert max(st, key=st.get) == "aoi.drain"
    ops = [name for name, _ in r["breakdown"]["device_ops"]]
    assert ops[0] == "kernel/%aoi_event_kernel.5"
    assert all(name.split("/")[0] in st for name in ops)
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert max(gaps, key=gaps.get) == "aoi.wait"
    assert sum(gaps.values()) == pytest.approx(c["window_s"] - c["busy_s"])
    per = stages.per_tick_ms(t, r)
    assert per["ticks"] == 4
    assert {"aoi.upload", "aoi.launch", "aoi.wait", "aoi.readback"} <= \
        set(per["host"])
