"""From a profiler trace to per-chip device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
what the reduction needs, as plain lists (also the format of the recorded
sample the tests read): every event of each TPU plane's "XLA Ops" line,
as its HLO op name and its kind, and the benchmark's own host spans
(``bench.*``, written with ``jax.profiler.TraceAnnotation``). The "Async
XLA Ops" line (copies overlapping compute) is left out.

On a v5e trace (jax 0.9) an op event's name is the op's whole HLO text,
``%<name> = <shape> <opcode>(<operands>), <attributes>``. The event
kernel is the step's only Mosaic custom call (``custom_call_target=
"tpu_custom_call"``); its op name comes from the ``cond`` branch it sits
in (``%branch_1_fun.1``), not from the kernel. Collectives are named
after their opcode (``%collective-permute-start.2``, ``%all-reduce.5``).

``reduce`` clips each chip's op events to the ``bench.window`` span and
returns, per chip: busy seconds (union of op intervals), and the self
time of the event kernel's ops, of collective ops and of all other ops.
Self time is an event's duration less that of the events nested in it on
the same line, so an op that wraps others (a loop) is not counted twice.
Each idle gap is split over the host spans it overlaps (the benchmark's
spans follow one another on one thread), the rest going to "host:other".
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

WINDOW = "bench.window"
HOST_PREFIX = "bench."
# Device planes of a TPU trace and the line that holds one event per op.
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
KERNEL = 'custom_call_target="tpu_custom_call"'
COLLECTIVE = re.compile(
    r"^%?(all-gather|all-reduce|all-to-all|collective-permute"
    r"|reduce-scatter|send|recv)\b")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def kind_of(hlo_text: str) -> tuple[str, str]:
    """(op name, "kernel" | "collective" | "other") of one op event."""
    name = hlo_text.split(" = ", 1)[0]
    if KERNEL in hlo_text:
        return name, "kernel"
    if COLLECTIVE.match(name):
        return name, "collective"
    return name, "other"


def load(path: str) -> dict:
    """{"devices": {chip: [(op name, kind, start_ns, dur_ns), ...]},
    "host": [(name, start_ns, dur_ns), ...]} from one xplane file."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: dict = {}
    host: list = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices[int(m.group(1))] = [
                    (*kind_of(e.name), float(e.start_ns),
                     float(e.duration_ns)) for e in line.events]
            elif not m:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return {"devices": devices, "host": host}


def _clip(events, w0: float, w1: float):
    """Op events cut to the window, as (name, kind, start, dur), sorted
    by start, longer first."""
    out = []
    for name, kind, s, d in events:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            out.append((name, kind, a, b - a))
    return sorted(out, key=lambda e: (e[2], -e[3]))


def _self_times(events):
    """Self time per event: its duration less its directly nested events."""
    selfs = [e[3] for e in events]
    stack: list = []  # indices of open events
    for i, (_, _, s, d) in enumerate(events):
        while stack and sum(events[stack[-1]][2:]) <= s:
            stack.pop()
        if stack:
            parent = stack[-1]
            selfs[parent] -= min(d, sum(events[parent][2:]) - s)
        stack.append(i)
    return [max(0.0, x) for x in selfs]


def _union(events):
    """Merged busy intervals [(start, end)] of sorted events."""
    out: list = []
    for _, _, s, d in events:
        e = s + d
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(trace: dict) -> dict:
    """Per-chip seconds inside the window, and the breakdown."""
    spans = [e for e in trace["host"] if e[0] == WINDOW]
    if not spans:
        raise ValueError(f"no {WINDOW} span in the trace")
    _, w0, wd = max(spans, key=lambda e: e[2])
    w1 = w0 + wd
    host = sorted((e for e in trace["host"] if e[0] != WINDOW),
                  key=lambda e: e[1])
    starts = [e[1] for e in host]
    chips = {}
    op_time: dict = defaultdict(float)
    gap_time: dict = defaultdict(float)
    for chip, events in sorted(trace["devices"].items()):
        ev = _clip(events, w0, w1)
        busy = _union(ev)
        sums = {"kernel": 0.0, "collective": 0.0, "other": 0.0}
        for (name, kind, _, _), st in zip(ev, _self_times(ev)):
            sums[kind] += st
            op_time[name] += st
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            left = b - a
            i = max(0, bisect.bisect_right(starts, a) - 1)
            while left > 0 and i < len(host) and host[i][1] < b:
                name, s, d = host[i]
                part = min(b, s + d) - max(a, s)
                if part > 0:
                    gap_time[name] += part
                    left -= part
                i += 1
            if left > 0:
                gap_time["host:other"] += left
        chips[chip] = {
            "busy_s": sum(b - a for a, b in busy) / 1e9,
            "kernel_s": sums["kernel"] / 1e9,
            "collective_s": sums["collective"] / 1e9,
            "other_s": sums["other"] / 1e9,
            "window_s": wd / 1e9,
        }
    if not chips:
        raise ValueError("no device op line in the trace")
    n = len(chips)

    def top(d):
        return [[k, v / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {"chips": chips, "window_s": wd / 1e9,
            "breakdown": {"device_ops": top(op_time),
                          "idle_gaps": top(gap_time)}}


def mean(chips: dict, key: str) -> float:
    return sum(c[key] for c in chips.values()) / len(chips)


def summarize(path: str, top: int = 40) -> None:
    """Print a trace's planes and lines, and each device line's ops by
    total time with the stats of one event: the look by hand that names
    the kernel, the collectives and the host threads."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: " + ", ".join(
            f"{ln.name!r} ({sum(1 for _ in ln.events)})" for ln in lines))
        if not DEVICE_PLANE.match(plane.name):
            continue
        for ln in lines:
            tot: dict = defaultdict(float)
            stats: dict = {}
            for e in ln.events:
                tot[e.name] += e.duration_ns
                stats.setdefault(e.name, [(k, str(v)[:80]) for k, v in e.stats])
            for name, ns in sorted(tot.items(), key=lambda kv: -kv[1])[:top]:
                print(f"  {ln.name!r} {name!r} {ns / 1e6:.3f} ms "
                      f"{stats[name]}")


if __name__ == "__main__":
    import sys

    summarize(find_xplane(sys.argv[1]))
