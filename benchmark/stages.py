"""The AOI engine's stages in a kept profiler trace: device self time by
the step's named scopes, host time by the engine's spans.

    python3 benchmark/run.py --workload open_world_100k.walk --seed 7 \\
        --seconds 30 --trace 1 --trace-dir /tmp/walk_trace
    python3 -m benchmark.stages /tmp/walk_trace [--sample out.json]

It reads the same trace as ``trace.py`` and keeps two things more:

- each op event's stage, the first ``aoi.`` component of the op's name
  stack (its ``op_name`` metadata, which a v5e trace keeps as the
  ``tf_op`` stat of the op's event metadata: ``jit(aoi_step)/aoi.drain/
  ...``), or ``unscoped``; the step's named scopes are ``aoi.table``,
  ``aoi.feats``, ``aoi.guard``, ``aoi.gather``, ``aoi.drain``,
  ``aoi.pack`` (single chip) and ``aoi.halo``, ``aoi.tier``,
  ``aoi.logic``;
- the engine's host spans, ``aoi.<phase>`` (``telemetry.phases.
  engine_span``: upload, launch, wait, readback, page, plan), beside the
  benchmark's ``bench.*`` spans.

``reduce`` returns ``trace.reduce``'s numbers unchanged, and adds per chip
``stages``: self seconds by stage, where the event kernel's ops count as
``kernel`` and collectives as ``collective``, so the scopes and
``unscoped`` sum to ``other_s``. In its breakdown each device op is named
``<stage>/<op>``, and each idle gap goes to the innermost host span open
over it (``aoi.wait`` inside ``bench.collect``), the rest to
``host:other``.
"""

from __future__ import annotations

import argparse
import bisect
import json
import sys
from collections import defaultdict

from benchmark import trace

HOST_PREFIXES = ("bench.", "aoi.")
UNSCOPED = "unscoped"
TF_OP = "tf_op"


def stage_of(op_name: str) -> str:
    """The first ``aoi.`` component of a name stack, or ``unscoped``."""
    for part in op_name.split("/"):
        if part.startswith("aoi."):
            return part
    return UNSCOPED


# --- the op names: event metadata, which ProfileData does not expose ----------
#
# Protobuf wire format of the xplane (tsl/profiler/protobuf/xplane.proto):
# XSpace 1 = planes; XPlane 2 = name, 4 = event_metadata, 5 =
# stat_metadata (maps: entry 2 = value); XEventMetadata 2 = name, 5 =
# stats; XStatMetadata 1 = id, 2 = name; XStat 1 = metadata_id, 5 =
# str_value.


def _varint(b: bytes, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b: bytes, span: tuple[int, int]):
    """(field, value) of one message: an int, or the (start, end) of a
    length-delimited value; fixed-width values are skipped."""
    i, end = span
    while i < end:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
            yield key >> 3, v
        elif wire == 2:
            n, i = _varint(b, i)
            yield key >> 3, (i, i + n)
            i += n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {wire} at {i}")


def _map_values(b: bytes, span: tuple[int, int], field: int):
    for f, entry in _fields(b, span):
        if f == field:
            for ef, value in _fields(b, entry):
                if ef == 2:
                    yield value


def tf_ops(path: str) -> dict:
    """{device plane name: {op event name: its ``tf_op``}} of one xplane
    file."""
    with open(path, "rb") as f:
        b = f.read()
    out: dict = {}
    for f, plane in _fields(b, (0, len(b))):
        if f != 1:
            continue
        name = next((b[v[0]:v[1]].decode() for pf, v in _fields(b, plane)
                     if pf == 2), "")
        if not trace.DEVICE_PLANE.match(name):
            continue
        stat_ids = {}
        for meta in _map_values(b, plane, 5):
            d = dict(_fields(b, meta))
            stat_ids[d.get(1, 0)] = b[d[2][0]:d[2][1]].decode()
        want = {k for k, v in stat_ids.items() if v == TF_OP}
        ops = out[name] = {}
        for meta in _map_values(b, plane, 4):
            op = None
            for ef, v in _fields(b, meta):
                if ef == 2:
                    op = b[v[0]:v[1]].decode(errors="replace")
                elif ef == 5:
                    st = dict(_fields(b, v))
                    if st.get(1) in want and isinstance(st.get(5), tuple):
                        ops[op] = b[st[5][0]:st[5][1]].decode(
                            errors="replace")
    return out


def load(path: str) -> dict:
    """{"devices": {chip: [(op, kind, start_ns, dur_ns, stage), ...]},
    "host": [(name, start_ns, dur_ns), ...]} from one xplane file."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    names = tf_ops(path)
    devices: dict = {}
    host: list = []
    for plane in data.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == trace.OPS_LINE:
                ops = names.get(plane.name, {})
                devices[int(m.group(1))] = [
                    (*trace.kind_of(e.name), float(e.start_ns),
                     float(e.duration_ns), stage_of(ops.get(e.name, "")))
                    for e in line.events]
            elif not m:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events
                            if e.name.startswith(HOST_PREFIXES))
    return {"devices": devices, "host": host}


def _stage(kind: str, scope: str) -> str:
    return scope if kind == "other" else kind


def _innermost(host: list) -> list:
    """Host spans flattened to disjoint (start, end, name) segments, each
    named after the innermost span open over it. The spans come from one
    thread and nest; one that outlasts its parent is cut at the parent's
    end."""
    segs: list = []
    stack: list = []  # (end, name) of open spans, innermost last
    t = float("-inf")

    def close_until(limit):
        nonlocal t
        while stack and stack[-1][0] <= limit:
            end, name = stack.pop()
            if end > t:
                segs.append((t, end, name))
                t = end

    for name, s, d in sorted(host, key=lambda e: (e[1], -e[2])):
        close_until(s)
        if stack and s > t:
            segs.append((t, s, stack[-1][1]))
        t = max(t, s)
        end = s + d
        if stack:
            end = min(end, stack[-1][0])
        stack.append((end, name))
    close_until(float("inf"))
    return [sg for sg in segs if sg[1] > sg[0]]


def _gap_split(busy: list, w0: float, w1: float, segs: list,
               into: dict) -> None:
    """Add each idle gap of one chip to the innermost span over it."""
    starts = [sg[0] for sg in segs]
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    for a, b in zip(edges[0::2], edges[1::2]):
        left = b - a
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while left > 0 and i < len(segs) and segs[i][0] < b:
            s, e, name = segs[i]
            part = min(b, e) - max(a, s)
            if part > 0:
                into[name] += part
                left -= part
            i += 1
        if left > 0:
            into["host:other"] += left


def window(t: dict) -> tuple[float, float]:
    spans = [e for e in t["host"] if e[0] == trace.WINDOW]
    if not spans:
        raise ValueError(f"no {trace.WINDOW} span in the trace")
    _, w0, wd = max(spans, key=lambda e: e[2])
    return w0, w0 + wd


def reduce(t: dict) -> dict:
    """``trace.reduce`` of the trace, with per-chip ``stages`` and the
    breakdown by stage and innermost host span. Op events without a
    stage (an older recorded sample) count as unscoped."""
    devices = {
        chip: [(f"{_stage(e[1], e[4] if len(e) > 4 else UNSCOPED)}/{e[0]}",
                e[1], e[2], e[3]) for e in events]
        for chip, events in t["devices"].items()}
    bench_host = [e for e in t["host"] if e[0].startswith(trace.HOST_PREFIX)]
    out = trace.reduce({"devices": devices, "host": bench_host})
    w0, w1 = window(t)
    segs = _innermost([e for e in t["host"] if e[0] != trace.WINDOW])
    gaps: dict = defaultdict(float)
    for chip, events in sorted(devices.items()):
        ev = trace._clip(events, w0, w1)
        stages: dict = defaultdict(float)
        for (name, _, _, _), st in zip(ev, trace._self_times(ev)):
            stages[name.split("/", 1)[0]] += st / 1e9
        out["chips"][chip]["stages"] = dict(sorted(stages.items()))
        _gap_split(trace._union(ev), w0, w1, segs, gaps)
    n = len(devices)
    out["breakdown"]["idle_gaps"] = [
        [k, v / n / 1e9]
        for k, v in sorted(gaps.items(), key=lambda kv: -kv[1])[:10]]
    return out


def per_tick_ms(t: dict, reduced: dict) -> dict:
    """Means per window tick (ticks: ``bench.dispatch`` spans that start
    in the window): device seconds by stage (chip mean), and host
    seconds by span name, clipped to the window."""
    w0, w1 = window(t)
    ticks = sum(1 for name, s, _ in t["host"]
                if name == "bench.dispatch" and w0 <= s < w1)
    if not ticks:
        raise ValueError("no bench.dispatch span in the window")
    stages: dict = defaultdict(float)
    for c in reduced["chips"].values():
        for k, v in c["stages"].items():
            stages[k] += v / len(reduced["chips"])
    host: dict = defaultdict(float)
    for name, s, d in t["host"]:
        if name != trace.WINDOW:
            host[name] += max(0.0, min(s + d, w1) - max(s, w0)) / 1e9
    return {"ticks": ticks,
            "stages": {k: v / ticks * 1e3 for k, v in sorted(stages.items())},
            "host": {k: v / ticks * 1e3 for k, v in sorted(host.items())}}


def sample(t: dict, ms: float) -> dict:
    """``ms`` milliseconds from the middle of the window, as a recorded
    sample: every host span and op event that overlaps it, and a
    ``bench.window`` span of that length."""
    w0, w1 = window(t)
    a = (w0 + w1) / 2 - ms * 5e5
    b = a + ms * 1e6

    def overlaps(s, d):
        return s < b and s + d > a

    return {"devices": {str(c): [list(e) for e in ev if overlaps(e[2], e[3])]
                        for c, ev in t["devices"].items()},
            "host": [[trace.WINDOW, a, b - a]] + [
                list(e) for e in t["host"]
                if e[0] != trace.WINDOW and overlaps(e[1], e[2])]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--sample", help="write a recorded sample here")
    ap.add_argument("--sample-ms", type=float, default=200.0)
    args = ap.parse_args(argv)
    t = load(trace.find_xplane(args.trace_dir))
    reduced = reduce(t)
    json.dump({"per_tick_ms": per_tick_ms(t, reduced), **reduced},
              sys.stdout, indent=1)
    print()
    if args.sample:
        with open(args.sample, "w") as f:
            json.dump(sample(t, args.sample_ms), f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
