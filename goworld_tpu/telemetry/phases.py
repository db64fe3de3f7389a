"""Tick-phase tracer: per-iteration wall-time attribution for hot loops.

The instrument CheetahGIS-style streaming engines live on: every stage of
the update pipeline gets its own duration histogram, continuously, in
production — so a regression names its phase instead of hiding in an
aggregate tick time (the failure mode that let round 5's 16% CPU-bench
regression pass unnoticed).

Usage, inside a loop that must stay cheap (the 5 ms game tick):

    tracer = PhaseTracer("game_tick_phase_seconds",
                         ("dispatch", "entity_logic", "aoi", "sync_send"))
    while True:
        ...wait for work...
        tracer.begin()            # tick starts AFTER the idle wait
        handle_packets()
        tracer.mark("dispatch")
        tick_timers()
        tracer.mark("entity_logic")
        aoi_tick()
        tracer.mark("aoi")
        post_tick()
        tracer.mark("entity_logic")   # same phase twice: segments accumulate
        tracer.commit()               # observe phases + "total"

Cost per tick: one monotonic() call per mark, a small-dict accumulate, and
one histogram observe per touched phase at commit — microseconds against a
5 ms tick budget.

Phase semantics under the fused tick ([aoi] fuse_logic, entity/columns.py):
per-class columnar tick programs compile INTO the AOI device launch, so
``run_tick_batches`` skips them and ``entity_logic`` collapses to the
residual host work (timers, crontab, post queue, non-fusable hooks) while
the logic cost moves inside the ``aoi`` phase's device step — the collapse
is the observable signature that fusion is live (aoi_fused_classes/
aoi_fused_slots on /metrics name the cause).

The AOI engine's host path is timed from inside by :func:`engine_span`,
one primitive for two readers: the span's seconds accumulate on
``aoi_host_phase_seconds_total{phase}``, and a profiler session, when one
is active, sees the same span as ``aoi.<phase>`` on the host timeline
beside the device planes.
"""

from __future__ import annotations

import contextlib
import functools
import time
from typing import Any, Iterator, Optional, Sequence

from goworld_tpu.telemetry.metrics import REGISTRY, Registry

#: Label value reserved for the whole begin()→commit() span.
TOTAL_PHASE = "total"

#: Host wall seconds per AOI engine phase. The engine's own spans
#: (engine_span): upload, launch, wait, readback, page, plan; the
#: service's: delivery (event decode + interest-edge application) and
#: persist (entity snapshot packing).
AOI_HOST_PHASE = REGISTRY.counter(
    "aoi_host_phase_seconds_total",
    "Host wall seconds per AOI phase (upload|launch|wait|readback|page|"
    "plan: engine dispatch and collect; delivery: event decode + "
    "interest-edge application; persist: entity snapshot packing).",
    ("phase",))


@functools.lru_cache(maxsize=None)
def _resolved(phase: str) -> tuple[Any, str, Any]:
    """(TraceAnnotation, span name, counter child) of one phase, resolved
    once; JAX is imported on the first span."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation, f"aoi.{phase}", AOI_HOST_PHASE.labels(phase)


@contextlib.contextmanager
def engine_span(phase: str) -> Iterator[None]:
    """``with engine_span("wait"): ...`` — time one phase of the AOI
    engine's host path: ``aoi.<phase>`` on the profiler's host timeline
    (a TraceMe, nearly free with no session) and its perf_counter seconds
    on ``aoi_host_phase_seconds_total{phase}``. Processes that never open
    a span never import JAX."""
    annotation, name, child = _resolved(phase)
    with annotation(name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            child.inc(time.perf_counter() - t0)


class PhaseTracer:
    """Histogram family labeled by ``phase``, fed by begin/mark/commit."""

    __slots__ = ("_family", "_children", "_t0", "_last", "_acc")

    def __init__(self, name: str, phases: Sequence[str], help: str = "",
                 registry: Optional[Registry] = None) -> None:
        reg = registry or REGISTRY
        self._family = reg.histogram(
            name,
            help or "Wall seconds per loop-tick phase (telemetry PhaseTracer).",
            labelnames=("phase",),
        )
        # Pre-resolve children: no labels() dict lookup on the hot path.
        self._children = {p: self._family.labels(p) for p in phases}
        self._children[TOTAL_PHASE] = self._family.labels(TOTAL_PHASE)
        self._t0 = 0.0
        self._last = 0.0
        self._acc: dict[str, float] = {}

    def begin(self) -> None:
        """Start a tick. Call AFTER any idle wait so queue-blocked time
        doesn't pollute the first phase."""
        self._t0 = self._last = time.monotonic()
        self._acc.clear()

    def mark(self, phase: str) -> None:
        """Attribute the segment since the previous mark (or begin) to
        ``phase``. Re-marking a phase within one tick accumulates."""
        now = time.monotonic()
        self._acc[phase] = self._acc.get(phase, 0.0) + (now - self._last)
        self._last = now

    def commit(self):
        """Observe every accumulated phase plus the whole-tick total.

        Returns ``(t0_monotonic, total_seconds, phases_dict)`` so the
        caller can feed the same attribution to the flight recorder /
        trace ring without re-timing anything (None when no begin()
        preceded). The returned dict is a copy — safe to keep."""
        if not self._t0:
            return None  # commit without begin: nothing to attribute
        phases = dict(self._acc)
        for phase, took in phases.items():
            child = self._children.get(phase)
            if child is None:  # late-declared phase: resolve once, keep
                child = self._children[phase] = self._family.labels(phase)
            child.observe(took)
        total = self._last - self._t0
        self._children[TOTAL_PHASE].observe(total)
        t0 = self._t0
        self._t0 = 0.0
        self._acc.clear()
        return t0, total, phases
