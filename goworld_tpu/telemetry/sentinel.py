"""Device-runtime sentinel: launch/trace accounting for the engine step jits.

After PR 12 the engine's most important steady-state invariant — a fused
tick is ONE device launch with ZERO retraces — was enforced only by a
test (``test_fused_service_one_launch_trace_counts``). This module makes
it *observable in a live cluster* (the AsyncTaichi point: once execution
is batched and asynchronous, per-launch runtime attribution is the only
way to see regressions):

- :class:`SentinelJit` wraps a jitted callable returned by the engine's
  lru-cached jit factories (ops/neighbor.py, parallel/spatial.py,
  parallel/mesh.py). Every call bumps ``jit_launches_total{fn}``; the
  trace-cache size of the underlying jit (``_cache_size``) is compared
  after the call, so a compile is detected *without touching the traced
  function* — gwlint R1's whole-program view of the step bodies is
  unchanged, and the per-launch overhead is a counter bump plus one
  integer compare, never a device sync.
- A **steady-state retrace detector**: once an instance has served more
  than ``[telemetry] retrace_warm_ticks`` launches, any further trace is
  a regression — ``jit_retrace_events_total{fn}`` increments and ONE
  structured WARN names the arg shape/dtype delta against the previous
  trace signature and carries the flight recorder's recent ticks
  (repeat retraces with the *same* signature do not re-WARN; a new
  distinct signature does). Warm-up traces (first compile, tier growth,
  program-set churn on a *fresh* jit instance) are counted on
  ``jit_traces_total{fn}`` but never alarmed.
- ``jit_cached_traces{fn}`` mirrors each instrumented jit's live trace
  cache, and :func:`install_compile_cache_listener` forwards jax's
  persistent-compilation-cache monitoring events onto
  ``jit_compile_cache_hits_total`` / ``jit_compile_cache_misses_total``
  (the [aoi] compilation_cache story, live).

Thread model: launches happen on the game loop; the prewarm threads
(BatchAOIService / spatial fallback warmup) may drive the same instance
concurrently. The rare trace path takes one per-instance lock; the
launch path is lock-free beside the counter's own lock.
"""

from __future__ import annotations

import json
import threading
from typing import Any, Optional

from goworld_tpu.telemetry.metrics import REGISTRY

_LAUNCHES = REGISTRY.counter(
    "jit_launches_total",
    "Dispatches of each instrumented engine step jit.", ("fn",))
_TRACES = REGISTRY.counter(
    "jit_traces_total",
    "XLA traces (compiles) of each instrumented engine step jit.", ("fn",))
_RETRACES = REGISTRY.counter(
    "jit_retrace_events_total",
    "Steady-state retraces: traces that happened after the warm-tick "
    "threshold on an already-compiled jit (each one is a regression).",
    ("fn",))
_CACHED = REGISTRY.gauge(
    "jit_cached_traces",
    "Live trace-cache entries held by each instrumented jit.", ("fn",))
_CACHE_HITS = REGISTRY.counter(
    "jit_compile_cache_hits_total",
    "Persistent XLA compile-cache hits (jax monitoring).")
_CACHE_MISSES = REGISTRY.counter(
    "jit_compile_cache_misses_total",
    "Persistent XLA compile-cache misses (jax monitoring).")

#: Launches after which a fresh trace on an instance is a steady-state
#: retrace ([telemetry] retrace_warm_ticks).
_warm_launches: int = 32


def configure(warm_launches: Optional[int] = None) -> None:
    global _warm_launches
    if warm_launches is not None:
        _warm_launches = max(1, int(warm_launches))


def configure_from_config(tcfg: Any) -> None:
    """Apply a read_config.TelemetryConfig (each process at boot)."""
    configure(warm_launches=getattr(tcfg, "retrace_warm_ticks", None))


def warm_launches() -> int:
    return _warm_launches


def _sig_of(args: tuple[Any, ...], kwargs: dict[str, Any]) -> tuple[str, ...]:
    """Shape/dtype signature of one call, for the retrace WARN delta.
    Positional args first, then keywords sorted by name. The array KIND
    (the type's top-level package: jaxlib vs numpy) is part of the
    signature — jax caches a numpy-array call separately from a
    device-array call of the same shape, and host code regressing to
    numpy args mid-run is exactly the per-tick-transfer retrace this
    sentinel exists to name."""

    def one(a: Any) -> str:
        dtype = getattr(a, "dtype", None)
        shape = getattr(a, "shape", None)
        if dtype is not None and shape is not None:
            dims = ",".join(str(d) for d in shape)
            kind = type(a).__module__.split(".")[0]
            return f"{kind}:{dtype}[{dims}]"
        return f"py:{type(a).__name__}"

    sig = [one(a) for a in args]
    sig.extend(f"{k}={one(v)}" for k, v in sorted(kwargs.items()))
    return tuple(sig)


def _sig_delta(prev: tuple[str, ...],
               cur: tuple[str, ...]) -> list[dict[str, Any]]:
    """Positions where the signatures disagree (arity changes included)."""
    out: list[dict[str, Any]] = []
    for i in range(max(len(prev), len(cur))):
        p = prev[i] if i < len(prev) else "<absent>"
        c = cur[i] if i < len(cur) else "<absent>"
        if p != c:
            out.append({"arg": i, "was": p, "now": c})
    return out


class SentinelJit:
    """One instrumented jitted callable (see module docstring).

    Wraps the object ``jax.jit`` returned; the engines keep calling it
    (and its ``_cache_size``) exactly as before. Per-instance state, not
    per-label: the lru-cached factories return a fresh instance per
    (params, backend, programs) key, so a tier jump or program-set churn
    compiles inside its own warm window and never false-alarms.
    """

    __slots__ = ("label", "_jitted", "_lock", "_launches", "_traces_seen",
                 "_cs_ok", "_sig", "_warned_sig", "_launch_child",
                 "_trace_child", "_retrace_child", "_cached_gauge")

    def __init__(self, label: str, jitted: Any) -> None:
        self.label = label
        self._jitted = jitted
        self._lock = threading.Lock()
        self._launches = 0
        self._traces_seen = 0
        self._cs_ok = True
        self._sig: Optional[tuple[str, ...]] = None
        self._warned_sig: Optional[tuple[str, ...]] = None
        self._launch_child = _LAUNCHES.labels(label)
        self._trace_child = _TRACES.labels(label)
        self._retrace_child = _RETRACES.labels(label)
        self._cached_gauge = _CACHED.labels(label)

    def _cache_size(self) -> int:
        """Delegate for the engines' ``fused_trace_count`` probes."""
        size = self._jitted._cache_size()
        return int(size)

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        self._launches += 1
        self._launch_child.inc()
        out = self._jitted(*args, **kwargs)
        if self._cs_ok:
            try:
                cs = int(self._jitted._cache_size())
            except Exception:  # pragma: no cover - private-API drift
                self._cs_ok = False
            else:
                if cs != self._traces_seen:
                    self._note_trace(cs, args, kwargs)
        return out

    def _note_trace(self, cache_size: int, args: tuple[Any, ...],
                    kwargs: dict[str, Any]) -> None:
        """Bookkeep one observed trace (rare path: first compile, tier
        warmup, or — past the warm threshold — a steady-state retrace)."""
        with self._lock:
            fresh = cache_size - self._traces_seen
            if fresh <= 0:  # cache shrank (jax GC'd an entry): resync only
                self._traces_seen = cache_size
                self._cached_gauge.set(cache_size)
                return
            self._traces_seen = cache_size
            self._trace_child.inc(fresh)
            self._cached_gauge.set(cache_size)
            sig = _sig_of(args, kwargs)
            prev, self._sig = self._sig, sig
            # Warm window: the launch that triggered this trace is within
            # the threshold, or this instance had never compiled before.
            if prev is None or self._launches <= _warm_launches:
                return
            self._retrace_child.inc(fresh)
            if sig == self._warned_sig:
                return  # identical delta already alarmed once
            self._warned_sig = sig
        self._warn_retrace(prev, sig)

    def _warn_retrace(self, prev: tuple[str, ...],
                      sig: tuple[str, ...]) -> None:
        """ONE structured WARN per distinct retrace signature: the shape/
        dtype delta against the previous trace plus the flight recorder's
        recent ticks — the whole incident is machine-readable from the
        log alone (same contract as the slow-tick dump)."""
        from goworld_tpu.telemetry import tracing
        from goworld_tpu.utils import gwlog

        rec = tracing.flight_recorder()
        flight = rec.snapshot().get("recent", [])[-20:] if rec else []
        gwlog.warnf(
            "steady-state retrace: %s",
            json.dumps({
                "fn": self.label,
                "launches": self._launches,
                "cached_traces": self._traces_seen,
                "warm_launches": _warm_launches,
                "delta": _sig_delta(prev, sig),
                "prev_signature": list(prev),
                "new_signature": list(sig),
                "flight": flight,
            }, separators=(",", ":"), default=str))


def steady_state_retraces() -> float:
    """Sum of ``jit_retrace_events_total`` across every instrumented jit
    (the bench floor headlines assert this stays 0)."""
    fam = REGISTRY.family("jit_retrace_events_total")
    if fam is None:
        return 0.0
    return sum(child.value for _, child in fam.children())


def launches_total(fn: str | None = None) -> float:
    """Launches of one instrumented jit, or of all of them (``fn=None``)."""
    if fn is None:
        return sum(child.value for _, child in _LAUNCHES.children())
    return float(_LAUNCHES.labels(fn).value)


def compile_cache_hits() -> float:
    return float(_CACHE_HITS.value)


def traces_total(fn: str) -> float:
    return float(_TRACES.labels(fn).value)


def retrace_events_total(fn: str) -> float:
    return float(_RETRACES.labels(fn).value)


_cache_listener_installed = False


def install_compile_cache_listener() -> None:
    """Forward jax's persistent compile-cache monitoring events onto the
    hit/miss counters. Idempotent; a jax without the monitoring API (or
    no jax at all) leaves the counters at 0. Called by the engine jit
    factories — processes that never touch jax never import it here."""
    global _cache_listener_installed
    if _cache_listener_installed:
        return
    _cache_listener_installed = True
    try:
        from jax import monitoring

        def on_event(event: str, **kwargs: Any) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                _CACHE_HITS.inc()
            elif event == "/jax/compilation_cache/cache_misses":
                _CACHE_MISSES.inc()

        monitoring.register_event_listener(on_event)
    except Exception:  # pragma: no cover - monitoring API drift
        pass
