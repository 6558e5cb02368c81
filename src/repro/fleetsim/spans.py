"""Host phase spans and compile counters of a FleetSim call.

``phase(phases, name)`` times one host phase of a call: it opens the
profiler span ``fleetsim.<name>`` (``jax.profiler.TraceAnnotation``, on the
profiler's clock, shared with the device trace) and adds the phase's
seconds to the call's ``phases`` dict.  Outside a profiler session the span
costs a few clock reads.

The compile counters come from process-wide ``jax.monitoring`` listeners:
how many jaxpr traces, MLIR lowerings and backend compiles ran (and their
seconds, nested traces counted once, within the trace that encloses
them), and how many persistent-cache hits and misses.  A
call snapshots them before and after (``compile_events``); the difference
tells a fresh compile (a miss) from a cache load (a hit) from a program
reused in memory (no backend compile at all).
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager

import jax

#: prefix of every host span the program opens
SPAN_PREFIX = "fleetsim."

#: timed events → counter name (each also counts ``<name>_s`` seconds)
DURATION_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jaxpr_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "mlir_lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}
#: plain events → counter name
COUNT_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
COUNTERS = (*(n for v in DURATION_EVENTS.values() for n in (v, v + "_s")),
            *COUNT_EVENTS.values())

_counts = dict.fromkeys(COUNTERS, 0.0)
#: per duration event, the latest spans whose seconds are counted: a jit
#: traced inside another reports its span first, and the enclosing span
#: that arrives later replaces it, so nested time counts once
_spans: dict[str, list[tuple[float, float]]] = {
    n: [] for n in DURATION_EVENTS.values()}
_MAX_OPEN = 10_000
_lock = threading.Lock()


def _on_span(event: str, start: float, end: float, **_) -> None:
    name = DURATION_EVENTS.get(event)
    if name is None:
        return
    with _lock:
        _counts[name] += 1
        spans = _spans[name]
        while spans and spans[-1][0] >= start:
            s, e = spans.pop()
            _counts[name + "_s"] -= e - s
        spans.append((start, end))
        del spans[:-_MAX_OPEN]
        _counts[name + "_s"] += end - start


def _on_event(event: str, **_) -> None:
    name = COUNT_EVENTS.get(event)
    if name is not None:
        with _lock:
            _counts[name] += 1


jax.monitoring.register_event_time_span_listener(_on_span)
jax.monitoring.register_event_listener(_on_event)


def compile_counts() -> dict[str, float]:
    """The process's compile counters so far."""
    with _lock:
        return dict(_counts)


def compile_events(before: dict[str, float]) -> dict[str, float]:
    """The compile counters added since the snapshot ``before``."""
    now = compile_counts()
    return {k: now[k] - before[k] for k in COUNTERS}


@contextmanager
def phase(phases: dict[str, float], name: str):
    """Time the host phase ``name``: a ``fleetsim.<name>`` profiler span,
    and its seconds added to ``phases[name]``."""
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
            yield
    finally:
        phases[name] = phases.get(name, 0.0) + time.perf_counter() - t0
