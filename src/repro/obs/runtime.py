"""Build accounting: one process-wide ``jax.monitoring`` listener, and
the ring of sweep-call records.

JAX reports how long it spends turning a Python function into a program
- ``/jax/core/compile/jaxpr_trace_duration`` (trace),
``.../jaxpr_to_mlir_module_duration`` (lower) and
``.../backend_compile_duration`` (compile, or load from the persistent
cache) - on the thread that pays it.  The listener here takes the
*outermost* of those events (a trace nested in another trace or in a
lowering is part of it) and

  * charges its seconds to the record open on that thread
    (``repro.obs.spans.charge``): a served batch, a sweep call;
  * logs it, with the innermost open span as its ``route``, in a
    bounded event log with exact per-(kind, route) counts.

This sees every route the same way: the scan decider's jit, the
kernel route's jitted decision programs, the sweep grid; each is built
on its first call per static config and shape.  Telemetry snapshots
read the log; the conformance leg excludes it (builds are
process-global and timing-dependent by nature).
"""

from __future__ import annotations

import collections
import threading
import time
from typing import List, Optional

import jax

from repro.obs import spans

#: monitored event -> build kind
BUILD_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
#: build events the log keeps (the counts stay exact past it)
LOG_CAPACITY = 1 << 14
#: sweep-call records kept (``sweep_records``)
SWEEP_CAPACITY = 1 << 14

_LOCK = threading.Lock()
_EVENTS: collections.deque = collections.deque(maxlen=LOG_CAPACITY)
_COUNTS: collections.Counter = collections.Counter()
_SWEEP: collections.deque = collections.deque(maxlen=SWEEP_CAPACITY)


class _Depth(threading.local):
    def __init__(self) -> None:
        self.n = 0      # build events open on this thread


_DEPTH = _Depth()


def _on_start(event: str, value, **_) -> None:
    if event in BUILD_EVENTS:
        _DEPTH.n += 1


def _on_span(event: str, start: float, end: float, *,
             fun_name: str = "", **_) -> None:
    kind = BUILD_EVENTS.get(event)
    if kind is None:
        return
    _DEPTH.n = max(0, _DEPTH.n - 1)
    if _DEPTH.n:
        return                  # nested: the enclosing event holds it
    seconds = end - start
    spans.charge(kind, seconds)
    route = spans.innermost()
    with _LOCK:
        _EVENTS.append({"kind": kind, "route": route, "label": fun_name,
                        "t_s": time.perf_counter() - seconds,
                        "dur_s": seconds})
        _COUNTS[kind, route] += 1


# JAX opens each build event with a scalar (its start time) and closes
# it with a time span; the pair gives the nesting depth per thread.
jax.monitoring.register_scalar_listener(_on_start)
jax.monitoring.register_event_time_span_listener(_on_span)


def compile_events() -> List[dict]:
    """The logged build events, oldest first: ``kind`` (trace / lower /
    compile), ``route`` (the innermost span open when it ran, '' if
    none), ``label`` (JAX's name of the function), ``t_s`` (start, on
    the perf_counter axis) and ``dur_s``."""
    with _LOCK:
        return [dict(e) for e in _EVENTS]


def compile_count(route: Optional[str] = None,
                  kind: str = "trace") -> int:
    """Exact count of build events of ``kind`` (in span ``route``, or
    anywhere)."""
    with _LOCK:
        return sum(n for (k, r), n in _COUNTS.items()
                   if k == kind and (route is None or r == route))


def reset_compile_log() -> None:
    with _LOCK:
        _EVENTS.clear()
        _COUNTS.clear()


def sweep_call() -> spans.recording:
    """Open the record of one sweep call; it joins ``sweep_records``."""
    return spans.recording(spans.Record(), _SWEEP.append)


def sweep_records() -> list:
    """The newest sweep-call records, oldest first."""
    return list(_SWEEP)
