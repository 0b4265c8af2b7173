"""Phase spans on the profiler's clock, kept as one record per unit of
work, with Chrome trace-event export.

A *span* times one phase of the program: ``with span("broker.decide"):``
at a layer boundary.  Every span is mirrored as a
``jax.profiler.TraceAnnotation`` of the same name, so under a profiler
it lands on the host plane of the device trace, on the device's clock.
The unit of record is the committed micro-batch (``BatchRecord``, whose
root span is ``broker.batch``) or one sweep call (``Record``, one per
``compare_workloads``): each span closed while a record is open on the
thread adds its seconds to the record's phase of that name (a phase
that runs twice in one batch sums), with its first start, last end and
parent.  The sharded plane runs each batch in two ``broker.batch``
blocks, dispatch and resolve, with other shards' work between them;
both go on the batch's one record (``SpanRecorder.resume``).  Build
time (trace, lower, compile or cache load) is charged to the open
record by ``repro.obs.runtime``'s listener.

Spans are ``with`` blocks inside the functions they time, never
wrappers: the Pallas kernels' source locations carry the whole Python
stack, so a frame more on the decide path costs lowering time.

The served path appends nothing per request: a record holds the
batch's ``t_submit`` vector, and the request spans are derived from the
records when read (``SpanRecorder.spans``).  ``chrome_trace()`` dumps
them in the Chrome trace-event JSON format (``chrome://tracing`` /
Perfetto): ``pid`` is the authority shard, ``tid`` the agent (or
``authority`` for batch and phase spans), ``ts`` / ``dur`` are
microseconds relative to the recorder's epoch.
"""

from __future__ import annotations

import collections
import json
import threading
import time
from typing import Iterator, NamedTuple, Optional

import numpy as np
from jax.profiler import TraceAnnotation

#: the root span of a committed micro-batch: its seconds are the batch's
#: public flush time (``BatchRecord.flush_s``)
BATCH = "broker.batch"


class Span(NamedTuple):
    name: str        # e.g. "read artifact-3" / "decide" / "broker.cut"
    cat: str         # "request" | "batch" | "phase" | "compile"
    ts_s: float      # start, seconds on the perf_counter axis
    dur_s: float
    pid: int         # authority shard
    tid: object      # agent id, or "authority"
    args: dict


class Record:
    """What one unit of work spent, phase by phase.

    ``phases`` maps a span name to ``[first start, last end, seconds,
    parent name]``; ``trace_s`` / ``lower_s`` / ``compile_s`` are the
    build seconds charged while the record was open and ``n_builds``
    the programs compiled or loaded from the persistent cache.
    """

    __slots__ = ("t0", "wall_s", "phases", "trace_s", "lower_s",
                 "compile_s", "n_builds")

    def __init__(self) -> None:
        self.t0 = 0.0
        self.wall_s = 0.0
        self.phases: dict = {}
        self.trace_s = self.lower_s = self.compile_s = 0.0
        self.n_builds = 0

    def add(self, name: str, parent: Optional[str], t0: float,
            t1: float) -> None:
        cell = self.phases.get(name)
        if cell is None:
            self.phases[name] = [t0, t1, t1 - t0, parent]
        else:
            cell[1] = t1
            cell[2] += t1 - t0

    def seconds(self, name: str) -> float:
        """Seconds spent in phase ``name`` (0.0 when it did not run)."""
        cell = self.phases.get(name)
        return cell[2] if cell is not None else 0.0

    def self_seconds(self, name: str) -> float:
        """Seconds of phase ``name`` outside its child phases."""
        return self.seconds(name) - sum(
            cell[2] for cell in self.phases.values() if cell[3] == name)

    @property
    def build_s(self) -> float:
        return self.trace_s + self.lower_s + self.compile_s


class BatchRecord(Record):
    """A committed micro-batch: its phases and what telemetry knows of
    its requests (``acts``/``arts``/``writes``/``t_submit`` are indexed
    by agent slot)."""

    __slots__ = ("shard", "names", "acts", "arts", "writes", "t_submit",
                 "t_decide", "decide_s", "t_respond", "route",
                 "queue_depth")

    def __init__(self, shard: int = 0) -> None:
        super().__init__()
        self.shard = shard
        self.t_submit: Optional[np.ndarray] = None

    @property
    def flush_s(self) -> float:
        """The whole batch, from cut to the end of its telemetry."""
        return self.seconds(BATCH)


class _Thread(threading.local):
    def __init__(self) -> None:
        self.record: Optional[Record] = None   # open on this thread
        self.open: list = []                   # open span names


_THREAD = _Thread()


def current() -> Optional[Record]:
    """The record open on the calling thread, if any."""
    return _THREAD.record


def innermost() -> str:
    """The innermost span open on the calling thread ('' if none)."""
    opened = _THREAD.open
    return opened[-1] if opened else ""


class span:
    """``with span(name):`` times one phase into the open record and
    mirrors it as a profiler annotation of the same name."""

    __slots__ = ("name", "_ann", "_t0")

    def __init__(self, name: str) -> None:
        self.name = name

    def __enter__(self) -> "span":
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        _THREAD.open.append(self.name)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        thread = _THREAD
        opened = thread.open
        opened.pop()
        self._ann.__exit__(None, None, None)
        if thread.record is not None:
            thread.record.add(self.name, opened[-1] if opened else None,
                              self._t0, t1)
        return False


class recording(span):
    """``with recording(record, sink, name):`` opens ``record`` on the
    thread for the block; on a clean exit the record, with its
    ``t0``/``wall_s``, goes to ``sink``.  With a ``name`` the block is
    also the record's root span.  A record opened again (its work run
    in two blocks) keeps the ``t0`` of its first block, and ``wall_s``
    runs to the end of its last."""

    __slots__ = ("record", "_sink", "_prev")

    def __init__(self, record: Optional[Record], sink,
                 name: Optional[str] = None) -> None:
        self.name = name
        self.record = record
        self._sink = sink

    def __enter__(self) -> Optional[Record]:
        thread = _THREAD
        self._prev = thread.record
        thread.record = self.record
        if self.name is not None:
            span.__enter__(self)
        else:
            self._t0 = time.perf_counter()
        return self.record

    def __exit__(self, exc_type, *exc) -> bool:
        if self.name is not None:
            span.__exit__(self, exc_type, *exc)
        rec = self.record
        _THREAD.record = self._prev
        if rec is not None:
            if not rec.wall_s:      # a resumed record keeps its start
                rec.t0 = self._t0
            rec.wall_s = time.perf_counter() - rec.t0
            if exc_type is None and self._sink is not None:
                self._sink(rec)
        return False


def charge(kind: str, seconds: float) -> None:
    """Charge ``seconds`` of build work (``trace`` / ``lower`` /
    ``compile``) to the record open on the calling thread."""
    rec = _THREAD.record
    if rec is None:
        return
    if kind == "trace":
        rec.trace_s += seconds
    elif kind == "lower":
        rec.lower_s += seconds
    else:
        rec.compile_s += seconds
        rec.n_builds += 1


class SpanRecorder:
    """Bounded ring of committed batch records (capacity in batches).

    ``n_recorded`` counts every span the records stand for - one batch
    span plus one request span per resolved request - exactly, past
    the ring's wrap.
    """

    def __init__(self, capacity: int = 1 << 14) -> None:
        self.capacity = capacity
        self.records: collections.deque = collections.deque(
            maxlen=capacity)
        self.n_recorded = 0
        self.epoch = time.perf_counter()

    def batch(self, shard: int = 0) -> recording:
        """The root span of one micro-batch; the batch is kept once its
        telemetry has filled in its requests."""
        return recording(BatchRecord(shard), self._commit, BATCH)

    def resume(self, rec: BatchRecord) -> recording:
        """The rest of a batch whose first half ran in another
        :meth:`batch` block: the phases of both halves go on its one
        record, which is kept once this block closes."""
        return recording(rec, self._commit, BATCH)

    def _commit(self, rec: BatchRecord) -> None:
        if rec.t_submit is None:        # nothing committed (empty cut
            return                      # or a failed batch)
        self.records.append(rec)
        self.n_recorded += 1 + int(rec.acts.sum())

    # ------------------------------------------------------------ views
    @property
    def spans(self) -> Iterator[Span]:
        """Every record as spans: its ``decide`` batch span, its phases
        and one ``request`` span per request."""
        for rec in self.records:
            yield from _spans_of(rec)

    def chrome_trace(self) -> dict:
        """The records as a Chrome trace-event JSON object."""
        events = []
        for s in self.spans:
            events.append({
                "name": s.name,
                "cat": s.cat,
                "ph": "X",
                "ts": (s.ts_s - self.epoch) * 1e6,
                "dur": s.dur_s * 1e6,
                "pid": s.pid,
                "tid": (s.tid if isinstance(s.tid, int)
                        else str(s.tid)),
                "args": dict(s.args),
            })
        return {"traceEvents": events,
                "displayTimeUnit": "ms",
                "otherData": {"n_recorded": self.n_recorded,
                              "capacity": self.capacity}}

    def to_chrome_json(self) -> str:
        return json.dumps(self.chrome_trace(), indent=2, default=float)


def _spans_of(rec: BatchRecord) -> Iterator[Span]:
    agents = np.flatnonzero(rec.acts)
    pid = rec.shard
    yield Span("decide", "batch", rec.t_decide, rec.decide_s, pid,
               "authority", {"batch_size": int(agents.size),
                             "route": rec.route,
                             "queue_depth": rec.queue_depth,
                             "flush_s": rec.flush_s,
                             "build_s": rec.build_s,
                             "n_builds": rec.n_builds})
    for name, (t0, t1, seconds, parent) in rec.phases.items():
        yield Span(name, "phase", t0, seconds, pid, "authority",
                   {"parent": parent, "end_s": t1})
    decide_end = rec.t_decide + rec.decide_s
    for agent in agents.tolist():
        t_submit = float(rec.t_submit[agent])
        op = "write" if rec.writes[agent] else "read"
        yield Span(
            f"{op} {rec.names[int(rec.arts[agent])]}", "request",
            t_submit, max(0.0, rec.t_respond - t_submit), pid, agent,
            {"queue_s": max(0.0, rec.t_decide - t_submit),
             "decide_s": rec.decide_s,
             "apply_s": max(0.0, rec.t_respond - decide_end)})
