"""Artifact Coherence Broker: an asyncio single-writer authority.

The simulator answers "how many tokens would a fleet spend"; this
module answers "serve the fleet".  Many concurrent agent clients issue
read/write requests against a shared artifact store; the broker is the
serialization point (paper A2/AS1): all directory mutation happens on
ONE flush task, so the three verified invariants (SWMR, monotonic
versioning, K-bounded staleness) hold under true asyncio interleaving
by construction - and are *checked* after every micro-batch, not
assumed.

State machinery is reused, not reimplemented:

  * content plane: ``repro.core.protocol``'s ``ArtifactStore`` +
    ``EventBus`` (``VERSION_UPDATE`` messages on every commit) +
    ``TokenLedger`` accounting;
  * decision plane: ``repro.service.batching`` - coalesced micro-batches
    resolved by the simulator's own serialized authority pass
    (``acs.apply_actions``) or the batched Pallas MESI kernel;
  * audit plane: every decision lands in a ``ServiceTrace``
    (``repro.service.trace``) that replays bit-for-bit through the
    four-way differential oracle, closing the live-service <->
    conformance loop.
"""

from __future__ import annotations

import asyncio
import collections
import dataclasses
import time
from typing import Callable, Dict, NamedTuple, Optional, Sequence

import numpy as np

from repro.configs.coherence import CoherenceConfig
from repro.content.chunks import (BYTES_PER_TOKEN, ChunkStore,
                                  diff_chunks)
from repro.core import acs, invariants
from repro.core.protocol import (ArtifactStore, EventBus, Message,
                                 TokenLedger)
from repro.core.states import MESIState
from repro.obs.spans import BATCH, span
from repro.obs.stats import unified_stats
from repro.obs.telemetry import BatchObservation, Telemetry
from repro.service.batching import BatchDecider
from repro.service.trace import ServiceTrace

_E = int(MESIState.E)

class InvariantViolation(AssertionError):
    """A verified CCS invariant failed on live broker state."""


class ReadResult(NamedTuple):
    content: tuple
    version: int
    hit: bool            # False = coherence fill (tokens were charged)
    latency_s: float
    #: chunked brokers only: the actual delta payload of a miss -
    #: ((chunk_idx, chunk_tokens), ...) covering exactly the reader's
    #: stale chunks (empty tuple on a hit; ``None`` when the content
    #: plane is off).  ``content`` is always the full authority copy;
    #: ``repro.content.apply_delta(prev, delta, chunk_tokens)`` patched
    #: onto any previously-held copy reproduces it byte-for-byte.
    delta: tuple | None = None
    #: wire bytes this read cost under delta coherence (-1 when off)
    delta_bytes: int = -1


class WriteResult(NamedTuple):
    version: int
    latency_s: float
    #: chunked brokers only: chunks this commit actually dirtied
    #: (measured by content-address diff; ``None`` when off)
    dirty_chunks: tuple | None = None


def _fail(batch: list, error: Exception) -> None:
    for req in batch:
        if not req.future.done():
            req.future.set_exception(error)


@dataclasses.dataclass
class _Request:
    agent: int
    artifact: int
    is_write: bool
    content: Optional[tuple]
    future: asyncio.Future
    t_submit: float


class _Staged(NamedTuple):
    """A cut batch as the decider takes it, with what its checks and
    telemetry need from before the decision."""

    batch: list
    acts: np.ndarray
    arts: np.ndarray
    writes: np.ndarray
    t_submit: np.ndarray
    wmasks: Optional[np.ndarray]
    state_before: Optional[np.ndarray]
    queue_depth: int
    ver_before: np.ndarray


class _Flight(NamedTuple):
    """A batch between its ``_flush_begin`` and its ``_flush_end``."""

    staged: _Staged
    decision: object          # the decider's ``InFlight``
    t_decide: float
    dispatch_s: float
    record: object            # its ``BatchRecord`` (None: no telemetry)


class CoherenceBroker:
    """The single-writer directory service.

    Use as an async context manager::

        async with CoherenceBroker(cfg) as broker:
            await broker.read(agent=0, artifact="plan")
    """

    def __init__(self, config: CoherenceConfig,
                 contents: Optional[Dict[str, Sequence[int]]] = None,
                 *, on_commit: Optional[Callable] = None,
                 device=None, telemetry: Optional[Telemetry] = None,
                 shard: int = 0,
                 wake: Optional[asyncio.Event] = None) -> None:
        if not config.topology.trivial:
            raise ValueError(
                "CoherenceBroker is the single-authority shard; "
                "non-trivial topologies need "
                "repro.service.connect(...) / "
                "ShardedCoherenceBroker")
        self.config = config
        core, service = config.core, config.service
        self.names = tuple(config.artifacts)
        self._index = {a: d for d, a in enumerate(self.names)}
        self.acs_config = config.acs_config()
        self.decider = BatchDecider(self.acs_config, service.backend,
                                    device=device)
        #: called as ``on_commit(broker, commit)`` after every committed
        #: micro-batch (the sharded authority plane uses this to build
        #: the globally-sequenced trace)
        self._on_commit = on_commit
        #: decision-plane busy time: seconds spent inside the decider
        #: (the serialized per-authority bottleneck the shard-capacity
        #: metric is built on)
        self.decide_busy_s = 0.0
        #: shard label this authority stamps on its metrics (the
        #: sharded plane passes its shard id; standalone brokers are
        #: shard 0 - the same label the conformance replay uses)
        self.shard = int(shard)
        #: the telemetry plane handle (None = disabled).  A sharded
        #: deployment hands ONE shared ``Telemetry`` to every
        #: sub-broker; a standalone broker builds its own.
        self.telemetry: Optional[Telemetry] = telemetry
        if self.telemetry is None and service.telemetry:
            self.telemetry = Telemetry(
                config.n_agents, strategy=core.strategy,
                backend=self.decider.backend)
        self.bus = EventBus()
        self.store = ArtifactStore()
        for name in self.names:
            content = (contents or {}).get(
                name, list(range(core.artifact_tokens)))
            if len(content) != core.artifact_tokens:
                raise ValueError(
                    f"artifact {name!r} content length {len(content)} != "
                    f"artifact_tokens {core.artifact_tokens} (the "
                    f"broker's accounting is fixed-slot, like the "
                    f"simulator's)")
            self.store.put(name, list(content))
        self.chunks: Optional[ChunkStore] = None
        if core.chunk_tokens > 0:
            self.chunks = ChunkStore(self.store, core.chunk_tokens)
            for name in self.names:
                self.chunks.register(name)
        #: bytes-on-wire ledger (content plane; all zero when off)
        self.wire = {"delta_bytes": 0, "full_bytes": 0,
                     "n_chunks_fetched": 0}
        self.ledger = TokenLedger()
        self.trace = ServiceTrace.for_broker(config)
        self.latencies = collections.deque(maxlen=service.latency_window)
        self.n_batches = 0
        self._pending: list = []
        #: set by every submit.  A sharded plane passes its own
        #: event: its one task flushes every shard, and this broker
        #: starts no flush task of its own.
        self._driven = wake is not None
        self._wake = wake if wake is not None else asyncio.Event()
        self._flusher_task: Optional[asyncio.Task] = None
        self._started = False
        self._closed = False

    # ------------------------------------------------------- lifecycle
    async def start(self) -> "CoherenceBroker":
        self._started = True
        if self._flusher_task is None and not self._driven:
            self._flusher_task = asyncio.get_running_loop().create_task(
                self._flusher())
        return self

    async def stop(self) -> None:
        self._closed = True
        self._wake.set()
        if self._flusher_task is not None:
            await self._flusher_task
            self._flusher_task = None

    async def __aenter__(self) -> "CoherenceBroker":
        return await self.start()

    async def __aexit__(self, *exc) -> None:
        await self.stop()

    # ------------------------------------------------------ client API
    def artifact_index(self, artifact: str) -> int:
        try:
            return self._index[artifact]
        except KeyError:
            raise KeyError(
                f"unknown artifact {artifact!r}; registered: "
                f"{list(self.names)}") from None

    async def read(self, agent: int, artifact: str) -> ReadResult:
        """Consume an artifact: zero tokens when the agent's coherent
        copy is valid, a full fetch otherwise."""
        return await self._submit(agent, artifact, False, None)

    async def write(self, agent: int, artifact: str,
                    content: Optional[Sequence[int]] = None
                    ) -> WriteResult:
        """Read-modify-write through the authority (upgrade -> commit).
        ``content=None`` commits a same-size revision of the current
        canonical content (pointer-semantics update)."""
        if content is not None:
            content = tuple(content)
            if len(content) != self.config.core.artifact_tokens:
                raise ValueError(
                    f"write of {len(content)} tokens to fixed "
                    f"{self.config.core.artifact_tokens}-token artifact slot")
        return await self._submit(agent, artifact, True, content)

    def _submit(self, agent: int, artifact: str, is_write: bool,
                content) -> asyncio.Future:
        if self._closed:
            raise RuntimeError("broker is stopped")
        if not 0 <= agent < self.config.n_agents:
            raise ValueError(f"agent {agent} outside [0, "
                             f"{self.config.n_agents})")
        if not self._started:
            raise RuntimeError("broker not started - use "
                               "`async with CoherenceBroker(...)` or "
                               "await broker.start()")
        fut = asyncio.get_running_loop().create_future()
        self._pending.append(_Request(
            agent=agent, artifact=self.artifact_index(artifact),
            is_write=is_write, content=content, future=fut,
            t_submit=time.perf_counter()))
        self._wake.set()
        return fut

    # --------------------------------------------------------- flusher
    async def _flusher(self) -> None:
        while True:
            await self._wake.wait()
            self._wake.clear()
            if self._closed and not self._pending:
                return
            if self.config.service.batch_window > 0:
                await asyncio.sleep(self.config.service.batch_window)
            else:
                # one event-loop pass: every already-scheduled client
                # coroutine gets to enqueue before the batch is cut.
                await asyncio.sleep(0)
            while self._pending:
                self._flush_once()
                if self._pending:       # same-agent conflict spillover
                    await asyncio.sleep(0)
            if self._closed:
                return

    def _cut_batch(self) -> list:
        """Drain pending FIFO into a micro-batch: at most one request
        per agent (a batch is one serialized authority pass; a second
        request from the same agent belongs to the next pass)."""
        max_batch = self.config.service.max_batch or self.config.n_agents
        batch, rest, seen = [], [], set()
        for req in self._pending:
            if req.agent in seen or len(batch) >= max_batch:
                rest.append(req)
            else:
                seen.add(req.agent)
                batch.append(req)
        self._pending = rest
        return batch

    def _flush_once(self) -> None:
        # one batch, from cut to the end of its telemetry; the phase
        # spans are with-blocks in place (a frame more adds host time to
        # every batch)
        tel = self.telemetry
        with (tel.spans.batch(self.shard) if tel is not None
              else span(BATCH)):
            with span("broker.cut"):
                batch = self._cut_batch()
            if not batch:
                return
            try:
                staged = self._stage(batch)
                with span("broker.decide"):
                    t_decide = time.perf_counter()
                    decision = self.decider.decide(
                        staged.acts, staged.arts, staged.writes,
                        write_chunks=staged.wmasks)
                    busy_s = time.perf_counter() - t_decide
                self._finish(staged, decision, t_decide, busy_s)
            except Exception as e:   # noqa: BLE001 - fail the batch,
                _fail(batch, e)      # not the event loop

    # A flush task that holds several shards' batches in flight at once
    # (``ShardedCoherenceBroker``) runs a flush in two halves: every
    # shard's ``_flush_begin`` (cut, stage, dispatch), then each
    # shard's ``_flush_end`` (resolve, checks, respond, telemetry).  The
    # batch keeps one record over both halves.
    def _flush_begin(self) -> Optional[_Flight]:
        tel = self.telemetry
        with (tel.spans.batch(self.shard) if tel is not None
              else span(BATCH)) as record:
            with span("broker.cut"):
                batch = self._cut_batch()
            if not batch:
                return None
            try:
                staged = self._stage(batch)
                with span("broker.decide"):
                    t_decide = time.perf_counter()
                    flight = self.decider.dispatch(
                        staged.acts, staged.arts, staged.writes,
                        write_chunks=staged.wmasks)
                    dispatch_s = time.perf_counter() - t_decide
            except Exception as e:   # noqa: BLE001
                _fail(batch, e)
                return None
        return _Flight(staged, flight, t_decide, dispatch_s,
                       record if tel is not None else None)

    def _flush_end(self, flight: _Flight) -> None:
        tel = self.telemetry
        with (tel.spans.resume(flight.record) if tel is not None
              else span(BATCH)):
            try:
                with span("broker.decide"):
                    t_resolve = time.perf_counter()
                    decision = self.decider.resolve(flight.decision)
                    # the batch's own decide time: both halves, not the
                    # other shards' work between them
                    busy_s = (flight.dispatch_s + time.perf_counter()
                              - t_resolve)
                self._finish(flight.staged, decision, flight.t_decide,
                             busy_s)
            except Exception as e:   # noqa: BLE001
                _fail(flight.staged.batch, e)

    def _measure_write_masks(self, batch: list) -> Optional[np.ndarray]:
        """(n, C) measured dirty chunk masks for the batch's writes.

        Masks are diffed *sequentially in the authority's agent order*
        against the content each write will actually see at its
        serialization slot (two same-batch writers of one artifact:
        the second diffs against the first's content, exactly as the
        commits apply below)."""
        if self.chunks is None:
            return None
        n = self.config.n_agents
        masks = np.zeros((n, self.chunks.n_chunks_of(self.names[0])),
                         bool)
        pending: Dict[str, list] = {}
        for req in sorted(batch, key=lambda r: r.agent):
            if not req.is_write:
                continue
            name = self.names[req.artifact]
            cur = pending.get(name)
            if cur is None:
                cur = list(self.store.get(name))
            new = (list(req.content) if req.content is not None
                   else cur)
            masks[req.agent] = diff_chunks(cur, new,
                                           self.config.core.chunk_tokens)
            pending[name] = new
        return masks

    def _stage(self, batch: list) -> _Staged:
        n = self.config.n_agents
        with span("broker.stage"):
            acts = np.zeros(n, bool)
            arts = np.zeros(n, np.int32)
            writes = np.zeros(n, bool)
            t_submit = np.zeros(n)
            for req in batch:
                acts[req.agent] = True
                arts[req.agent] = req.artifact
                writes[req.agent] = req.is_write
                t_submit[req.agent] = req.t_submit
            wmasks = self._measure_write_masks(batch)
            state_before = (np.asarray(self.decider.arrays.state,
                                       np.int32).copy()
                            if self.telemetry is not None else None)
            queue_depth = len(batch) + len(self._pending)
            ver_before = np.asarray(self.decider.arrays.version,
                                    np.int64).copy()
        return _Staged(batch, acts, arts, writes, t_submit, wmasks,
                       state_before, queue_depth, ver_before)

    def _finish(self, staged: _Staged, decision, t_decide: float,
                busy_s: float) -> None:
        """Checks, responses and telemetry of a decided batch."""
        (batch, acts, arts, writes, t_submit, wmasks, state_before,
         queue_depth, ver_before) = staged
        tel = self.telemetry
        self.decide_busy_s += busy_s

        with span("broker.checks"):
            ver_after = np.asarray(self.decider.arrays.version, np.int64)
            if self.config.service.check_invariants:
                self._check_invariants(batch, ver_before, ver_after)

        with span("broker.respond"):
            # ledger: exact integer deltas from the decision engine
            for field, delta in decision.ledger_delta.items():
                setattr(self.ledger, field,
                        getattr(self.ledger, field) + delta)
            if decision.wire_delta is not None:
                for field, delta in decision.wire_delta.items():
                    self.wire[field] += delta

            # content plane + responses, in the authority's agent order
            # (reads at slot a see commits from slots < a, exactly the
            # order the decision plane serialized)
            now = time.perf_counter()
            latencies = {}
            for req in sorted(batch, key=lambda r: r.agent):
                name = self.names[req.artifact]
                version = int(decision.version[req.agent])
                latency = now - req.t_submit
                latencies[req.agent] = latency
                self.latencies.append(latency)
                if req.is_write:
                    content = (list(req.content)
                               if req.content is not None
                               else list(self.store.get(name)))
                    dirty = None
                    if self.chunks is not None:
                        self.chunks.put(name, content)
                        dirty = tuple(np.flatnonzero(wmasks[req.agent])
                                      .tolist())
                    else:
                        self.store.put(name, content)
                    self.bus.publish(Message(
                        "VERSION_UPDATE", f"agent-{req.agent}", name,
                        version, timestamp=now))
                    req.future.set_result(WriteResult(
                        version, latency, dirty_chunks=dirty))
                else:
                    delta = None
                    delta_bytes = -1
                    if self.chunks is not None:
                        fetched = np.flatnonzero(
                            decision.fetched_chunks[req.agent])
                        delta = self.chunks.delta(name, fetched)
                        delta_bytes = 0
                        if decision.miss[req.agent]:
                            delta_bytes = (sum(len(c) for _, c in delta)
                                           + acs.SIGNAL_TOKENS
                                           ) * BYTES_PER_TOKEN
                    req.future.set_result(ReadResult(
                        tuple(self.store.get(name)), version,
                        hit=not bool(decision.miss[req.agent]),
                        latency_s=latency, delta=delta,
                        delta_bytes=delta_bytes))
            self.n_batches += 1

        with span("broker.telemetry"):
            if self.config.service.capture_trace:
                self.trace.append_step(acts, arts, writes, decision.miss,
                                       decision.version, latencies,
                                       write_chunks=wmasks,
                                       decide_s=busy_s,
                                       batch_size=len(batch))
            if tel is not None:
                tel.record_batch(BatchObservation(
                    names=self.names, acts=acts, arts=arts,
                    writes=writes,
                    miss=np.asarray(decision.miss, bool),
                    version=np.asarray(decision.version, np.int64),
                    ledger_delta=decision.ledger_delta,
                    state_before=state_before,
                    state_after=np.asarray(self.decider.arrays.state,
                                           np.int32),
                    ver_after=ver_after,
                    wire_delta=decision.wire_delta,
                    shard=self.shard, live=True, busy_s=busy_s,
                    route=self.decider.backend, queue_depth=queue_depth,
                    t_decide=t_decide, t_respond=now,
                    t_submit=t_submit, latencies=latencies))
            if self._on_commit is not None:
                self._on_commit(self, {
                    "acts": acts, "arts": arts, "writes": writes,
                    "miss": decision.miss, "version": decision.version,
                    "latencies": latencies, "write_chunks": wmasks,
                    "busy_s": busy_s})

    # ------------------------------------------------------ invariants
    def _check_invariants(self, batch, ver_before, ver_after) -> None:
        state = np.asarray(self.decider.arrays.state)
        if not invariants.single_writer(state):
            raise InvariantViolation(
                f"SWMR violated: two M holders\n{state}")
        if not invariants.exclusive_means_alone(state):
            raise InvariantViolation(
                f"exclusivity violated\n{state}")
        if (state >= _E).any():
            raise InvariantViolation(
                f"E/M persisted past a committed batch\n{state}")
        if not invariants.monotonic_version(ver_before, ver_after):
            raise InvariantViolation(
                f"version regressed: {ver_before} -> {ver_after}")
        bumps = np.zeros(len(self.names), np.int64)
        for req in batch:
            if req.is_write:
                bumps[req.artifact] += 1
        if not np.array_equal(ver_after - ver_before, bumps):
            raise InvariantViolation(
                f"version bump mismatch: delta {ver_after - ver_before}"
                f" vs writes {bumps}")
        if self.config.core.max_stale_steps > 0:
            consumed = int(self.decider.metrics.max_consumed_staleness)
            if consumed > self.config.core.max_stale_steps:
                raise InvariantViolation(
                    f"K-staleness violated: served a hit "
                    f"{consumed} action-steps stale "
                    f"(K={self.config.core.max_stale_steps})")

    # ----------------------------------------------------------- stats
    @property
    def directory_state(self) -> np.ndarray:
        """(n_agents, n_artifacts) MESI matrix (live view)."""
        return np.asarray(self.decider.arrays.state, np.int32)

    @property
    def versions(self) -> np.ndarray:
        return np.asarray(self.decider.arrays.version, np.int32)

    def stats(self) -> dict:
        """The unified stats mapping (``repro.obs.stats``): the
        canonical nested schema."""
        return unified_stats(self)
