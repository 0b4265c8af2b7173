"""Micro-batching decision layer for the artifact-coherence broker.

The broker never decides one request at a time: in-flight read/write
requests are coalesced into a *micro-batch* (at most one per agent) and
resolved by ONE call into the coherence state machine - the service
analog of the fused sweep engine, which amortizes *compilation* across
a grid the way this layer amortizes *dispatch* across concurrent
clients.

Two interchangeable execution routes, both bit-exact with the
simulator (and therefore with the four-way differential oracle):

  ``scan``    one jitted ``acs.apply_actions`` call - literally the
              simulation's serialized agent pass, compiled once per
              static broker config (module-level jit cache, same
              pattern as ``repro.sim.engine``).  Covers every
              invalidation strategy plus K-staleness enforcement.
  ``pallas``  one ``kernels.mesi_transition.mesi_decision_dispatch`` pass:
              the MESI transition kernel on the single directory, which
              writes each request's fill bit and served version at its
              serialization slot.  Covers the differential strategies
              (lazy / eager / access_count) with ``max_stale_steps=0``;
              staleness diagnostics are scan-route-only, mirroring the
              oracle's Pallas scope note.  Its device work, and the
              content plane's chunk tick, are jitted programs built
              once per static config and shape, as the scan pass is.

``auto`` resolves to the kernel route on a real TPU backend (where the
sim engine also routes ticks through the kernel) and to ``scan``
elsewhere; a caller forces either with ``backend=``.

A decision is two halves on either route: ``dispatch`` stages the batch
and calls the device program, returning its outputs unread, and
``resolve`` reads them back and derives the outcomes.  ``decide`` runs
the two back to back; the sharded plane dispatches every shard's batch
before it resolves any, so the shards' programs run at once.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import acs
from repro.kernels.backend import interpret_default, resolve_interpret
from repro.kernels.chunk_diff import chunk_tick_pallas
from repro.kernels.mesi_transition import (mesi_decision_dispatch,
                                          mesi_decision_resolve)
from repro.obs.spans import span

#: strategies the kernel route supports (== oracle DIFFERENTIAL scope).
KERNEL_STRATEGIES = (acs.LAZY, acs.EAGER, acs.ACCESS_COUNT)

#: ACSMetrics content-plane counters forwarded as the wire-byte delta.
_WIRE_FIELDS = ("delta_bytes", "full_bytes", "n_chunks_fetched")


class BatchDecision(NamedTuple):
    """Host-side result of one coalesced decision pass."""

    miss: np.ndarray     # (n,) bool: request triggered a coherence fill
    version: np.ndarray  # (n,) int32: version served at the agent's slot
    ledger_delta: dict   # exact integer counter deltas for this batch
    #: (n, C) bool chunks each fill shipped (content plane; else None)
    fetched_chunks: np.ndarray | None = None
    #: exact byte-ledger deltas (content plane; else None)
    wire_delta: dict | None = None


class InFlight(NamedTuple):
    """A micro-batch :meth:`BatchDecider.dispatch` sent to the device
    and :meth:`BatchDecider.resolve` has not read back yet."""

    acts: np.ndarray
    arts: np.ndarray
    writes: np.ndarray
    write_chunks: np.ndarray | None
    #: the route's unread outputs: the scan pass's ``(counters before,
    #: outputs)``, the kernel route's ``DecisionInFlight``
    device: object


def _kernel_supported(cfg: acs.ACSConfig) -> bool:
    return (cfg.strategy in KERNEL_STRATEGIES
            and cfg.max_stale_steps == 0)


def resolve_decide_backend(cfg: acs.ACSConfig,
                           backend: str = "auto") -> str:
    """'scan' | 'pallas' for a broker with static config ``cfg``."""
    if backend == "scan":
        return "scan"
    if backend == "pallas":
        if not _kernel_supported(cfg):
            raise ValueError(
                "pallas decision route covers lazy/eager/access_count "
                "with max_stale_steps=0; use backend='scan' for "
                f"strategy={acs.STRATEGY_NAMES[cfg.strategy]} "
                f"max_stale_steps={cfg.max_stale_steps}")
        return "pallas"
    if backend != "auto":
        raise ValueError(f"unknown decision backend {backend!r}")
    return ("pallas" if not interpret_default() and _kernel_supported(cfg)
            else "scan")


@functools.lru_cache(maxsize=None)
def _scan_decider(cfg: acs.ACSConfig):
    """One compiled serialized-authority pass per static broker config;
    every micro-batch of the broker's lifetime reuses it.  For chunked
    configs the pass also carries the content plane (the per-agent
    dirty chunk masks become a traced operand)."""

    if acs.content_enabled(cfg):
        def fn(arrays, met, acts, arts, writes, write_chunks):
            return acs.apply_actions(cfg, arrays, met, acts, arts,
                                     writes, write_chunks=write_chunks)
    else:
        def fn(arrays, met, acts, arts, writes):
            return acs.apply_actions(cfg, arrays, met, acts, arts,
                                     writes)

    return jax.jit(fn)


@functools.partial(jax.jit, static_argnames=(
    "artifact_tokens", "chunk_tokens", "signal_tokens", "interpret"))
def _chunk_decider(chunk_version, chunk_sync, chunk_dirty, miss,
                   write_acts, arts, write_chunks, *, artifact_tokens,
                   chunk_tokens, signal_tokens, interpret):
    """The content plane's chunk tick on the single directory (one sim),
    compiled once per static config and shape like ``_scan_decider``.
    Returns ``chunk_tick_pallas``'s outputs without the sim axis."""
    out = chunk_tick_pallas(
        chunk_version[None], chunk_sync[None], chunk_dirty[None],
        miss[None], write_acts[None], arts[None], write_chunks[None],
        artifact_tokens=artifact_tokens, chunk_tokens=chunk_tokens,
        signal_tokens=signal_tokens, interpret=interpret)
    return tuple(o[0] for o in out)


#: ACSMetrics counter fields forwarded into the broker's token ledger.
_LEDGER_FIELDS = ("fetch_tokens", "push_tokens", "signal_tokens",
                  "n_fetches", "n_hits", "n_reads", "n_writes",
                  "n_invalidation_signals")

#: kernel counter slot -> ledger field (mesi_transition layout).
_KERNEL_SLOTS = {"fetch_tokens": 0, "signal_tokens": 1, "push_tokens": 2,
                 "n_fetches": 3, "n_hits": 4,
                 "n_invalidation_signals": 5}


class BatchDecider:
    """Stateful decision engine: owns the directory arrays and applies
    one coalesced micro-batch per call.

    The broker is the *single writer* of this state - only the flush
    task calls :meth:`decide` (or its halves), which is what makes SWMR
    hold under true asyncio interleaving (enforced with a reentrancy
    guard that holds from a dispatch to its resolve, checked by the
    invariant suite after every batch).
    """

    def __init__(self, cfg: acs.ACSConfig, backend: str = "auto",
                 device=None) -> None:
        self.cfg = cfg
        self.backend = resolve_decide_backend(cfg, backend)
        self.arrays = acs.init_arrays(cfg)
        self.metrics = acs.init_metrics()
        #: device this authority's directory lives on.  The sharded
        #: plane pins each shard's decider to its own device of the
        #: sweep mesh (``launch.mesh.shard_devices``), so every shard's
        #: serialized pass runs as its own device program - the
        #: service-plane analog of the sharded sweep grids.
        self.device = device
        if device is not None:
            self.arrays = jax.device_put(self.arrays, device)
            self.metrics = jax.device_put(self.metrics, device)
        self._scan = _scan_decider(cfg) if self.backend == "scan" else None
        self._deciding = False

    # ------------------------------------------------------------------
    def decide(self, acts: np.ndarray, arts: np.ndarray,
               writes: np.ndarray,
               write_chunks: np.ndarray | None = None) -> BatchDecision:
        """Resolve one micro-batch (at most one request per agent).

        ``write_chunks`` (n, C) bool is required for chunked configs:
        the *measured* dirty chunk mask of each write in the batch
        (the broker diffs actual content digests)."""
        return self.resolve(self.dispatch(acts, arts, writes,
                                          write_chunks))

    def dispatch(self, acts: np.ndarray, arts: np.ndarray,
                 writes: np.ndarray,
                 write_chunks: np.ndarray | None = None) -> InFlight:
        """The first half of :meth:`decide`: stage the batch and call
        the device program.  Its outputs stay on the device until
        :meth:`resolve`; no other batch may be dispatched before."""
        if self._deciding:
            raise RuntimeError(
                "re-entrant decide(): the broker's single-writer "
                "discipline was violated")
        if acs.content_enabled(self.cfg) and write_chunks is None:
            raise ValueError("chunked decider needs write_chunks masks")
        self._deciding = True
        try:
            route = (self._dispatch_scan if self.backend == "scan"
                     else self._dispatch_pallas)
            return InFlight(acts, arts, writes, write_chunks,
                            route(acts, arts, writes, write_chunks))
        except BaseException:
            self._deciding = False
            raise

    def resolve(self, flight: InFlight) -> BatchDecision:
        """The second half of :meth:`decide`: read the dispatched
        batch's outputs back and derive its outcomes."""
        try:
            if self.backend == "scan":
                return self._resolve_scan(flight)
            return self._resolve_pallas(flight)
        finally:
            self._deciding = False

    # ------------------------------------------------------------------
    # The phases below are with-blocks in place: stage (host arrays to
    # the device), call (the jitted pass or kernel, until it returns:
    # trace, lower, compile or load, dispatch), readback (outputs to the
    # host: device wait and transfer) and outcomes (host derivation of
    # per-request outcomes and ledger deltas).
    def _dispatch_scan(self, acts, arts, writes, write_chunks):
        content = acs.content_enabled(self.cfg)
        fields = _LEDGER_FIELDS + (_WIRE_FIELDS if content else ())
        with span("broker.decide.readback"):
            before = {f: int(getattr(self.metrics, f)) for f in fields}
        with span("broker.decide.stage"):
            batch = [jnp.asarray(acts, bool), jnp.asarray(arts, jnp.int32),
                     jnp.asarray(writes, bool)]
            if content:
                batch.append(jnp.asarray(write_chunks, bool))
        with span("broker.decide.call"):
            # the previous directory is released here, not at return
            self.arrays, self.metrics, out = self._scan(
                self.arrays, self.metrics, *batch)
            del batch
        return before, out

    def _resolve_scan(self, flight: InFlight) -> BatchDecision:
        content = acs.content_enabled(self.cfg)
        fields = _LEDGER_FIELDS + (_WIRE_FIELDS if content else ())
        before, out = flight.device
        with span("broker.decide.readback"):
            after = {f: int(getattr(self.metrics, f)) for f in fields}
            miss = np.asarray(out.miss, bool)
            version = np.asarray(out.version, np.int32)
            fetched = (np.asarray(out.fetched_chunks, bool)
                       if content else None)
            del out
        with span("broker.decide.outcomes"):
            delta = {f: after[f] - before[f] for f in _LEDGER_FIELDS}
            wire = ({f: after[f] - before[f] for f in _WIRE_FIELDS}
                    if content else None)
            return BatchDecision(miss=miss, version=version,
                                 ledger_delta=delta,
                                 fetched_chunks=fetched, wire_delta=wire)

    def _dispatch_pallas(self, acts, arts, writes, write_chunks):
        a = self.arrays
        # mesi_decision_dispatch times its own stage and call
        return mesi_decision_dispatch(
            a.state, a.version, a.last_sync, a.reads_since_fetch,
            np.asarray(acts, bool), np.asarray(arts, np.int32),
            np.asarray(writes, bool),
            artifact_tokens=self.cfg.artifact_tokens,
            eager=self.cfg.strategy == acs.EAGER,
            access_k=(self.cfg.access_k
                      if self.cfg.strategy == acs.ACCESS_COUNT else 0),
            signal_tokens=acs.SIGNAL_TOKENS)

    def _resolve_pallas(self, flight: InFlight) -> BatchDecision:
        acts, arts, writes, write_chunks = flight[:4]
        a = self.arrays
        # mesi_decision_resolve times its own readback
        st, ver, sy, rd, cnt, miss, served = mesi_decision_resolve(
            flight.device)
        acts_np = np.asarray(acts, bool)
        writes_np = np.asarray(writes, bool)
        with span("broker.decide.outcomes"):
            delta = {f: int(cnt[slot])
                     for f, slot in _KERNEL_SLOTS.items()}
            # the kernel tracks token counters only; action counts come
            # from the batch itself (same derivation as
            # oracle.replay_pallas).
            delta["n_reads"] = int((acts_np & ~writes_np).sum())
            delta["n_writes"] = int((acts_np & writes_np).sum())
            # agent_actions is a scan-path diagnostic (staleness clocks);
            # each acting agent performed exactly one action this batch.
            self.arrays = a._replace(
                state=st, version=ver, last_sync=sy,
                reads_since_fetch=rd,
                agent_actions=a.agent_actions + jnp.asarray(acts_np,
                                                            jnp.int32))
            del a       # the previous directory is released here
            self.metrics = self.metrics._replace(**{
                f: getattr(self.metrics, f) + delta[f]
                for f in _LEDGER_FIELDS})
        fetched = wire = None
        if acs.content_enabled(self.cfg):
            # Content plane rides the same serialization order: the
            # chunk tick consumes the per-request miss bits and the
            # measured dirty masks.
            with span("broker.decide.stage"):
                wact = (acts_np & writes_np).astype(np.int32)
                chunk_args = (
                    self.arrays.chunk_version, self.arrays.chunk_sync,
                    self.arrays.chunk_dirty, miss.astype(np.int32),
                    wact, np.asarray(arts, np.int32),
                    np.asarray(write_chunks, np.int32))
            with span("broker.decide.call"):
                cv, cs, dirty, fetched_c, ccnt = _chunk_decider(
                    *chunk_args,
                    artifact_tokens=self.cfg.artifact_tokens,
                    chunk_tokens=self.cfg.chunk_tokens,
                    signal_tokens=acs.SIGNAL_TOKENS,
                    interpret=resolve_interpret(None))
            with span("broker.decide.readback"):
                ccnt_np = np.asarray(ccnt, np.int64)
                fetched = np.asarray(fetched_c, bool)
            with span("broker.decide.outcomes"):
                self.arrays = self.arrays._replace(
                    chunk_version=cv, chunk_sync=cs, chunk_dirty=dirty)
                wire = {"delta_bytes": int(ccnt_np[0]),
                        "full_bytes": int(ccnt_np[1]),
                        "n_chunks_fetched": int(ccnt_np[2])}
                self.metrics = self.metrics._replace(**{
                    f: getattr(self.metrics, f) + wire[f]
                    for f in _WIRE_FIELDS})
        return BatchDecision(miss=miss, version=served,
                             ledger_delta=delta, fetched_chunks=fetched,
                             wire_delta=wire)
