"""Tick-based discrete-event simulation engine (paper SS8).

Fleet-scale sweep architecture: an entire ``(variant x volatility x
run)`` evaluation grid compiles **once** and runs as **one** batched XLA
program - and on a multi-device host that single program is
**device-sharded** with ``jax.shard_map`` over a 1-D mesh
(``repro.launch.mesh.make_sweep_mesh``), so an 8-device host executes 8
grid slices of the same compiled program in parallel.  Four mechanisms
make that possible:

  1. **Traced sweep axes.**  ``volatility`` and ``p_act`` (and the PRNG
     key, as always) are traced scalars of the episode runner
     (``repro.core.acs.run_episode``), so a single compiled program
     covers every point of a volatility sweep - and the heterogeneous
     generalization (``compare_workloads``) traces whole per-agent x
     per-artifact rate matrices the same way, so one program covers an
     entire zoo of workload families.  Strategy and the
     shape-determining fields (agents, artifacts, steps) stay static -
     they select code, not data.
  2. **Module-level jit cache.**  Compiled grid programs are cached per
     static ``ACSConfig`` signature (``_static_key``), so repeated
     ``run_scenario`` / ``compare`` calls never retrace.  The cache is
     instrumented (``trace_count``) so benchmarks and tests can assert
     the one-compilation property.
  3. **Fused baseline.**  ``compare`` / ``sweep_volatility`` stack the
     broadcast baseline and the coherent variant along a leading variant
     axis *inside* the same jitted program - one launch, not two.
  4. **Device sharding with a global key schedule.**  When more than
     one local device is attached (every local device by default; cap
     the count with the ``devices=`` argument), the grid program is
     wrapped in ``shard_map`` over a 1-D mesh: the ``runs`` axis is
     sharded (falling back to the ``workloads`` / scenario-cell axis,
     else padding runs - ``shard_plan``).  Episode
     keys are derived *inside* the program by ``acs.run_keys`` -
     ``fold_in`` on the **global** run index carried by a sharded
     ``run_ids`` operand, never on device-local position - so sharded
     ledgers are bit-identical to the single-device path and replayable
     through the ``repro.sim.oracle`` conformance harness.  The key
     operands are donated to the program (freshly built every call, so
     XLA may reuse their buffers for episode state).

Per-tick MESI transitions route through the Pallas kernel
(``repro.kernels.mesi_transition``) when a real TPU backend is attached
and the flattened batch is large enough to fill it; otherwise the
vectorized ``lax.scan`` path (vmapped ``acs.run_episode``) is used.
Force either with the ``tick_backend=`` argument.  Under ``shard_map``
the kernel is invoked per device on that device's slice of the episode
batch.

Population statistics (mean, population std) are reported exactly as the
paper does (10 runs, sigma over the population).
"""

from __future__ import annotations

import contextlib
import dataclasses
import warnings
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.content.chunks import BYTES_PER_TOKEN
from repro.core import acs
from repro.core.states import MESIState
from repro.kernels.backend import interpret_default
from repro.kernels.chunk_diff import (N_CHUNK_COUNTERS,
                                      chunk_tick_pallas)
from repro.kernels.mesi_transition import (N_COUNTERS, episode_step_keys,
                                           mesi_tick_pallas)
from repro.launch.mesh import make_sweep_mesh
from repro.obs import runtime as obs_runtime
from repro.obs.spans import span
from repro.sim.scenarios import ScenarioConfig

# ---------------------------------------------------------------------------
# Compilation accounting.  ``_note_trace`` runs as a Python side effect at
# *trace* time only, so the counter increments once per compiled program
# (and once more per shape-driven retrace) - never per execution.

_TRACE_COUNT = 0


def _note_trace() -> None:
    global _TRACE_COUNT
    _TRACE_COUNT += 1


def trace_count() -> int:
    """Number of sweep/episode program compilations since last reset."""
    return _TRACE_COUNT


def reset_trace_count() -> None:
    global _TRACE_COUNT
    _TRACE_COUNT = 0


class TraceCounter:
    """Compilations observed since a fixed starting point (see
    ``trace_counter``)."""

    def __init__(self, start: int) -> None:
        self._start = start

    @property
    def count(self) -> int:
        return _TRACE_COUNT - self._start


@contextlib.contextmanager
def trace_counter(clear_cache: bool = True):
    """Scoped compilation accounting.

    ``trace_count`` is process-global: a bare ``reset_trace_count()`` in
    one test module stomps the accounting every other module sees, so
    recompile-guard assertions become import-order dependent.  This
    context manager yields a ``TraceCounter`` whose ``.count`` is the
    number of compilations *inside the with-block only* - no reset, no
    cross-module leak.  ``clear_cache=True`` (default) also drops the
    jit caches on entry so the block starts cold.
    """
    if clear_cache:
        clear_compile_cache()
    yield TraceCounter(_TRACE_COUNT)


# ---------------------------------------------------------------------------
# Static signature + jit cache.

#: ACSConfig fields baked into compiled code.  ``volatility``,
#: ``p_act`` and ``write_locality`` are deliberately absent: they are
#: traced sweep axes.  ``chunk_tokens`` is static (it sets the chunk
#: axis shape); one compiled program covers every locality x
#: volatility x family point of a given chunk geometry.
_STATIC_FIELDS = ("n_agents", "n_artifacts", "artifact_tokens", "n_steps",
                  "strategy", "ttl_events", "access_k", "max_stale_steps",
                  "chunk_tokens")

_GRID_CACHE: dict = {}

#: Minimum flattened episode batch before the Pallas tick path pays off
#: on TPU (below this the grid underfills the VPU slabs).
PALLAS_MIN_BATCH = 256

_PALLAS_STRATEGIES = (acs.LAZY, acs.EAGER, acs.ACCESS_COUNT)

_I = int(MESIState.I)


def _static_key(cfg: acs.ACSConfig) -> tuple:
    return tuple(getattr(cfg, f) for f in _STATIC_FIELDS)


def clear_compile_cache() -> None:
    """Drop cached grid programs (benchmarks measuring cold compiles)."""
    _GRID_CACHE.clear()
    jax.clear_caches()


def _pallas_tick_supported(cfg: acs.ACSConfig) -> bool:
    """The batched MESI kernel implements the invalidation strategies
    (lazy / eager / access-count) without K-staleness enforcement;
    broadcast and TTL are bulk-inject paths with no per-agent kernel."""
    return cfg.strategy in _PALLAS_STRATEGIES and cfg.max_stale_steps == 0


def resolve_tick_backend(cfg: acs.ACSConfig, batch: int) -> str:
    """'pallas' | 'scan' for a grid of ``batch`` flattened episodes."""
    if (not interpret_default() and _pallas_tick_supported(cfg)
            and batch >= PALLAS_MIN_BATCH):
        return "pallas"
    return "scan"


# ---------------------------------------------------------------------------
# Device sharding.  Sweep grids are embarrassingly parallel along their
# batch axes; ``shard_plan`` picks which axis a given grid shards over.


class ShardPlan(NamedTuple):
    """How one grid call maps onto the device mesh.

    ``axis`` is ``None`` (unsharded single-device program), ``"runs"``
    (run axis sharded) or ``"workloads"`` (scenario/workload cell axis
    sharded).  ``pad_runs`` is the padded run-axis length the program
    sees; padding runs is the always-available fallback because run
    keys are derived from **global** run indices, so extra trailing
    runs are real (discarded) episodes, not perturbed ones.
    """

    devices: int
    axis: Optional[str]
    pad_runs: int


def shard_plan(n_cells: int, n_runs: int,
               devices: Optional[int] = None) -> ShardPlan:
    """Pick the mesh axis for an ``(n_cells x n_runs)`` grid.

    Preference order: shard ``runs`` when it divides the device count,
    else shard the cell (``workloads``) axis when that divides, else
    pad ``runs`` up to the next multiple and shard it (the padded tail
    is sliced off on the host).  ``devices=None`` plans over every
    local device; on a single-device host that is the plain-jit path.
    """
    n_local = jax.local_device_count()
    devices = (n_local if devices is None
               else max(1, min(devices, n_local)))
    if devices <= 1:
        return ShardPlan(1, None, n_runs)
    if n_runs % devices == 0:
        return ShardPlan(devices, "runs", n_runs)
    if n_cells % devices == 0:
        return ShardPlan(devices, "workloads", n_runs)
    pad = -n_runs % devices
    return ShardPlan(devices, "runs", n_runs + pad)


def _shard_wrap(run_grid, plan: ShardPlan, n_cell_operands: int,
                n_key_operands: int = 2):
    """Wrap a grid program per the plan and jit it.

    Operand convention: ``n_cell_operands`` leading operands carry the
    cell axis (volatilities / rate matrices / base keys), then
    ``run_ids`` last.  Outputs are ``(variant, cell, run)`` stacks.
    The trailing ``n_key_operands`` operands (base keys + run ids) are
    donated - they are rebuilt host-side on every call.
    """
    n_args = n_cell_operands + 1
    donate = tuple(range(n_args - n_key_operands, n_args))
    if plan.axis is None:
        return jax.jit(run_grid, donate_argnums=donate)
    mesh = make_sweep_mesh(plan.devices, plan.axis)
    if plan.axis == "runs":
        in_specs = (P(),) * n_cell_operands + (P("runs"),)
        out_specs = P(None, None, "runs")
    else:
        in_specs = (P("workloads"),) * n_cell_operands + (P(),)
        out_specs = P(None, "workloads", None)
    return jax.jit(
        # check_vma off: the grid body is collective-free (episodes are
        # independent) and pallas_call has no varying-axes rule
        jax.shard_map(run_grid, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False),
        donate_argnums=donate)


def _call_grid(fn, *args) -> dict:
    """Execute a compiled grid program and gather to host: the call
    until it returns (``sweep.dispatch``: trace, lower, compile or load
    on a cold shape, then dispatch) and the gather (``sweep.readback``:
    device wait and transfer).

    The donated key operands rarely alias an output buffer on CPU
    (dtype/shape mismatch), and XLA warns about every unusable
    donation at compile time; that warning is noise here - donation is
    an upper bound the backend may use, not a promise - so it is
    silenced for the call.
    """
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        with span("sweep.dispatch"):
            out = fn(*args)
    with span("sweep.readback"):
        return jax.device_get(out)


# ---------------------------------------------------------------------------
# Result containers (unchanged public shape).


@dataclasses.dataclass(frozen=True)
class RunStats:
    """Per-configuration population statistics over n_runs.

    ``max_staleness_max`` / ``max_version_lag_max`` are ``-1`` when the
    episodes ran on the Pallas tick path, which does not track staleness
    diagnostics (use ``tick_backend="scan"`` to audit them).
    """

    name: str
    strategy: str
    n_runs: int
    total_tokens_mean: float
    total_tokens_std: float
    sync_tokens_mean: float
    sync_tokens_std: float
    fetch_tokens_mean: float
    signal_tokens_mean: float
    push_tokens_mean: float
    broadcast_tokens_mean: float
    cache_hit_rate_mean: float
    cache_hit_rate_std: float
    n_fetches_mean: float
    n_writes_mean: float
    n_reads_mean: float
    max_staleness_max: int
    max_version_lag_max: int
    #: worst staleness a served cache hit carried (post-revalidation);
    #: ``-1`` on the Pallas tick path (not tracked there).
    max_consumed_staleness_max: int = -1
    #: content-plane bytes-on-wire (``-1`` when ``chunk_tokens == 0``):
    #: delta = what chunk coherence shipped, full = what whole-artifact
    #: lazy would ship for the same miss sequence.
    delta_bytes_mean: float = -1.0
    full_bytes_mean: float = -1.0
    n_chunks_fetched_mean: float = -1.0

    def savings_vs(self, baseline: "RunStats") -> float:
        return 1.0 - self.total_tokens_mean / baseline.total_tokens_mean

    def savings_std_vs(self, baseline: "RunStats",
                       per_run_tokens: np.ndarray,
                       baseline_mean: Optional[float] = None) -> float:
        b = baseline.total_tokens_mean if baseline_mean is None \
            else baseline_mean
        return float(np.std(1.0 - per_run_tokens / b))


@dataclasses.dataclass(frozen=True)
class RunResult:
    stats: RunStats
    per_run_total_tokens: np.ndarray  # (n_runs,)
    per_run_chr: np.ndarray


@dataclasses.dataclass(frozen=True)
class Comparison:
    """Coherent strategy vs broadcast baseline for one scenario."""

    scenario: str
    volatility: float
    strategy: str
    broadcast: RunStats
    coherent: RunStats
    savings_mean: float
    savings_std: float
    crr: float           # Coherence Reduction Ratio (SS8.2)
    chr_mean: float
    chr_std: float
    #: per-run total tokens of the coherent variant, in run order
    coherent_per_run_tokens: tuple = ()


# ---------------------------------------------------------------------------
# Episode programs.


def _episode_metrics(cfg: acs.ACSConfig, key: jax.Array,
                     volatility=None, p_act=None, rates=None,
                     locality=None) -> dict:
    met = acs.run_episode(cfg, key, volatility=volatility, p_act=p_act,
                          rates=rates, locality=locality)
    out = {
        "total_tokens": met.total_tokens,
        "sync_tokens": met.sync_tokens,
        "fetch_tokens": met.fetch_tokens,
        "signal_tokens": met.signal_tokens,
        "push_tokens": met.push_tokens,
        "broadcast_tokens": met.broadcast_tokens,
        "cache_hit_rate": met.cache_hit_rate,
        "n_fetches": met.n_fetches,
        "n_writes": met.n_writes,
        "n_reads": met.n_reads,
        "max_staleness": met.max_staleness,
        "max_version_lag": met.max_version_lag,
        "max_consumed_staleness": met.max_consumed_staleness,
    }
    if acs.content_enabled(cfg):
        out["delta_bytes"] = met.delta_bytes
        out["full_bytes"] = met.full_bytes
        out["n_chunks_fetched"] = met.n_chunks_fetched
    return out


def _broadcast_content_fill(cfg: acs.ACSConfig, out: dict) -> dict:
    """Analytic bytes-on-wire of the broadcast baseline (content-plane
    grids only): every step injects every artifact into every agent,
    so delta and whole-artifact accounting coincide - ``n_steps * n *
    m * (|d| + signal)`` bytes, exactly mirroring the token-ledger's
    ``broadcast_tokens`` accumulation."""
    per_ep = (cfg.n_steps * cfg.n_agents * cfg.n_artifacts
              * (cfg.artifact_tokens + acs.SIGNAL_TOKENS)
              * BYTES_PER_TOKEN)
    like = out["total_tokens"]
    out = dict(out)
    out["delta_bytes"] = jnp.full_like(like, per_ep)
    out["full_bytes"] = jnp.full_like(like, per_ep)
    out["n_chunks_fetched"] = jnp.full_like(
        like, cfg.n_steps * cfg.n_agents * cfg.n_artifacts
        * acs.content_chunks(cfg))
    return out


def _episodes_pallas(cfg: acs.ACSConfig, keys: jax.Array, vols: jax.Array,
                     p_acts: jax.Array,
                     rates: Optional[acs.RateMatrices] = None,
                     locs: Optional[jax.Array] = None) -> dict:
    """B episodes through the batched Pallas MESI tick.

    ``keys`` (B, 2) uint32, ``vols`` / ``p_acts`` (B,) traced scalars,
    ``rates`` an optional batched ``RateMatrices`` ((B, n) / (B, n, m)
    leaves; overrides the scalars - the heterogeneous workload route),
    ``locs`` the (B,) traced write-locality scalars (content plane
    only).  Returns the metrics dict of (B,) arrays.  Staleness
    diagnostics (``max_staleness`` / ``max_version_lag`` /
    ``max_consumed_staleness``) are not tracked by the kernel and
    report the ``-1`` not-tracked sentinel - this is the throughput
    path for token-traffic metrics; use the scan path when auditing
    staleness invariants.  With the content plane enabled, every MESI
    tick is chased by one ``chunk_tick_pallas`` call fed the MESI
    kernel's per-agent miss output - same serialization order, so the
    byte ledger is bit-identical to the scan path.
    """
    B = keys.shape[0]
    n, m = cfg.n_agents, cfg.n_artifacts
    content = acs.content_enabled(cfg)
    C = acs.content_chunks(cfg) if content else 0
    step_keys = episode_step_keys(keys, cfg.n_steps)  # (S, B, 2)

    def draw(k, v, p, r):
        # acs.draw_actions is the single sampling source of truth, so
        # the action streams (and hence all token counters) match the
        # scan path bit-for-bit.
        a, d, w = acs.draw_actions(k, n, m, v, p, r)
        return a.astype(jnp.int32), d, w.astype(jnp.int32)

    def body(carry, ks):
        (state, version, sync, reads, counters, n_reads, n_writes,
         cv, cs, dirty, ccounters) = carry
        if rates is None:
            a, d, w = jax.vmap(
                lambda k, v, p: draw(k, v, p, None))(ks, vols, p_acts)
        else:
            a, d, w = jax.vmap(
                lambda k, r: draw(k, None, None, r))(ks, rates)
        state, version, sync, reads, cnt, miss = mesi_tick_pallas(
            state, version, sync, reads, a, d, w,
            artifact_tokens=cfg.artifact_tokens,
            eager=cfg.strategy == acs.EAGER,
            access_k=cfg.access_k
            if cfg.strategy == acs.ACCESS_COUNT else 0,
            signal_tokens=acs.SIGNAL_TOKENS)
        counters = counters + cnt
        n_reads = n_reads + jnp.sum(a * (1 - w), axis=1)
        n_writes = n_writes + jnp.sum(a * w, axis=1)
        if content:
            wch = jax.vmap(
                lambda k, loc: acs.draw_write_chunks(k, n, C, loc)
            )(ks, locs).astype(jnp.int32)
            cv, cs, dirty, _, ccnt = chunk_tick_pallas(
                cv, cs, dirty, miss, a * w, d, wch,
                artifact_tokens=cfg.artifact_tokens,
                chunk_tokens=cfg.chunk_tokens,
                signal_tokens=acs.SIGNAL_TOKENS)
            ccounters = ccounters + ccnt
        return (state, version, sync, reads, counters,
                n_reads, n_writes, cv, cs, dirty, ccounters), None

    init = (
        jnp.full((B, n, m), _I, jnp.int32),
        jnp.ones((B, m), jnp.int32),
        jnp.zeros((B, n, m), jnp.int32),
        jnp.zeros((B, n, m), jnp.int32),
        jnp.zeros((B, N_COUNTERS), jnp.int32),
        jnp.zeros((B,), jnp.int32),
        jnp.zeros((B,), jnp.int32),
        jnp.ones((B, m, C), jnp.int32) if content else None,
        jnp.zeros((B, n, m, C), jnp.int32) if content else None,
        jnp.zeros((B, m, C), jnp.int32) if content else None,
        jnp.zeros((B, N_CHUNK_COUNTERS), jnp.int32) if content else None,
    )
    (_, _, _, _, counters, n_reads, n_writes, _, _, _, ccounters), _ = \
        jax.lax.scan(body, init, step_keys)

    fetch, signal, push = counters[:, 0], counters[:, 1], counters[:, 2]
    n_fetches, n_hits = counters[:, 3], counters[:, 4]
    z = jnp.zeros((B,), jnp.int32)
    untracked = jnp.full((B,), -1, jnp.int32)   # sentinel, see docstring
    denom = jnp.maximum(n_hits + n_fetches, 1)
    out = {
        "total_tokens": fetch + signal + push,
        "sync_tokens": fetch + signal,
        "fetch_tokens": fetch,
        "signal_tokens": signal,
        "push_tokens": push,
        "broadcast_tokens": z,
        "cache_hit_rate": n_hits.astype(jnp.float32) / denom,
        "n_fetches": n_fetches,
        "n_writes": n_writes,
        "n_reads": n_reads,
        "max_staleness": untracked,
        "max_version_lag": untracked,
        "max_consumed_staleness": untracked,
    }
    if content:
        out["delta_bytes"] = ccounters[:, 0]
        out["full_bytes"] = ccounters[:, 1]
        out["n_chunks_fetched"] = ccounters[:, 2]
    return out


def _grid_fn(cfg: acs.ACSConfig, include_broadcast: bool,
             tick_backend: str, plan: ShardPlan):
    """Cached (possibly device-sharded) grid program for one static
    configuration.

    Signature of the returned callable::

        fn(vols (V,), p_acts (V,), base_keys (V, 2), run_ids (R,))
            -> dict of (n_variants, V, R) arrays

    Episode keys are derived in-program as ``fold_in(base_keys[v],
    run_ids[r])`` (``acs.run_keys``).  ``run_ids`` carries global run
    indices, so when the plan shards the ``runs`` axis each device
    still derives the exact keys of the single-device schedule for its
    slice.  Variant axis: ``[broadcast, coherent]`` when
    ``include_broadcast``, else ``[coherent]`` - the baseline runs
    *inside* the same XLA program as the coherent variant (one
    compilation, one launch, every device).
    """
    if tick_backend == "pallas" and not _pallas_tick_supported(cfg):
        # The kernel only implements the invalidation strategies; a
        # forced "pallas" on TTL/broadcast/K-staleness configs would
        # silently compute lazy semantics.
        tick_backend = "scan"
    # the FULL resolved plan is part of the key: two plans over the same
    # devices/axis can still pad the run axis differently (pad_runs), and
    # a stale hit would silently run the wrong grid padding
    cache_key = (_static_key(cfg), include_broadcast, tick_backend, plan)
    fn = _GRID_CACHE.get(cache_key)
    if fn is not None:
        return fn
    content = acs.content_enabled(cfg)
    # Broadcast has no content plane (bulk injection ships everything);
    # its byte columns are filled analytically below.
    bc_cfg = dataclasses.replace(cfg, strategy=acs.BROADCAST,
                                 chunk_tokens=0)

    def scan_variant(vcfg, vols, p_acts, locs, keys):
        def cell(v, p, loc, ks):
            return jax.vmap(lambda k: _episode_metrics(
                vcfg, k, v, p, locality=loc))(ks)
        return jax.vmap(cell)(vols, p_acts, locs, keys)

    def pallas_variant(vcfg, vols, p_acts, locs, keys):
        V, R = keys.shape[0], keys.shape[1]
        out = _episodes_pallas(
            vcfg, keys.reshape(V * R, keys.shape[2]),
            jnp.repeat(vols, R), jnp.repeat(p_acts, R),
            locs=jnp.repeat(locs, R) if content else None)
        return {k: a.reshape(V, R) for k, a in out.items()}

    coherent = pallas_variant if tick_backend == "pallas" else scan_variant

    def run_grid(*args):
        if content:
            vols, p_acts, locs, base_keys, run_ids = args
        else:
            vols, p_acts, base_keys, run_ids = args
            locs = jnp.zeros_like(vols)
        _note_trace()
        keys = jax.vmap(lambda bk: acs.run_keys(bk, run_ids))(base_keys)
        outs = []
        if include_broadcast:
            # Broadcast is a bulk-inject path with no per-agent kernel;
            # it always takes the scan variant.
            bc = scan_variant(bc_cfg, vols, p_acts, locs, keys)
            if content:
                bc = _broadcast_content_fill(cfg, bc)
            outs.append(bc)
        outs.append(coherent(cfg, vols, p_acts, locs, keys))
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *outs)

    fn = _shard_wrap(run_grid, plan, n_cell_operands=4 if content else 3)
    _GRID_CACHE[cache_key] = fn
    return fn


def _het_grid_fn(cfg: acs.ACSConfig, include_broadcast: bool,
                 tick_backend: str, plan: ShardPlan):
    """Cached (possibly device-sharded) grid program for heterogeneous
    (rate-matrix) workloads sharing one static configuration.

    Signature of the returned callable::

        fn(rates: RateMatrices with (W, n) / (W, n, m) leaves,
           base_keys (W, 2), run_ids (R,))
            -> dict of (n_variants, W, R) arrays

    The rate matrices are *traced* tensor axes: one compilation covers
    every workload family of the same static shape, and re-running with
    different rates (new families, perturbed skews) retraces nothing.
    Key derivation and sharding exactly as ``_grid_fn``; the
    ``workloads`` fallback shards the leading W axis of every rate
    leaf.  Variant axis exactly as ``_grid_fn``.
    """
    if tick_backend == "pallas" and not _pallas_tick_supported(cfg):
        tick_backend = "scan"
    cache_key = ("het", _static_key(cfg), include_broadcast, tick_backend,
                 plan)   # full plan: see _grid_fn (pad_runs matters)
    fn = _GRID_CACHE.get(cache_key)
    if fn is not None:
        return fn
    content = acs.content_enabled(cfg)
    bc_cfg = dataclasses.replace(cfg, strategy=acs.BROADCAST,
                                 chunk_tokens=0)

    def scan_variant(vcfg, rates, locs, keys):
        def cell(r, loc, ks):
            return jax.vmap(lambda k: _episode_metrics(
                vcfg, k, rates=r, locality=loc))(ks)
        return jax.vmap(cell)(rates, locs, keys)

    def pallas_variant(vcfg, rates, locs, keys):
        W, R = keys.shape[0], keys.shape[1]
        flat = jax.tree_util.tree_map(
            lambda x: jnp.repeat(x, R, axis=0), rates)
        out = _episodes_pallas(
            vcfg, keys.reshape(W * R, keys.shape[2]),
            None, None, rates=flat,
            locs=jnp.repeat(locs, R) if content else None)
        return {k: a.reshape(W, R) for k, a in out.items()}

    coherent = pallas_variant if tick_backend == "pallas" else scan_variant

    def run_grid(*args):
        if content:
            rates, locs, base_keys, run_ids = args
        else:
            rates, base_keys, run_ids = args
            locs = jnp.zeros_like(rates.p_act[..., 0])
        _note_trace()
        keys = jax.vmap(lambda bk: acs.run_keys(bk, run_ids))(base_keys)
        outs = []
        if include_broadcast:
            bc = scan_variant(bc_cfg, rates, locs, keys)
            if content:
                bc = _broadcast_content_fill(cfg, bc)
            outs.append(bc)
        outs.append(coherent(cfg, rates, locs, keys))
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *outs)

    fn = _shard_wrap(run_grid, plan, n_cell_operands=3 if content else 2)
    _GRID_CACHE[cache_key] = fn
    return fn


def _base_keys(seeds: Sequence[int]) -> jax.Array:
    """(V, 2) per-cell base keys: ``PRNGKey(seed_v)``.  Rebuilt fresh
    on every grid call (the operand is donated to the program)."""
    return jnp.stack([jax.random.PRNGKey(int(s)) for s in seeds])


def _grid_keys(seeds: Sequence[int], n_runs: int) -> jax.Array:
    """(V, R, 2) uint32 key grid: ``fold_in(PRNGKey(seed_v), r)`` -
    exactly the per-run key schedule the grid programs derive in-device
    via ``acs.run_keys`` (loop baselines in tests/benches consume this
    host-side form)."""
    rs = jnp.arange(n_runs)
    return jnp.stack([
        acs.run_keys(jax.random.PRNGKey(int(s)), rs) for s in seeds])


def _grid_call(fn, plan: ShardPlan, n_runs: int, *cell_args) -> dict:
    """Run a grid program: append the (padded) global ``run_ids``
    operand, execute, and slice off any padded trailing runs."""
    with span("sweep.operands"):
        run_ids = jnp.arange(plan.pad_runs, dtype=jnp.int32)
    out = _call_grid(fn, *cell_args, run_ids)
    if plan.pad_runs != n_runs:
        out = {k: a[..., :n_runs] for k, a in out.items()}
    return out


# ---------------------------------------------------------------------------
# Host-side aggregation.


def _result_from(cell: dict, name: str, strategy_name: str,
                 n_runs: int) -> RunResult:
    total = np.asarray(cell["total_tokens"], dtype=np.float64)
    chr_ = np.asarray(cell["cache_hit_rate"], dtype=np.float64)
    stats = RunStats(
        name=name,
        strategy=strategy_name,
        n_runs=n_runs,
        total_tokens_mean=float(total.mean()),
        total_tokens_std=float(total.std()),
        sync_tokens_mean=float(np.mean(cell["sync_tokens"])),
        sync_tokens_std=float(np.std(np.asarray(
            cell["sync_tokens"], dtype=np.float64))),
        fetch_tokens_mean=float(np.mean(cell["fetch_tokens"])),
        signal_tokens_mean=float(np.mean(cell["signal_tokens"])),
        push_tokens_mean=float(np.mean(cell["push_tokens"])),
        broadcast_tokens_mean=float(np.mean(cell["broadcast_tokens"])),
        cache_hit_rate_mean=float(chr_.mean()),
        cache_hit_rate_std=float(chr_.std()),
        n_fetches_mean=float(np.mean(cell["n_fetches"])),
        n_writes_mean=float(np.mean(cell["n_writes"])),
        n_reads_mean=float(np.mean(cell["n_reads"])),
        max_staleness_max=int(np.max(cell["max_staleness"])),
        max_version_lag_max=int(np.max(cell["max_version_lag"])),
        max_consumed_staleness_max=int(
            np.max(cell["max_consumed_staleness"])),
        delta_bytes_mean=float(np.mean(cell["delta_bytes"]))
        if "delta_bytes" in cell else -1.0,
        full_bytes_mean=float(np.mean(cell["full_bytes"]))
        if "full_bytes" in cell else -1.0,
        n_chunks_fetched_mean=float(np.mean(cell["n_chunks_fetched"]))
        if "n_chunks_fetched" in cell else -1.0,
    )
    return RunResult(stats=stats, per_run_total_tokens=total,
                     per_run_chr=chr_)


def _cell(out: dict, variant: int, v: int) -> dict:
    return {k: np.asarray(a)[variant, v] for k, a in out.items()}


def _comparison_of(name: str, volatility: float, bc: RunResult,
                   co: RunResult) -> Comparison:
    savings_runs = (1.0 - co.per_run_total_tokens
                    / bc.stats.total_tokens_mean)
    return Comparison(
        scenario=name,
        volatility=volatility,
        strategy=co.stats.strategy,
        broadcast=bc.stats,
        coherent=co.stats,
        savings_mean=float(savings_runs.mean()),
        savings_std=float(savings_runs.std()),
        crr=co.stats.total_tokens_mean / bc.stats.total_tokens_mean,
        chr_mean=co.stats.cache_hit_rate_mean,
        chr_std=co.stats.cache_hit_rate_std,
        coherent_per_run_tokens=tuple(
            int(t) for t in co.per_run_total_tokens),
    )


def _comparison_from(scn: ScenarioConfig, bc: RunResult,
                     co: RunResult) -> Comparison:
    return _comparison_of(scn.name, scn.acs.volatility, bc, co)


# ---------------------------------------------------------------------------
# Public API.


def run_scenario(scn: ScenarioConfig,
                 tick_backend: Optional[str] = None,
                 devices: Optional[int] = None) -> RunResult:
    """Run ``scn.n_runs`` independent seeded episodes, vmapped.

    Uses the module-level jit cache: repeated calls with the same static
    configuration (any volatility / p_act / seed) reuse one compiled
    program.  ``devices`` caps the shard count (default: every local
    device; 1 forces the unsharded program).
    """
    backend = tick_backend or resolve_tick_backend(scn.acs, scn.n_runs)
    plan = shard_plan(1, scn.n_runs, devices)
    fn = _grid_fn(scn.acs, include_broadcast=False, tick_backend=backend,
                  plan=plan)
    cell_ops = [
        jnp.asarray([scn.acs.volatility], jnp.float32),
        jnp.asarray([scn.acs.p_act], jnp.float32),
    ]
    if acs.content_enabled(scn.acs):
        cell_ops.append(jnp.asarray([scn.acs.write_locality],
                                    jnp.float32))
    out = _grid_call(fn, plan, scn.n_runs, *cell_ops,
                     _base_keys([scn.seed]))
    return _result_from(
        _cell(out, 0, 0), scn.name,
        acs.STRATEGY_NAMES[scn.acs.strategy], scn.n_runs)


def compare_grid(scns: Sequence[ScenarioConfig],
                 tick_backend: Optional[str] = None,
                 devices: Optional[int] = None) -> list[Comparison]:
    """Broadcast-vs-coherent for many scenarios, fused.

    Scenarios sharing a static signature (and n_runs) are batched into a
    single XLA program: variant x scenario x run.  Heterogeneous lists
    still work - each static group compiles once.  On a multi-device
    host each group's program is device-sharded per ``shard_plan``
    (``devices=1`` forces single-device execution).
    """
    groups: dict = {}
    for i, s in enumerate(scns):
        groups.setdefault((_static_key(s.acs), s.n_runs), []).append(i)
    results: list = [None] * len(scns)
    for (_, n_runs), idxs in groups.items():
        sub = [scns[i] for i in idxs]
        cfg = sub[0].acs
        # Only the coherent variant can take the kernel (broadcast is a
        # bulk-inject scan path), so size the threshold on that half.
        backend = tick_backend or resolve_tick_backend(
            cfg, len(sub) * n_runs)
        plan = shard_plan(len(sub), n_runs, devices)
        fn = _grid_fn(cfg, include_broadcast=True, tick_backend=backend,
                      plan=plan)
        cell_ops = [
            jnp.asarray([s.acs.volatility for s in sub], jnp.float32),
            jnp.asarray([s.acs.p_act for s in sub], jnp.float32),
        ]
        if acs.content_enabled(cfg):
            cell_ops.append(jnp.asarray(
                [s.acs.write_locality for s in sub], jnp.float32))
        out = _grid_call(fn, plan, n_runs, *cell_ops,
                         _base_keys([s.seed for s in sub]))
        for j, i in enumerate(idxs):
            bc = _result_from(_cell(out, 0, j), sub[j].name,
                              acs.STRATEGY_NAMES[acs.BROADCAST], n_runs)
            co = _result_from(_cell(out, 1, j), sub[j].name,
                              acs.STRATEGY_NAMES[cfg.strategy], n_runs)
            results[i] = _comparison_from(sub[j], bc, co)
    return results


def compare(scn: ScenarioConfig, strategy_code: Optional[int] = None,
            tick_backend: Optional[str] = None,
            devices: Optional[int] = None) -> Comparison:
    """Run broadcast + coherent variants of one scenario (one program)."""
    coh_scn = scn if strategy_code is None else scn.with_strategy(
        strategy_code)
    return compare_grid([coh_scn], tick_backend=tick_backend,
                        devices=devices)[0]


def sweep_cells(base_scn: ScenarioConfig, volatilities,
                n_runs: Optional[int] = None) -> list[ScenarioConfig]:
    """The per-volatility scenario cells of a V-sweep (deterministic
    per-cell seeds derived from the base seed).  Single source of truth
    for the grid both the fused path and any loop baseline run over."""
    runs = n_runs or base_scn.n_runs
    return [dataclasses.replace(
        base_scn,
        acs=dataclasses.replace(base_scn.acs, volatility=float(v)),
        n_runs=runs,
        seed=base_scn.seed + int(round(float(v) * 1000)))
        for v in volatilities]


def _rate_stack(workloads) -> acs.RateMatrices:
    """Stack per-workload rate matrices along a leading W axis."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *[w.rates() for w in workloads])


def _locality_stack(workloads) -> jax.Array:
    """(W,) traced write-locality operand of a content-plane grid."""
    return jnp.asarray(
        [getattr(w, "write_locality", w.acs.write_locality)
         for w in workloads], jnp.float32)


def compare_workloads(workloads, tick_backend: Optional[str] = None,
                      devices: Optional[int] = None
                      ) -> list["Comparison"]:
    """Broadcast-vs-coherent for heterogeneous workloads, fused.

    ``workloads``: ``repro.sim.workloads.Workload`` instances (anything
    with ``.acs``, ``.seed``, ``.n_runs``, ``.name``,
    ``.effective_volatility()`` and ``.rates()`` works).  Workloads
    sharing a static signature (and n_runs) batch into a single XLA
    program - variant x workload x run - with the rate matrices as
    traced axes, so an entire zoo of families costs ONE compilation and
    re-running with new or perturbed families costs zero more.  On a
    multi-device host the program shards per ``shard_plan`` (run axis,
    falling back to the workload axis).
    """
    groups: dict = {}
    for i, w in enumerate(workloads):
        groups.setdefault((_static_key(w.acs), w.n_runs), []).append(i)
    results: list = [None] * len(workloads)
    # one record per call: operands, dispatch, readback, results
    with obs_runtime.sweep_call():
        for (_, n_runs), idxs in groups.items():
            sub = [workloads[i] for i in idxs]
            cfg = sub[0].acs
            backend = tick_backend or resolve_tick_backend(
                cfg, len(sub) * n_runs)
            plan = shard_plan(len(sub), n_runs, devices)
            fn = _het_grid_fn(cfg, include_broadcast=True,
                              tick_backend=backend, plan=plan)
            with span("sweep.operands"):
                cell_ops = [_rate_stack(sub)]
                if acs.content_enabled(cfg):
                    cell_ops.append(_locality_stack(sub))
                cell_ops.append(_base_keys([w.seed for w in sub]))
            out = _grid_call(fn, plan, n_runs, *cell_ops)
            with span("sweep.results"):
                for j, i in enumerate(idxs):
                    bc = _result_from(
                        _cell(out, 0, j), sub[j].name,
                        acs.STRATEGY_NAMES[acs.BROADCAST], n_runs)
                    co = _result_from(
                        _cell(out, 1, j), sub[j].name,
                        acs.STRATEGY_NAMES[cfg.strategy], n_runs)
                    results[i] = _comparison_of(
                        sub[j].name, sub[j].effective_volatility(), bc,
                        co)
    return results


def run_workload(w, tick_backend: Optional[str] = None,
                 devices: Optional[int] = None) -> RunResult:
    """Run one heterogeneous workload (no baseline), fused and cached."""
    backend = tick_backend or resolve_tick_backend(w.acs, w.n_runs)
    plan = shard_plan(1, w.n_runs, devices)
    fn = _het_grid_fn(w.acs, include_broadcast=False,
                      tick_backend=backend, plan=plan)
    cell_ops = [_rate_stack([w])]
    if acs.content_enabled(w.acs):
        cell_ops.append(_locality_stack([w]))
    out = _grid_call(fn, plan, w.n_runs, *cell_ops,
                     _base_keys([w.seed]))
    return _result_from(_cell(out, 0, 0), w.name,
                        acs.STRATEGY_NAMES[w.acs.strategy], w.n_runs)


def sweep_volatility(base_scn: ScenarioConfig, volatilities,
                     n_runs: Optional[int] = None,
                     tick_backend: Optional[str] = None,
                     devices: Optional[int] = None
                     ) -> list[Comparison]:
    """Fused V-sweep: ONE jitted program for the whole
    ``(variant x volatility x run)`` grid.  Volatility is a traced
    Bernoulli parameter, so a single compilation covers the sweep and is
    reused across sweeps of any volatility values - the fleet-scale
    path.  On a multi-device host the program is device-sharded
    (``shard_plan``); ledgers are bit-identical at any device count."""
    return compare_grid(sweep_cells(base_scn, volatilities, n_runs),
                        tick_backend=tick_backend, devices=devices)
