"""Batched MESI coherence tick as a Pallas TPU kernel.

This is the paper-specific compute hot-spot: parameter sweeps run
thousands of simulated deployments concurrently (fleet-scale evaluation,
SS8), and the per-tick work is a serialized-agent state transition over
the (n_agents x n_artifacts) coherence matrix of every simulation.

TPU adaptation: one program owns a ``block_sims`` slab of simulations
resident in VMEM; agents are processed with a sequential fori_loop
(the authority's serialization order - a *semantic* requirement, not a
perf artifact) while the simulation dimension is vectorized.  Mosaic
lowers neither a dynamic slice nor a scatter, so the loop's agent index
and each sim's artifact index select and update by one-hot masks over
the agent and artifact axes (m <= 16), and counters take masked adds -
fully static shapes, the standard TPU answer to data-dependent
indexing.

Counters layout (out[..., c]): 0 fetch_tokens, 1 signal_tokens,
2 push_tokens, 3 n_fetches, 4 n_hits, 5 n_invalidation_signals;
6-7 reserved (zero).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.states import MESIState
from repro.kernels.backend import resolve_interpret

_I, _S = int(MESIState.I), int(MESIState.S)
N_COUNTERS = 8

#: sims per grid step of ``mesi_decision_batch``: its n+1 prefix sims
#: tile over a grid so that a 256-agent directory block stays within VMEM.
DECISION_BLOCK = 8


def episode_step_keys(keys: jax.Array, n_steps: int) -> jax.Array:
    """Per-step PRNG keys for a batch of kernel-routed episodes.

    ``keys`` is a ``(B, 2)`` batch of per-episode keys - in the sweep
    engine these come from ``repro.core.acs.run_keys`` (``fold_in`` on
    the **global** run index), so under ``shard_map`` each device
    derives the same schedule the single-device path derives for its
    slice of episodes.  Returns ``(n_steps, B, 2)``: step-major, the
    scan order of the batched episode loop, and step ``s`` holds
    exactly ``split(key, n_steps)[s]`` - the schedule
    ``acs.run_episode`` uses - so kernel-routed episodes consume the
    same action stream as the ``lax.scan`` path bit-for-bit.
    """
    step_keys = jax.vmap(lambda k: jax.random.split(k, n_steps))(keys)
    return jnp.swapaxes(step_keys, 0, 1)


def pick_lane(lane_oh: jax.Array, x: jax.Array) -> jax.Array:
    """Entry of ``x`` (bs, 1, k) under the one-hot lane mask ``lane_oh``
    (bs, 1, k): ``(bs, 1, 1)``.  Mosaic lowers no dynamic slice, so the
    loop's agent index and each sim's artifact index select by mask."""
    return jnp.sum(jnp.where(lane_oh, x, 0), axis=2, keepdims=True)


def pick_row(row: jax.Array, x: jax.Array) -> jax.Array:
    """Agent row of a directory array: ``row`` is the (bs, n, 1) one-hot
    mask of the agent axis, ``x`` (bs, n, m) -> (bs, 1, m)."""
    return jnp.sum(jnp.where(row, x, 0), axis=1, keepdims=True)


def slot_add(counters: jax.Array, slot: int, value: jax.Array
              ) -> jax.Array:
    """``counters[..., slot] += value`` as a masked add over a lane iota
    (Mosaic has no scatter): ``counters`` (bs, 1, K), ``value``
    (bs, 1, 1)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, counters.shape, 2)
    return counters + jnp.where(lane == slot, value, 0)


def _mesi_kernel(state_ref, version_ref, sync_ref, reads_ref,
                 act_ref, art_ref, write_ref,
                 state_out, version_out, sync_out, reads_out, counter_out,
                 miss_out,
                 *, n_agents: int, n_artifacts: int, artifact_tokens: int,
                 eager: bool, access_k: int, signal_tokens: int):
    # Every value is rank 3 with the sim axis leading: directory arrays
    # (bs, n, m), per-artifact rows (bs, 1, m), per-agent rows (bs, 1, n),
    # per-sim scalars (bs, 1, 1).  The carried directory lives in the
    # output refs; the agent loop reads and rewrites them.
    state_out[...] = state_ref[...]
    version_out[...] = version_ref[...]
    sync_out[...] = sync_ref[...]
    reads_out[...] = reads_ref[...]
    counter_out[...] = jnp.zeros(counter_out.shape, jnp.int32)
    miss_out[...] = jnp.zeros(miss_out.shape, jnp.int32)
    bs = state_ref.shape[0]
    agent_col = jax.lax.broadcasted_iota(jnp.int32, (bs, n_agents, 1), 1)
    agent_lane = jax.lax.broadcasted_iota(jnp.int32, (bs, 1, n_agents), 2)
    art_lane = jax.lax.broadcasted_iota(jnp.int32, (bs, 1, n_artifacts), 2)

    def agent_body(a, carry):
        row = agent_col == a                        # (bs, n, 1)
        a_oh = agent_lane == a                      # (bs, 1, n)
        act = pick_lane(a_oh, act_ref[...]) != 0          # (bs, 1, 1)
        wr = pick_lane(a_oh, write_ref[...]) != 0
        is_write = jnp.logical_and(act, wr)
        is_read = jnp.logical_and(act, jnp.logical_not(wr))
        d_oh = art_lane == pick_lane(a_oh, art_ref[...])  # (bs, 1, m)

        state = state_out[...]
        sync = sync_out[...]
        reads = reads_out[...]
        version = version_out[...]                  # (bs, 1, m)
        miss = pick_lane(d_oh, pick_row(row, state)) == _I    # (bs, 1, 1)
        if access_k > 0:
            miss = jnp.logical_or(
                miss, pick_lane(d_oh, pick_row(row, reads)) >= access_k)
        miss = jnp.logical_and(act, miss)
        hit = jnp.logical_and(act, jnp.logical_not(miss))
        miss_i = miss.astype(jnp.int32)

        # --- coherence fill on miss (row a, chosen artifact)
        fill = jnp.logical_and(row, jnp.logical_and(miss, d_oh))
        state = jnp.where(fill, _S, state)
        sync = jnp.where(fill, version, sync)
        reads = jnp.where(fill, 0, reads)
        counters = counter_out[...]
        counters = slot_add(counters, 0,
                             (artifact_tokens + signal_tokens) * miss_i)
        counters = slot_add(counters, 3, miss_i)
        counters = slot_add(counters, 4, hit.astype(jnp.int32))
        miss_out[...] = jnp.where(a_oh, miss_i, miss_out[...])

        # --- write path: invalidate peers, bump version, commit
        wmask = jnp.logical_and(is_write, d_oh)     # (bs, 1, m)
        peer_valid = jnp.logical_and(
            jnp.logical_and(wmask, jnp.logical_not(row)), state != _I)
        n_peers = jnp.sum(jnp.sum(peer_valid.astype(jnp.int32), axis=2,
                                  keepdims=True), axis=1, keepdims=True)
        counters = slot_add(counters, 1, signal_tokens * n_peers)
        counters = slot_add(counters, 5, n_peers)
        state = jnp.where(peer_valid, _I, state)

        new_ver = jnp.where(wmask, version + 1, version)
        writer = jnp.logical_and(wmask, row)
        state = jnp.where(writer, _S, state)
        sync = jnp.where(writer, new_ver, sync)
        reads = jnp.where(writer, 0, reads)

        if eager:
            # push-on-commit to active sharers
            state = jnp.where(peer_valid, _S, state)
            sync = jnp.where(peer_valid, new_ver, sync)
            reads = jnp.where(peer_valid, 0, reads)
            counters = slot_add(
                counters, 2, (artifact_tokens + signal_tokens) * n_peers)

        # --- read bookkeeping
        own = jnp.logical_and(row, jnp.logical_and(is_read, d_oh))
        reads = jnp.where(own, reads + 1, reads)

        state_out[...] = state
        sync_out[...] = sync
        reads_out[...] = reads
        version_out[...] = new_ver
        counter_out[...] = counters
        return carry

    jax.lax.fori_loop(0, n_agents, agent_body, 0)


def mesi_tick_pallas(state, version, last_sync, reads_since_fetch,
                     acts, arts, writes, *, artifact_tokens: int,
                     eager: bool = False, access_k: int = 0,
                     signal_tokens: int = 12, block_sims: int = 128,
                     interpret: bool | None = None):
    """One coherence tick over a batch of simulations.

    Shapes: state/last_sync/reads (B, n, m) int32; version (B, m) int32;
    acts/arts/writes (B, n) int32.  Returns (state', version', sync',
    reads', counters (B, 8), miss (B, n)) - ``miss`` is the per-agent
    coherence-fill indicator of this tick, which the chunk content
    plane (``repro.kernels.chunk_diff``) consumes to route delta
    fetches at the exact serialization slots the MESI decisions were
    made at.  ``interpret=None`` auto-detects the backend (compiled
    Mosaic on TPU, interpret mode elsewhere).
    """
    interpret = resolve_interpret(interpret)
    B, n, m = state.shape
    bs = min(block_sims, B)
    # the kernel is rank 3 throughout: per-sim rows get a unit axis
    args = [state, version[:, None, :], last_sync, reads_since_fetch,
            acts[:, None, :], arts[:, None, :], writes[:, None, :]]
    pad = (-B) % bs
    if pad:
        args = [jnp.pad(x, [(0, pad), (0, 0), (0, 0)]) for x in args]
    Bp = B + pad
    kernel = functools.partial(
        _mesi_kernel, n_agents=n, n_artifacts=m,
        artifact_tokens=artifact_tokens, eager=eager, access_k=access_k,
        signal_tokens=signal_tokens)
    blocks = [(bs, n, m), (bs, 1, m), (bs, n, m), (bs, n, m),
              (bs, 1, n), (bs, 1, n), (bs, 1, n)]
    out_blocks = [(bs, n, m), (bs, 1, m), (bs, n, m), (bs, n, m),
                  (bs, 1, N_COUNTERS), (bs, 1, n)]
    spec = lambda blk: pl.BlockSpec(blk, lambda i: (i, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid=(Bp // bs,),
        in_specs=[spec(b) for b in blocks],
        out_specs=[spec(b) for b in out_blocks],
        out_shape=[jax.ShapeDtypeStruct((Bp,) + b[1:], jnp.int32)
                   for b in out_blocks],
        interpret=interpret,
    )(*args)
    st, ver, sy, rd, cnt, miss = (o[:B] for o in out)
    return st, ver[:, 0], sy, rd, cnt[:, 0], miss[:, 0]


def mesi_decision_batch(state, version, last_sync, reads_since_fetch,
                        acts, arts, writes, *, artifact_tokens: int,
                        eager: bool = False, access_k: int = 0,
                        signal_tokens: int = 12,
                        interpret: bool | None = None):
    """One micro-batch of live coherence decisions via prefix-replicated
    simulations (the ``repro.service.batching`` kernel route).

    The kernel emits per-*simulation* aggregate counters, not
    per-request outcomes, yet a live broker must answer each request
    individually (fill vs hit, served version).  Trick: replicate the
    single directory into ``B = k+1`` sims where sim ``j`` enables only
    the first ``j`` active agents (in the authority's ascending-agent
    serialization order).  Agent processing is sequential and
    deterministic, so sim ``j`` agrees with the full batch on its
    prefix, and request ``j``'s outcome is the counter delta between
    consecutive prefix sims - every decision of the batch falls out of
    ONE ``mesi_tick_pallas`` call, vectorized over the sim lanes the
    kernel already batches on.

    Inputs: single-directory arrays - ``state``/``last_sync``/``reads``
    (n, m) int32, ``version`` (m,) int32 - plus the request vectors
    ``acts``/``arts``/``writes`` (n,) (at most one request per agent).
    Returns ``(state', version', sync', reads', counters (8,),
    miss (n,) bool, served_version (n,) int32)`` where the primed
    arrays/counters are the full-batch transition.
    """
    n, m = state.shape
    acts_np = np.asarray(acts, bool)
    order = np.flatnonzero(acts_np)          # ascending agent order
    k = int(order.size)
    if k == 0:
        zc = jnp.zeros((N_COUNTERS,), jnp.int32)
        return (state, version, last_sync, reads_since_fetch, zc,
                jnp.zeros((n,), bool), jnp.zeros((n,), jnp.int32))
    # sim j enables the first j requests; sim 0 is the no-op baseline.
    # B is padded to the FIXED n+1 (rows past k repeat the full batch,
    # so their counter deltas are zero) - every micro-batch size shares
    # one compiled program instead of one Mosaic compile per distinct k.
    B = n + 1
    acts_b = np.zeros((B, n), np.int32)
    for j, a in enumerate(order):
        acts_b[j + 1:, a] = 1
    tile = lambda arr: jnp.broadcast_to(arr, (B,) + arr.shape)
    st, ver, sy, rd, cnt, _ = mesi_tick_pallas(
        tile(state), tile(version), tile(last_sync),
        tile(reads_since_fetch), jnp.asarray(acts_b),
        tile(jnp.asarray(arts, jnp.int32)),
        tile(jnp.asarray(writes, jnp.int32)),
        artifact_tokens=artifact_tokens, eager=eager, access_k=access_k,
        signal_tokens=signal_tokens, block_sims=DECISION_BLOCK,
        interpret=interpret)
    cnt_np = np.asarray(cnt, np.int64)
    arts_np = np.asarray(arts, np.int64)
    sync_np = np.asarray(sy, np.int64)
    miss = np.zeros((n,), bool)
    served = np.zeros((n,), np.int32)
    for j, a in enumerate(order):
        # counter slot 3 = n_fetches; the delta between prefix j+1 and
        # prefix j is exactly request j's fill.
        miss[a] = (cnt_np[j + 1, 3] - cnt_np[j, 3]) == 1
        # sim j+1 processed request j last: its sync cell is the version
        # agent a is synced to at its serialization slot (later eager
        # pushes in the full batch must not leak into this answer).
        served[a] = sync_np[j + 1, a, arts_np[a]]
    return (st[-1], ver[-1], sy[-1], rd[-1], cnt[-1],
            jnp.asarray(miss), jnp.asarray(served))
