"""Batched chunk-diff / delta-coherence tick as a Pallas TPU kernel.

The content plane (``repro.content``) tracks per-chunk version counters
at the authority and a per-chunk sync vector per (agent, artifact)
cache entry.  Per orchestration step, the hot work is: for every fill
the MESI tick decided, compare the reader's chunk vector against the
authority's chunk versions and count the stale chunks' bytes (delta
fetch); for every commit, bump the dirtied span's versions.  Fleet
sweeps run this batched over (sims x agents x artifacts x chunks) -
this kernel does one whole tick of it in one ``pallas_call``.

TPU adaptation mirrors ``mesi_transition``: one program owns a
``block_sims`` slab of simulations in VMEM; agents are processed with
a sequential fori_loop (the authority's serialization order - chunk
versions bumped by agent ``a`` must be visible to the fill of agent
``a+1`` in the same tick), while the sim dimension rides the VPU
lanes.  Per-sim artifact choice becomes a one-hot mask over the
artifact dim, exactly as in the MESI kernel.

The MESI decision itself is **not** recomputed here: the kernel takes
the per-agent ``miss`` indicator the MESI tick emits
(``mesi_tick_pallas``'s sixth output), so the two kernels compose into
one bit-exact tick and neither duplicates the other's state machine.

Counters layout (out[..., c]): 0 delta_bytes (shipped), 1 full_bytes
(what whole-artifact lazy would ship for the same fills),
2 n_chunks_fetched; 3 reserved (zero).

Routing matches ``mesi_tick``: ``interpret=None`` auto-detects via
``repro.kernels.backend`` (compiled Mosaic on TPU, interpret mode
elsewhere); ``REPRO_CHUNK_DIFF=scan|pallas`` forces the pure-jnp
reference (``chunk_tick_ref``, bit-identical by construction and by
the byte-exact oracle) or the kernel in the service decision layer and
anywhere :func:`resolve_chunk_route` is consulted.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.content.chunks import BYTES_PER_TOKEN, chunk_sizes
from repro.kernels.backend import resolve_interpret
from repro.kernels.mesi_transition import pick_lane, pick_row, slot_add

N_CHUNK_COUNTERS = 4


def resolve_chunk_route(default: str = "auto") -> str:
    """'scan' (pure-jnp reference) | 'pallas' for content-plane ticks
    outside the fused engine (the engine follows ``REPRO_SIM_TICK``).
    Forced with ``REPRO_CHUNK_DIFF``; ``auto`` follows the caller's
    default."""
    forced = os.environ.get("REPRO_CHUNK_DIFF", default)
    if forced not in ("auto", "scan", "pallas"):
        raise ValueError(f"REPRO_CHUNK_DIFF must be auto|scan|pallas, "
                         f"got {forced!r}")
    return default if forced == "auto" else forced


def _chunk_kernel(cv_ref, cs_ref, dirty_ref, miss_ref, wact_ref, art_ref,
                  wmask_ref,
                  cv_out, cs_out, dirty_out, fetched_out, counter_out,
                  *, n_agents: int, n_artifacts: int, n_chunks: int,
                  chunk_tokens: int, artifact_tokens: int,
                  signal_tokens: int):
    # Values are rank 3 with the sim axis leading, as in the MESI
    # kernel: chunk planes (bs, m, C), per-agent rows (bs, 1, n), per-sim
    # scalars (bs, 1, 1).  The carried state lives in the output refs;
    # agent ``a``'s reader vectors are the (bs, m, C) plane cs[:, a].
    cv_out[...] = cv_ref[...]
    cs_out[...] = cs_ref[...]
    dirty_out[...] = dirty_ref[...]
    fetched_out[...] = jnp.zeros(fetched_out.shape, jnp.int32)
    counter_out[...] = jnp.zeros(counter_out.shape, jnp.int32)
    bs = cv_ref.shape[0]
    agent_col = jax.lax.broadcasted_iota(jnp.int32, (bs, n_agents, 1), 1)
    agent_lane = jax.lax.broadcasted_iota(jnp.int32, (bs, 1, n_agents), 2)
    art_col = jax.lax.broadcasted_iota(jnp.int32, (bs, n_artifacts, 1), 1)
    # (1, 1, C) chunk token sizes from the static geometry (a ragged
    # last chunk); built with iota - array constants can't be captured.
    cidx = jax.lax.broadcasted_iota(jnp.int32, (1, 1, n_chunks), 2)
    last = artifact_tokens - (n_chunks - 1) * chunk_tokens
    sizes_row = jnp.where(cidx < n_chunks - 1, chunk_tokens, last)

    def agent_body(a, carry):
        row = agent_col == a                        # (bs, n, 1)
        a_oh = agent_lane == a                      # (bs, 1, n)
        miss_a = pick_lane(a_oh, miss_ref[...]) != 0          # (bs, 1, 1)
        w_a = pick_lane(a_oh, wact_ref[...]) != 0
        d3 = art_col == pick_lane(a_oh, art_ref[...])         # (bs, m, 1)
        cv = cv_out[...]
        cs_a = cs_out[:, a]                                   # (bs, m, C)

        # --- delta fetch at this agent's serialization slot
        ver_at = jnp.sum(jnp.where(d3, cv, 0), axis=1, keepdims=True)
        sync_at = jnp.sum(jnp.where(d3, cs_a, 0), axis=1, keepdims=True)
        fetch = jnp.logical_and(miss_a, ver_at > sync_at)     # (bs, 1, C)
        fetch_i = fetch.astype(jnp.int32)
        delta_tok = jnp.sum(jnp.where(fetch, sizes_row, 0), axis=2,
                            keepdims=True)
        counters = counter_out[...]
        counters = slot_add(counters, 0, jnp.where(
            miss_a, (delta_tok + signal_tokens) * BYTES_PER_TOKEN, 0))
        counters = slot_add(counters, 1, jnp.where(
            miss_a, (artifact_tokens + signal_tokens) * BYTES_PER_TOKEN,
            0))
        counters = slot_add(counters, 2, jnp.sum(fetch_i, axis=2,
                                                 keepdims=True))
        counter_out[...] = counters
        fetched_out[...] = jnp.where(row, fetch_i, fetched_out[...])
        cs_a = jnp.where(jnp.logical_and(miss_a, d3), cv, cs_a)

        # --- chunk-granular commit: bump the dirtied span
        wd = jnp.logical_and(w_a, d3)                         # (bs, m, 1)
        bump = jnp.logical_and(
            wd, pick_row(row, wmask_ref[...]) != 0)           # (bs, m, C)
        cv = jnp.where(bump, cv + 1, cv)
        dirty_out[...] = jnp.where(bump, 1, dirty_out[...])
        cv_out[...] = cv
        cs_out[:, a] = jnp.where(wd, cv, cs_a)
        return carry

    jax.lax.fori_loop(0, n_agents, agent_body, 0)


def chunk_tick_pallas(chunk_version, chunk_sync, chunk_dirty,
                      miss, write_acts, arts, write_chunks, *,
                      artifact_tokens: int, chunk_tokens: int,
                      signal_tokens: int = 12, block_sims: int = 128,
                      interpret: bool | None = None):
    """One content-plane tick over a batch of simulations.

    Shapes: chunk_version/chunk_dirty (B, m, C) int32, chunk_sync
    (B, n, m, C) int32, miss/write_acts/arts (B, n) int32,
    write_chunks (B, n, C) int32.  ``miss`` comes from the same tick's
    ``mesi_tick_pallas`` call; ``write_acts`` is act AND write.
    Returns (chunk_version', chunk_sync', chunk_dirty',
    fetched (B, n, C), counters (B, 4)).
    """
    interpret = resolve_interpret(interpret)
    B, n, m, C = chunk_sync.shape
    bs = min(block_sims, B)
    # per-agent rows get a unit axis: the kernel body is rank 3
    args = [chunk_version, chunk_sync, chunk_dirty, miss[:, None, :],
            write_acts[:, None, :], arts[:, None, :], write_chunks]
    pad = (-B) % bs
    if pad:
        args = [jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
                for x in args]
    Bp = B + pad
    kernel = functools.partial(
        _chunk_kernel, n_agents=n, n_artifacts=m, n_chunks=C,
        chunk_tokens=chunk_tokens, artifact_tokens=artifact_tokens,
        signal_tokens=signal_tokens)
    blocks = [(bs, m, C), (bs, n, m, C), (bs, m, C), (bs, 1, n),
              (bs, 1, n), (bs, 1, n), (bs, n, C)]
    out_blocks = [(bs, m, C), (bs, n, m, C), (bs, m, C), (bs, n, C),
                  (bs, 1, N_CHUNK_COUNTERS)]
    spec = lambda blk: pl.BlockSpec(
        blk, lambda i: (i,) + (0,) * (len(blk) - 1))
    out = pl.pallas_call(
        kernel,
        grid=(Bp // bs,),
        in_specs=[spec(b) for b in blocks],
        out_specs=[spec(b) for b in out_blocks],
        out_shape=[jax.ShapeDtypeStruct((Bp,) + b[1:], jnp.int32)
                   for b in out_blocks],
        interpret=interpret,
    )(*args)
    cv, cs, dirty, fetched, counters = (o[:B] for o in out)
    return cv, cs, dirty, fetched, counters[:, 0]


def chunk_tick_ref(chunk_version, chunk_sync, chunk_dirty,
                   miss, write_acts, arts, write_chunks, *,
                   artifact_tokens: int, chunk_tokens: int,
                   signal_tokens: int = 12, block_sims: int = 128,
                   interpret: bool | None = None):
    """Pure-numpy reference of :func:`chunk_tick_pallas` (serialized
    agents, same signature/returns) - the scan-style oracle the kernel
    is asserted bit-identical against, and the route
    ``REPRO_CHUNK_DIFF=scan`` forces in the service layer."""
    cv = np.array(chunk_version, np.int32)
    cs = np.array(chunk_sync, np.int32)
    dirty = np.array(chunk_dirty, np.int32)
    miss = np.asarray(miss)
    wact = np.asarray(write_acts)
    arts = np.asarray(arts, np.int64)
    wmask = np.asarray(write_chunks)
    B, n, m, C = cs.shape
    sizes = chunk_sizes(artifact_tokens, chunk_tokens)
    fetched = np.zeros((B, n, C), np.int32)
    counters = np.zeros((B, N_CHUNK_COUNTERS), np.int32)
    for s in range(B):
        for a in range(n):
            d = int(arts[s, a])
            if miss[s, a]:
                stale = cv[s, d] > cs[s, a, d]
                counters[s, 0] += (int(sizes[stale].sum())
                                   + signal_tokens) * BYTES_PER_TOKEN
                counters[s, 1] += (artifact_tokens
                                   + signal_tokens) * BYTES_PER_TOKEN
                counters[s, 2] += int(stale.sum())
                fetched[s, a] = stale
                cs[s, a, d] = cv[s, d]
            if wact[s, a]:
                span = wmask[s, a] != 0
                cv[s, d][span] += 1
                dirty[s, d][span] = 1
                cs[s, a, d] = cv[s, d]
    return (jnp.asarray(cv), jnp.asarray(cs), jnp.asarray(dirty),
            jnp.asarray(fetched), jnp.asarray(counters))
