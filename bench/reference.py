"""Plain references of the coherence semantics the program serves.

Written from the protocol's rules (paper SS5: lazy write-invalidate,
fetch on demand; the content plane ships only a reader's stale chunks)
and independent of the program: no import of ``repro``, no value the
program computed.  Both take the serialization order as input - a batch
is processed agent by agent, ascending - and answer every request.

``invalidate=False`` is the control: the same protocol with the
write-invalidate step left out, so a peer keeps serving a copy that a
commit made stale.  It breaks the guarantee "a hit only on a valid
copy" and has to come out as not correct.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

SIGNAL_TOKENS = 12      # one coherence signal (paper SS8.1)
BYTES_PER_TOKEN = 4     # wire width of a token in the byte ledger

LEDGER_FIELDS = ("fetch_tokens", "push_tokens", "signal_tokens",
                 "n_fetches", "n_hits", "n_reads", "n_writes",
                 "n_invalidation_signals")
WIRE_FIELDS = ("delta_bytes", "full_bytes", "n_chunks_fetched")


@dataclasses.dataclass
class Answer:
    miss: bool
    version: int
    content: tuple
    delta: tuple | None


class ServedReference:
    """One directory of ``n`` agents x ``m`` artifacts, lazy protocol."""

    def __init__(self, n: int, m: int, tokens: int, contents: list,
                 chunk_tokens: int = 0, invalidate: bool = True) -> None:
        self.n, self.m, self.tokens = n, m, tokens
        self.ct = chunk_tokens
        self.invalidate = invalidate
        self.valid = np.zeros((n, m), bool)
        self.version = np.ones(m, np.int64)
        self.sync = np.zeros((n, m), np.int64)
        self.content = [tuple(c) for c in contents]
        self.ledger = dict.fromkeys(LEDGER_FIELDS, 0)
        self.wire = dict.fromkeys(WIRE_FIELDS, 0)
        if chunk_tokens:
            c = -(-tokens // chunk_tokens)
            self.chunk_version = np.ones((m, c), np.int64)
            self.chunk_sync = np.zeros((n, m, c), np.int64)

    def _chunks(self, content: tuple) -> list:
        return [content[i:i + self.ct]
                for i in range(0, len(content), self.ct)]

    def apply(self, a: int, d: int, write: bool,
              written: tuple | None = None) -> Answer:
        """Serve agent ``a``'s read or write of artifact ``d`` at its
        serialization slot.  ``written`` is a write's new content
        (``None`` commits the current content unchanged)."""
        led = self.ledger
        miss = not self.valid[a, d]
        delta = None
        if miss:
            led["fetch_tokens"] += self.tokens + SIGNAL_TOKENS
            led["n_fetches"] += 1
            self.valid[a, d] = True
            self.sync[a, d] = self.version[d]
        else:
            led["n_hits"] += 1
        if self.ct:
            stale = np.flatnonzero(self.chunk_version[d]
                                   > self.chunk_sync[a, d]) if miss else []
            chunks = self._chunks(self.content[d])
            delta = tuple((int(i), chunks[i]) for i in stale)
            if miss:
                self.wire["delta_bytes"] += (
                    sum(len(c) for _, c in delta) + SIGNAL_TOKENS
                ) * BYTES_PER_TOKEN
                self.wire["full_bytes"] += (
                    self.tokens + SIGNAL_TOKENS) * BYTES_PER_TOKEN
                self.wire["n_chunks_fetched"] += len(delta)
                self.chunk_sync[a, d] = self.chunk_version[d]
        if write:
            peers = self.valid[:, d].copy()
            peers[a] = False
            if self.invalidate:
                n_peers = int(peers.sum())
                led["signal_tokens"] += SIGNAL_TOKENS * n_peers
                led["n_invalidation_signals"] += n_peers
                self.valid[peers, d] = False
            self.version[d] += 1
            self.sync[a, d] = self.version[d]
            new = self.content[d] if written is None else tuple(written)
            if self.ct:
                dirty = [i for i, (x, y) in enumerate(zip(
                    self._chunks(self.content[d]), self._chunks(new)))
                    if x != y]
                self.chunk_version[d, dirty] += 1
                self.chunk_sync[a, d] = self.chunk_version[d]
            self.content[d] = new
            led["n_writes"] += 1
        else:
            led["n_reads"] += 1
        return Answer(miss, int(self.sync[a, d]), self.content[d], delta)


# ---------------------------------------------------------------------------
# Sweep episodes.

_SPAN_FOLD = 0x5EED     # fold_in constant of the write-span key


@functools.lru_cache(maxsize=None)
def _draw_program(n: int, m: int, n_steps: int, n_chunks: int):
    """The sweep's action stream, drawn from its documented key
    schedule: run key ``fold_in(PRNGKey(seed), run)``, ``split`` into
    one key per step; per step ``split(k, 3)`` gives activity
    (Bernoulli ``p_act``), artifact (categorical over ``log_pick``) and
    write (Bernoulli at the picked cell); ``fold_in(k, 0x5EED)`` gives
    the write span's start (a circular span of ``span`` chunks)."""
    import jax
    import jax.numpy as jnp

    def step(k, p_act, log_pick, write_rate, span):
        k_act, k_art, k_wr = jax.random.split(k, 3)
        acts = jax.random.bernoulli(k_act, p_act, (n,))
        arts = jax.random.categorical(k_art, log_pick, axis=-1)
        writes = jax.random.bernoulli(
            k_wr, write_rate[jnp.arange(n), arts], (n,))
        start = jax.random.randint(jax.random.fold_in(k, _SPAN_FOLD),
                                   (n,), 0, n_chunks)
        idx = jnp.arange(n_chunks, dtype=jnp.int32)
        wch = ((idx[None, :] - start[:, None]) % n_chunks) < span
        return acts, arts.astype(jnp.int32), writes, wch

    def run(key, p_act, log_pick, write_rate, span):
        keys = jax.random.split(key, n_steps)
        return jax.vmap(lambda k: step(k, p_act, log_pick, write_rate,
                                       span))(keys)

    def grid(seed_keys, run_ids, p_act, log_pick, write_rate, span):
        def cell(base, wr, sp):
            keys = jax.vmap(lambda r: jax.random.fold_in(base, r))(run_ids)
            return jax.vmap(lambda k: run(k, p_act, log_pick, wr, sp))(keys)
        return jax.vmap(cell)(seed_keys, write_rate, span)

    return jax.jit(grid)


def draw_episodes(seeds, n_runs: int, n: int, m: int, n_steps: int,
                  n_chunks: int, p_act: float, pick: np.ndarray,
                  write_rates, spans) -> tuple:
    """(W, R, S, n) acts/arts/writes and (W, R, S, n, C) write spans of
    the ``W`` cells seeded ``seeds``."""
    import jax
    import jax.numpy as jnp
    seed_keys = jnp.stack([jax.random.PRNGKey(int(s)) for s in seeds])
    log_pick = jnp.log(jnp.maximum(jnp.asarray(pick, jnp.float32), 1e-30))
    wr = jnp.stack([jnp.full((n, m), w, jnp.float32) for w in write_rates])
    out = _draw_program(n, m, n_steps, n_chunks)(
        seed_keys, jnp.arange(n_runs, dtype=jnp.int32),
        jnp.full((n,), p_act, jnp.float32), log_pick, wr,
        jnp.asarray(spans, jnp.int32))
    return tuple(np.asarray(x) for x in jax.device_get(out))


def episodes(acts, arts, writes, wch, *, m: int, tokens: int,
             chunk_tokens: int, invalidate: bool = True) -> dict:
    """Lazy-protocol totals of a batch of episodes, vectorized over the
    episodes: ``acts``/``arts``/``writes`` (B, S, n), ``wch``
    (B, S, n, C).  Returns per-episode int64 counters."""
    B, S, n = acts.shape
    C = wch.shape[-1]
    sizes = np.full(C, chunk_tokens, np.int64)
    sizes[-1] = tokens - (C - 1) * chunk_tokens
    rows = np.arange(B)
    valid = np.zeros((B, n, m), bool)
    cv = np.ones((B, m, C), np.int64)
    cs = np.zeros((B, n, m, C), np.int64)
    out = {k: np.zeros(B, np.int64) for k in (
        "fetch_tokens", "signal_tokens", "n_fetches", "n_hits",
        "n_reads", "n_writes", "delta_bytes", "full_bytes",
        "n_chunks_fetched")}
    for s in range(S):
        for a in range(n):
            act = acts[:, s, a].astype(bool)
            d = arts[:, s, a]
            w = act & writes[:, s, a].astype(bool)
            miss = act & ~valid[rows, a, d]
            out["fetch_tokens"] += miss * (tokens + SIGNAL_TOKENS)
            out["n_fetches"] += miss
            out["n_hits"] += act & ~miss
            out["n_reads"] += act & ~w
            out["n_writes"] += w
            stale = (cv[rows, d] > cs[rows, a, d]) & miss[:, None]
            out["delta_bytes"] += miss * (
                (stale * sizes).sum(1) + SIGNAL_TOKENS) * BYTES_PER_TOKEN
            out["full_bytes"] += miss * (
                tokens + SIGNAL_TOKENS) * BYTES_PER_TOKEN
            out["n_chunks_fetched"] += stale.sum(1)
            valid[rows[miss], a, d[miss]] = True
            cs[rows[miss], a, d[miss]] = cv[rows[miss], d[miss]]
            if w.any():
                wr = rows[w]
                dw = d[w]
                peers = valid[wr, :, dw].copy()
                peers[:, a] = False
                if invalidate:
                    out["signal_tokens"][wr] += SIGNAL_TOKENS * peers.sum(1)
                    cols = valid[wr, :, dw]
                    valid[wr, :, dw] = cols & ~peers
                valid[wr, a, dw] = True
                cv[wr, dw] += wch[w, s, a].astype(np.int64)
                cs[wr, a, dw] = cv[wr, dw]
    out["total_tokens"] = out["fetch_tokens"] + out["signal_tokens"]
    return out
