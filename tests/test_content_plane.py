"""Chunk-granular content plane: geometry, kernel parity, byte-exact
oracle, fused-grid integration and the live chunked broker.

The load-bearing properties:

  * chunk geometry round-trips (split -> reassemble identity) and the
    content-addressed store's chunk index always reassembles to the
    canonical artifact;
  * ``chunk_tick_pallas`` == ``chunk_tick_ref`` == the production
    ``acs`` scan path, bit-for-bit, on random inputs (the kernel's
    conformance surface);
  * the byte-exact oracle (``oracle.check_content_trace``) closes the
    loop across scan / Pallas / real-payload-store / whole-artifact
    baseline on every workload family;
  * the fused engine runs a whole (family x locality x volatility)
    content grid as ONE compiled program per chunk size, Pallas route
    bit-identical to scan, and delta coherence strictly dominates
    whole-artifact lazy;
  * the live broker ships actual chunk deltas that clients patch into
    byte-exact copies, and its captured trace replays through the
    content oracle against the live wire ledger.
"""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.content.chunks import (BYTES_PER_TOKEN, ChunkStore,
                                  apply_delta, chunk_digest, chunk_sizes,
                                  n_chunks, reassemble, split_chunks)
from repro.core import acs
from repro.core.protocol import ArtifactStore
from repro.kernels.chunk_diff import chunk_tick_pallas, chunk_tick_ref
from repro.kernels.mesi_transition import mesi_tick_pallas
from repro.sim import engine, workloads
from repro.sim import oracle

pytestmark = pytest.mark.content


# ---------------------------------------------------------------------------
# Geometry + content-addressed store.


class TestChunkGeometry:
    @pytest.mark.parametrize("T,ct,C,last", [
        (4096, 512, 8, 512), (4096, 1000, 5, 96), (96, 16, 6, 16),
        (100, 40, 3, 20), (7, 8, 1, 7)])
    def test_sizes(self, T, ct, C, last):
        assert n_chunks(T, ct) == C
        sizes = chunk_sizes(T, ct)
        assert sizes.sum() == T and sizes[-1] == last
        assert (sizes[:-1] == ct).all()

    def test_split_reassemble_identity(self, rng):
        for _ in range(10):
            T = int(rng.integers(1, 200))
            ct = int(rng.integers(1, 64))
            content = rng.integers(0, 1000, T).tolist()
            chunks = split_chunks(content, ct)
            assert len(chunks) == n_chunks(T, ct)
            assert reassemble(chunks) == tuple(content)

    def test_apply_delta_patches(self):
        base = list(range(20))
        new = list(base)
        new[8:16] = [99] * 8
        delta = ((1, tuple(new[8:16])),)
        assert apply_delta(base, delta, 8) == tuple(new)

    def test_digest_is_content_address(self):
        assert chunk_digest([1, 2, 3]) == chunk_digest((1, 2, 3))
        assert chunk_digest([1, 2, 3]) != chunk_digest([1, 2, 4])

    def test_chunk_store(self):
        store = ArtifactStore()
        store.put("a", list(range(100)))
        cs = ChunkStore(store, 40)
        cs.register("a")
        assert cs.n_chunks_of("a") == 3
        assert cs.reassembled("a") == tuple(range(100))
        new = list(range(100))
        new[0] = 777
        mask = cs.put("a", new)
        np.testing.assert_array_equal(mask, [True, False, False])
        assert cs.reassembled("a") == tuple(new)
        assert tuple(store.get("a")) == tuple(new)
        # delta serves exactly the requested chunks
        delta = cs.delta("a", [0, 2])
        assert [i for i, _ in delta] == [0, 2]
        assert delta[0][1] == tuple(new[:40])
        # identical chunks are deduplicated by digest
        store2 = ArtifactStore()
        store2.put("x", [5] * 80)
        cs2 = ChunkStore(store2, 40)
        cs2.register("x")
        assert cs2.n_unique_chunks == 1

    def test_chunk_count_change_rejected(self):
        store = ArtifactStore()
        store.put("a", list(range(100)))
        cs = ChunkStore(store, 40)
        cs.register("a")
        with pytest.raises(ValueError, match="chunk count"):
            cs.put("a", list(range(140)))


# ---------------------------------------------------------------------------
# Kernel parity: pallas == ref == production scan bodies.


def _random_chunk_inputs(rng, B, n, m, C):
    cv = rng.integers(1, 6, (B, m, C)).astype(np.int32)
    cs = np.minimum(rng.integers(0, 6, (B, n, m, C)), cv[:, None]) \
        .astype(np.int32)
    dirty = (cv > 1).astype(np.int32)
    miss = rng.integers(0, 2, (B, n)).astype(np.int32)
    wact = (rng.integers(0, 2, (B, n)) & miss |
            rng.integers(0, 2, (B, n))).astype(np.int32)
    arts = rng.integers(0, m, (B, n)).astype(np.int32)
    wmask = rng.integers(0, 2, (B, n, C)).astype(np.int32)
    return cv, cs, dirty, miss, wact, arts, wmask


@pytest.mark.pallas
class TestChunkDiffKernel:
    @pytest.mark.parametrize("B,n,m,C", [(4, 3, 2, 5), (16, 4, 3, 4),
                                         (34, 2, 2, 7)])
    def test_matches_ref(self, B, n, m, C):
        rng = np.random.default_rng(B * 31 + C)
        inputs = _random_chunk_inputs(rng, B, n, m, C)
        T, ct = C * 16 - 3, 16   # ragged last chunk
        out_p = chunk_tick_pallas(
            *[jnp.asarray(x) for x in inputs], artifact_tokens=T,
            chunk_tokens=ct, block_sims=16, interpret=True)
        out_r = chunk_tick_ref(*inputs, artifact_tokens=T,
                               chunk_tokens=ct)
        for got, want, label in zip(out_p, out_r,
                                    ("cv", "cs", "dirty", "fetched",
                                     "counters")):
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want), label)

    def test_matches_production_scan(self):
        """Kernel pair (mesi miss output -> chunk tick) == acs scan
        bodies over a multi-step episode."""
        cfg = acs.ACSConfig(n_agents=4, n_artifacts=3,
                            artifact_tokens=96, n_steps=1,
                            chunk_tokens=16, write_locality=0.3)
        n, m, C = 4, 3, acs.content_chunks(cfg)
        key = jax.random.PRNGKey(7)
        arrays, met = acs.init_arrays(cfg), acs.init_metrics()
        st = jnp.zeros((1, n, m), jnp.int32)
        ver = jnp.ones((1, m), jnp.int32)
        sy = jnp.zeros((1, n, m), jnp.int32)
        rd = jnp.zeros((1, n, m), jnp.int32)
        cv = jnp.ones((1, m, C), jnp.int32)
        cs = jnp.zeros((1, n, m, C), jnp.int32)
        dirty = jnp.zeros((1, m, C), jnp.int32)
        tot = np.zeros(4, np.int64)
        for step in range(8):
            k = jax.random.fold_in(key, step)
            a, d, w = acs.draw_actions(k, n, m, 0.5, 0.9)
            wch = acs.draw_write_chunks(k, n, C, 0.3)
            arrays, met, out = acs.apply_actions(
                cfg, arrays, met, a, d, w, write_chunks=wch)
            ai = jnp.asarray(a, jnp.int32)[None]
            wi = jnp.asarray(w, jnp.int32)[None]
            st, ver, sy, rd, _, miss = mesi_tick_pallas(
                st, ver, sy, rd, ai, d[None], wi,
                artifact_tokens=cfg.artifact_tokens, interpret=True)
            cv, cs, dirty, fetched, ccnt = chunk_tick_pallas(
                cv, cs, dirty, miss, ai * wi, d[None],
                jnp.asarray(wch, jnp.int32)[None],
                artifact_tokens=cfg.artifact_tokens,
                chunk_tokens=cfg.chunk_tokens, interpret=True)
            tot += np.asarray(ccnt[0], np.int64)
            np.testing.assert_array_equal(
                np.asarray(out.miss, np.int32), np.asarray(miss[0]))
            np.testing.assert_array_equal(
                np.asarray(out.fetched_chunks, np.int32),
                np.asarray(fetched[0]))
        np.testing.assert_array_equal(np.asarray(arrays.chunk_version),
                                      np.asarray(cv[0]))
        np.testing.assert_array_equal(np.asarray(arrays.chunk_sync),
                                      np.asarray(cs[0]))
        np.testing.assert_array_equal(np.asarray(arrays.chunk_dirty),
                                      np.asarray(dirty[0]))
        assert int(met.delta_bytes) == tot[0]
        assert int(met.full_bytes) == tot[1]
        assert int(met.n_chunks_fetched) == tot[2]


# ---------------------------------------------------------------------------
# Byte-exact oracle.

_SMALL = dict(n_agents=4, n_artifacts=3, n_runs=2, artifact_tokens=96,
              n_steps=8, chunk_tokens=16)


@pytest.mark.differential
class TestContentOracle:
    @pytest.mark.parametrize("family", sorted(workloads.FAMILIES))
    def test_families_byte_exact(self, family):
        w = workloads.make(family, **_SMALL)
        rep = oracle.content_differential_check(w)
        assert rep.ledger.delta_bytes <= rep.ledger.full_bytes
        assert "chunk_store" in rep.implementations
        assert "run_episode" in rep.implementations

    def test_access_count_strategy(self):
        w = workloads.make("zipf", strategy=acs.ACCESS_COUNT,
                           access_k=2, **_SMALL)
        rep = oracle.content_differential_check(w)
        assert rep.ledger.n_chunks_fetched > 0

    def test_ragged_chunks(self):
        # 96 tokens / 40-token chunks -> sizes (40, 40, 16)
        w = workloads.make("pipeline", **{**_SMALL,
                                          "chunk_tokens": 40})
        rep = oracle.content_differential_check(w)
        assert rep.chunk_version.shape[-1] == 3

    def test_strict_dominance_with_writes(self):
        """Any workload that re-fetches after a partial-span write
        ships strictly fewer bytes than whole-artifact lazy."""
        w = workloads.make("ping_pong", **_SMALL).with_locality(0.2)
        rep = oracle.content_differential_check(w)
        assert rep.ledger.delta_bytes < rep.ledger.full_bytes

    def test_full_locality_collapses_to_whole_artifact(self):
        """write_locality=1.0 dirties every chunk, so delta == full on
        every fill: the content plane degrades exactly to the paper's
        whole-artifact cost model."""
        w = workloads.make("ping_pong", **_SMALL).with_locality(1.0)
        rep = oracle.content_differential_check(w)
        assert rep.ledger.delta_bytes == rep.ledger.full_bytes

    def test_detects_corrupted_byte_ledger(self):
        """Sensitivity: a perturbed write span must break the
        conformance (the harness is not vacuous)."""
        w = workloads.make("bursty", **_SMALL)
        key = oracle.episode_key(w.seed, 0)
        trace = oracle.sample_trace(w.acs, key, w.rates(),
                                    locality=w.write_locality)
        writes = trace.acts & trace.writes
        if not writes.any():
            pytest.skip("no writes sampled")
        # complement every write span: any post-write re-fetch now
        # ships a different chunk set
        wc = trace.write_chunks.copy()
        wc[writes] = ~wc[writes]
        bad = dataclasses.replace(trace, write_chunks=wc)
        met = acs.run_episode(w.acs, key, rates=w.rates(),
                              locality=w.write_locality)
        rep = oracle.check_content_trace(w.acs, bad, name="perturbed")
        # the internally-consistent replay of the PERTURBED trace must
        # disagree with the engine's ledger for the true trace
        assert (rep.ledger.delta_bytes != int(met.delta_bytes)
                or rep.ledger.n_chunks_fetched
                != int(met.n_chunks_fetched))

    def test_rejects_unsupported_configs(self):
        w = workloads.make("zipf", strategy=acs.EAGER, **_SMALL)
        with pytest.raises(ValueError, match="content plane"):
            acs.init_arrays(w.acs)
        with pytest.raises(ValueError):
            oracle.check_content_trace(
                w.acs, oracle.Trace(
                    acts=np.zeros((8, 4), bool),
                    arts=np.zeros((8, 4), np.int32),
                    writes=np.zeros((8, 4), bool)))


# ---------------------------------------------------------------------------
# Fused engine integration.


class TestEngineContentGrid:
    def _zoo(self, **overrides):
        base = dict(chunk_tokens=24, n_steps=8, artifact_tokens=96)
        base.update(overrides)
        return [w.with_overrides(**base)
                for w in workloads.zoo(n_agents=4, n_artifacts=3,
                                       n_runs=2)]

    def test_one_compilation_and_dominance(self):
        zoo = self._zoo()
        with engine.trace_counter() as tc:
            cmps = engine.compare_workloads(zoo, tick_backend="scan")
            assert tc.count == 1
            engine.compare_workloads(zoo, tick_backend="scan")
            assert tc.count == 1, "steady-state rerun retraced"
        per_ep = (8 * 4 * 3 * (96 + acs.SIGNAL_TOKENS)
                  * BYTES_PER_TOKEN)
        for c in cmps:
            co, bc = c.coherent, c.broadcast
            assert 0 < co.delta_bytes_mean <= co.full_bytes_mean
            assert bc.delta_bytes_mean == per_ep  # analytic baseline
            assert co.full_bytes_mean < bc.delta_bytes_mean

    @pytest.mark.pallas
    def test_pallas_route_bit_identical(self):
        zoo = self._zoo()
        a = engine.compare_workloads(zoo, tick_backend="scan")
        b = engine.compare_workloads(zoo, tick_backend="pallas")
        for x, y in zip(a, b):
            assert (x.coherent.delta_bytes_mean
                    == y.coherent.delta_bytes_mean)
            assert (x.coherent.full_bytes_mean
                    == y.coherent.full_bytes_mean)
            assert (x.coherent.n_chunks_fetched_mean
                    == y.coherent.n_chunks_fetched_mean)
            assert (x.coherent.total_tokens_mean
                    == y.coherent.total_tokens_mean)

    def test_locality_and_volatility_are_traced(self):
        """Sweeping locality or volatility re-uses the compiled grid
        (they are traced operands, not baked constants) - and the
        results actually move."""
        zoo = self._zoo()
        with engine.trace_counter() as tc:
            lo = engine.compare_workloads(
                [w.with_locality(0.1) for w in zoo],
                tick_backend="scan")
            hi = engine.compare_workloads(
                [w.with_locality(1.0) for w in zoo],
                tick_backend="scan")
            assert tc.count == 1, "locality sweep must not retrace"
        for l, h in zip(lo, hi):
            assert (l.coherent.delta_bytes_mean
                    <= h.coherent.delta_bytes_mean)
        assert any(l.coherent.delta_bytes_mean
                   < h.coherent.delta_bytes_mean
                   for l, h in zip(lo, hi))

    def test_disabled_plane_reports_sentinels(self):
        w = workloads.make("zipf", n_agents=4, n_artifacts=3, n_runs=2,
                           artifact_tokens=96, n_steps=8)
        res = engine.run_workload(w, tick_backend="scan")
        assert res.stats.delta_bytes_mean == -1.0
        assert res.stats.full_bytes_mean == -1.0

    def test_token_ledger_unchanged_by_content_plane(self):
        """Enabling chunks must not move a single token counter - the
        content plane is a byte-accounting overlay, not a semantics
        change."""
        plain = workloads.make("bursty", n_agents=4, n_artifacts=3,
                               n_runs=2, artifact_tokens=96, n_steps=8)
        chunked = plain.with_overrides(chunk_tokens=16)
        a = engine.run_workload(plain, tick_backend="scan")
        b = engine.run_workload(chunked, tick_backend="scan")
        np.testing.assert_array_equal(a.per_run_total_tokens,
                                      b.per_run_total_tokens)


# ---------------------------------------------------------------------------
# Live chunked broker.


def _broker_config(backend="auto", chunk_tokens=24):
    from repro.service import CoherenceConfig
    return CoherenceConfig.make(
        4, ("plan", "notes", "scratch"), artifact_tokens=96,
        strategy="lazy", backend=backend, chunk_tokens=chunk_tokens)


async def _scripted_session(cfg):
    """Deterministic client script exercising cold fills, span writes,
    delta re-fetches, hits and a no-op write."""
    from repro.service import CoherenceBroker, make_clients, verify_broker
    async with CoherenceBroker(cfg) as broker:
        clients = make_clients(broker)
        for c in clients:
            r = await c.read("plan")
            assert not r.hit and len(r.delta) == 4   # cold: all chunks
        new = list(broker.store.get("plan"))
        new[:24] = [111] * 24
        w = await clients[0].write("plan", new)
        assert w.dirty_chunks == (0,)
        r = await clients[1].read("plan")
        assert not r.hit and [i for i, _ in r.delta] == [0]
        assert r.delta[0][1] == tuple(new[:24])
        r2 = await clients[1].read("plan")
        assert r2.hit and r2.delta == () and r2.delta_bytes == 0
        w2 = await clients[2].write("notes",
                                    list(broker.store.get("notes")))
        assert w2.dirty_chunks == ()        # measured no-op
        # two writers of one artifact in one conceptual exchange
        newer = list(new)
        newer[48:72] = [222] * 24
        await clients[3].write("plan", newer)
        r3 = await clients[1].read("plan")
        assert not r3.hit
        verify_broker(broker)
        return dict(broker.wire), broker.stats()


@pytest.mark.service
class TestChunkedBroker:
    def test_scripted_session_scan(self):
        wire, stats = asyncio.run(_scripted_session(
            _broker_config(backend="scan")))
        assert 0 < wire["delta_bytes"] < wire["full_bytes"]
        assert stats["wire"]["bytes_savings_vs_full"] > 0

    @pytest.mark.pallas
    def test_scan_and_pallas_routes_agree(self):
        wire_s, _ = asyncio.run(_scripted_session(
            _broker_config(backend="scan")))
        wire_p, _ = asyncio.run(_scripted_session(
            _broker_config(backend="pallas")))
        assert wire_s == wire_p

    def test_client_mirror_catches_bad_delta(self):
        """White-box: a client patching a WRONG delta must raise - the
        mirror check is not vacuous."""
        from repro.service.broker import ReadResult
        from repro.service.client import CoherentClient, DeltaMismatch

        class _FakeBroker:
            config = _broker_config()

            async def read(self, agent, artifact):
                return ReadResult(tuple(range(96)), 1, False, 0.0,
                                  delta=((0, tuple(range(24)),),),
                                  delta_bytes=0)

        client = CoherentClient(_FakeBroker(), 0)
        client._mirror["plan"] = tuple([7] * 96)   # stale local copy
        with pytest.raises(DeltaMismatch):
            asyncio.run(client.read("plan"))

    def test_content_verify_catches_corruption(self):
        """White-box: corrupt the live chunk index after the run - the
        content leg of verify_broker must fire."""
        from repro.service import CoherenceBroker, make_clients
        from repro.service.trace import verify_broker_content

        async def run():
            async with CoherenceBroker(_broker_config()) as broker:
                clients = make_clients(broker)
                await clients[0].read("plan")
                new = list(broker.store.get("plan"))
                new[0] = 9
                await clients[1].write("plan", new)
                await clients[0].read("plan")
                # corrupt: silently drop a chunk version bump
                arrays = broker.decider.arrays
                broker.decider.arrays = arrays._replace(
                    chunk_version=arrays.chunk_version.at[0, 0].add(-1))
                with pytest.raises(oracle.ConformanceError):
                    verify_broker_content(broker)

        asyncio.run(run())

    def test_trace_json_roundtrip_with_chunks(self):
        from repro.service import CoherenceBroker, make_clients
        from repro.service.trace import ServiceTrace

        async def run():
            async with CoherenceBroker(_broker_config()) as broker:
                clients = make_clients(broker)
                await clients[0].read("plan")
                new = list(broker.store.get("plan"))
                new[30] = 5
                await clients[1].write("plan", new)
                return broker.trace

        trace = asyncio.run(run())
        back = ServiceTrace.from_json(trace.to_json())
        assert back == trace
        ot = back.to_oracle_trace()
        assert ot.write_chunks is not None
        assert ot.write_chunks.any()


# ---------------------------------------------------------------------------
# Golden byte-ledger regression.


@pytest.mark.slow
def test_golden_content_ledgers(golden):
    """Exact byte ledgers for a fixed mini-grid; regenerate only via
    ``pytest --update-golden`` with the diff in review."""
    payload = {}
    for family in ("bursty", "ping_pong"):
        for ct in (16, 40):
            w = workloads.make(family, **{**_SMALL, "chunk_tokens": ct})
            rep = oracle.content_differential_check(w)
            payload[f"{family}/ct{ct}"] = {
                "delta_bytes": rep.ledger.delta_bytes,
                "full_bytes": rep.ledger.full_bytes,
                "n_chunks_fetched": rep.ledger.n_chunks_fetched,
                "n_fills": len(rep.fills),
            }
    golden("content", payload)
