"""Pallas kernel validation: interpret-mode kernels vs pure-jnp oracles.

Shape/dtype sweeps per kernel + hypothesis property tests for the MESI
tick kernel (which must agree with BOTH the numpy oracle and the
production ACS semantics).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # optional dev dep (requirements-dev.txt)
from hypothesis import given, settings, strategies as st
from numpy.testing import assert_allclose

from repro.core import acs
from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.mesi_transition import (mesi_decision_program,
                                           mesi_tick_pallas)
from repro.kernels.rmsnorm import rmsnorm_pallas

TOLS = {jnp.float32: dict(rtol=1e-5, atol=1e-5),
        jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


class TestRMSNorm:
    @pytest.mark.parametrize("rows,d", [(8, 128), (128, 256), (33, 512),
                                        (1, 2048), (260, 128)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_oracle(self, rows, d, dtype):
        k1, k2 = jax.random.split(jax.random.PRNGKey(rows * d))
        x = rand(k1, (rows, d), dtype)
        w = rand(k2, (d,), dtype)
        out = rmsnorm_pallas(x, w, interpret=True)
        expect = ref.rmsnorm_ref(x, w)
        assert out.dtype == x.dtype
        assert_allclose(np.asarray(out, np.float32),
                        np.asarray(expect, np.float32), **TOLS[dtype])

    def test_batched_shape(self):
        x = rand(jax.random.PRNGKey(0), (4, 16, 256), jnp.float32)
        w = jnp.ones((256,), jnp.float32)
        out = rmsnorm_pallas(x, w, interpret=True)
        assert out.shape == (4, 16, 256)
        assert_allclose(np.asarray(out), np.asarray(ref.rmsnorm_ref(x, w)),
                        rtol=1e-5, atol=1e-5)


class TestFlashAttention:
    @pytest.mark.parametrize("b,hq,hkv,lq,lk,d", [
        (1, 4, 4, 128, 128, 64),     # MHA square
        (2, 8, 2, 128, 256, 64),     # GQA, decode-style suffix
        (1, 8, 1, 256, 256, 128),    # MQA
        (1, 4, 2, 384, 384, 128),    # multi-block q and k
    ])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_oracle(self, b, hq, hkv, lq, lk, d, causal):
        keys = jax.random.split(jax.random.PRNGKey(42), 3)
        q = rand(keys[0], (b, hq, lq, d), jnp.float32)
        k = rand(keys[1], (b, hkv, lk, d), jnp.float32)
        v = rand(keys[2], (b, hkv, lk, d), jnp.float32)
        out = flash_attention_pallas(q, k, v, causal=causal,
                                     block_q=128, block_k=128,
                                     interpret=True)
        expect = ref.attention_ref(q, k, v, causal=causal)
        assert_allclose(np.asarray(out), np.asarray(expect),
                        rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("dtype", [jnp.bfloat16])
    def test_bf16(self, dtype):
        keys = jax.random.split(jax.random.PRNGKey(7), 3)
        q = rand(keys[0], (1, 4, 128, 64), dtype)
        k = rand(keys[1], (1, 2, 128, 64), dtype)
        v = rand(keys[2], (1, 2, 128, 64), dtype)
        out = flash_attention_pallas(q, k, v, interpret=True)
        expect = ref.attention_ref(q, k, v)
        assert out.dtype == dtype
        assert_allclose(np.asarray(out, np.float32),
                        np.asarray(expect, np.float32), **TOLS[dtype])

    def test_block_shape_invariance(self):
        """Softmax statistics must be block-size independent."""
        keys = jax.random.split(jax.random.PRNGKey(3), 3)
        q = rand(keys[0], (1, 2, 256, 64), jnp.float32)
        k = rand(keys[1], (1, 2, 256, 64), jnp.float32)
        v = rand(keys[2], (1, 2, 256, 64), jnp.float32)
        a = flash_attention_pallas(q, k, v, block_q=128, block_k=64,
                                   interpret=True)
        b = flash_attention_pallas(q, k, v, block_q=256, block_k=256,
                                   interpret=True)
        assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


class TestDecodeAttention:
    @pytest.mark.parametrize("b,hq,hkv,l,d", [
        (1, 8, 8, 256, 64),
        (2, 8, 2, 512, 64),
        (4, 16, 2, 1024, 128),
        (1, 8, 1, 256, 128),
    ])
    def test_matches_oracle_full_cache(self, b, hq, hkv, l, d):
        keys = jax.random.split(jax.random.PRNGKey(l), 3)
        q = rand(keys[0], (b, hq, d), jnp.float32)
        kc = rand(keys[1], (b, hkv, l, d), jnp.float32)
        vc = rand(keys[2], (b, hkv, l, d), jnp.float32)
        out = decode_attention_pallas(q, kc, vc, interpret=True)
        expect = ref.decode_attention_ref(q, kc, vc)
        assert_allclose(np.asarray(out), np.asarray(expect),
                        rtol=2e-4, atol=2e-4)

    def test_ragged_kv_lengths(self):
        """One compiled kernel serves any cache occupancy."""
        keys = jax.random.split(jax.random.PRNGKey(5), 3)
        b, hq, hkv, l, d = 4, 8, 2, 512, 64
        q = rand(keys[0], (b, hq, d), jnp.float32)
        kc = rand(keys[1], (b, hkv, l, d), jnp.float32)
        vc = rand(keys[2], (b, hkv, l, d), jnp.float32)
        kv_len = jnp.array([64, 200, 512, 1], jnp.int32)
        out = decode_attention_pallas(q, kc, vc, kv_len, interpret=True)
        expect = ref.decode_attention_ref(q, kc, vc, kv_len)
        assert_allclose(np.asarray(out), np.asarray(expect),
                        rtol=2e-4, atol=2e-4)

    def test_decode_consistent_with_prefill_last_row(self):
        """decode(q_last, cache) == last row of full flash attention."""
        keys = jax.random.split(jax.random.PRNGKey(9), 3)
        b, h, l, d = 1, 4, 256, 64
        q = rand(keys[0], (b, h, l, d), jnp.float32)
        k = rand(keys[1], (b, h, l, d), jnp.float32)
        v = rand(keys[2], (b, h, l, d), jnp.float32)
        full = flash_attention_pallas(q, k, v, causal=True, interpret=True)
        dec = decode_attention_pallas(q[:, :, -1], k, v, interpret=True)
        assert_allclose(np.asarray(dec), np.asarray(full[:, :, -1]),
                        rtol=2e-4, atol=2e-4)


def _random_tick_inputs(rng, B, n, m):
    state = rng.integers(0, 2, (B, n, m)).astype(np.int32)  # I or S
    version = rng.integers(1, 5, (B, m)).astype(np.int32)
    sync = np.where(state > 0, version[:, None, :], 0).astype(np.int32)
    reads = rng.integers(0, 3, (B, n, m)).astype(np.int32)
    acts = rng.integers(0, 2, (B, n)).astype(np.int32)
    arts = rng.integers(0, m, (B, n)).astype(np.int32)
    writes = rng.integers(0, 2, (B, n)).astype(np.int32)
    return state, version, sync, reads, acts, arts, writes


class TestMESITickKernel:
    @pytest.mark.parametrize("B,n,m", [(4, 4, 3), (16, 3, 2), (64, 8, 4),
                                       (130, 4, 3)])
    @pytest.mark.parametrize("eager,access_k", [(False, 0), (True, 0),
                                                (False, 3)])
    def test_matches_numpy_oracle(self, B, n, m, eager, access_k):
        rng = np.random.default_rng(B * n + m)
        inputs = _random_tick_inputs(rng, B, n, m)
        out = mesi_tick_pallas(*[jnp.asarray(x) for x in inputs],
                               artifact_tokens=4096, eager=eager,
                               access_k=access_k, block_sims=32,
                               interpret=True)
        exp_state, exp_ver, exp_sync, exp_reads, cnt = ref.mesi_tick_ref(
            *inputs, artifact_tokens=4096, eager=eager, access_k=access_k)
        np.testing.assert_array_equal(np.asarray(out[0]), exp_state)
        np.testing.assert_array_equal(np.asarray(out[1]), exp_ver)
        np.testing.assert_array_equal(np.asarray(out[2]), exp_sync)
        np.testing.assert_array_equal(np.asarray(out[3]), exp_reads)
        counters = np.asarray(out[4])
        np.testing.assert_array_equal(counters[:, 0], cnt["fetch_tokens"])
        np.testing.assert_array_equal(counters[:, 1], cnt["signal_tokens"])
        np.testing.assert_array_equal(counters[:, 2], cnt["push_tokens"])
        np.testing.assert_array_equal(counters[:, 3], cnt["n_fetches"])
        np.testing.assert_array_equal(counters[:, 4], cnt["n_hits"])

    @given(data=st.data())
    @settings(max_examples=20, deadline=None)
    def test_matches_production_acs_tick(self, data):
        """Kernel semantics == repro.core.acs tick (lazy), the production
        state machine, on arbitrary action vectors."""
        n, m = 3, 2
        cfg = acs.ACSConfig(n_agents=n, n_artifacts=m, artifact_tokens=64,
                            n_steps=1, strategy=acs.LAZY)
        arrays = acs.init_arrays(cfg)
        met = acs.init_metrics()
        script = data.draw(st.lists(
            st.tuples(st.booleans(), st.integers(0, m - 1), st.booleans()),
            min_size=n, max_size=n))
        acts = np.array([int(s[0]) for s in script], np.int32)
        arts = np.array([s[1] for s in script], np.int32)
        writes = np.array([int(s[2]) for s in script], np.int32)
        # replay through acs eagerly
        for a, (act, d, w) in enumerate(script):
            if not act:
                continue
            arrays = arrays._replace(
                agent_actions=arrays.agent_actions.at[a].add(1))
            if w:
                arrays, met = acs._do_write(cfg, arrays, met, a, d)
            else:
                arrays, met = acs._do_read(cfg, arrays, met, a, d)
        out = mesi_tick_pallas(
            jnp.zeros((1, n, m), jnp.int32),
            jnp.ones((1, m), jnp.int32),
            jnp.zeros((1, n, m), jnp.int32),
            jnp.zeros((1, n, m), jnp.int32),
            jnp.asarray(acts)[None], jnp.asarray(arts)[None],
            jnp.asarray(writes)[None],
            artifact_tokens=64, interpret=True)
        np.testing.assert_array_equal(np.asarray(out[0][0]),
                                      np.asarray(arrays.state))
        np.testing.assert_array_equal(np.asarray(out[1][0]),
                                      np.asarray(arrays.version))
        assert int(out[4][0, 0]) == int(met.fetch_tokens)
        assert int(out[4][0, 1]) == int(met.signal_tokens)
        assert int(out[4][0, 3]) == int(met.n_fetches)
        assert int(out[4][0, 4]) == int(met.n_hits)

    def test_swmr_preserved_by_kernel(self):
        rng = np.random.default_rng(0)
        inputs = _random_tick_inputs(rng, 64, 6, 4)
        out = mesi_tick_pallas(*[jnp.asarray(x) for x in inputs],
                               artifact_tokens=16, interpret=True)
        state = np.asarray(out[0])
        assert ((state == 3).sum(axis=1) <= 1).all()


#: (eager, access_k) of the three strategies the served kernel route takes
_STRATEGIES = {"lazy": (False, 0), "eager": (True, 0),
               "access_count": (False, 2)}


def _prefix_answers(directory, acts, arts, writes, **kw):
    """Each request's fill bit and served version the way the served
    route once derived them: ``n+1`` copies of the one directory, copy
    ``j`` enabling only the first ``j`` acting agents, through the
    kernel with its answers off; request ``j`` filled iff the fetch
    counter rose from copy ``j`` to ``j+1``, and was served the synced
    cell copy ``j+1`` ends with."""
    n = acts.shape[0]
    order = np.flatnonzero(acts)
    acts_b = np.zeros((n + 1, n), np.int32)
    for j, a in enumerate(order):
        acts_b[j + 1:, a] = 1
    tile = lambda x: jnp.broadcast_to(x, (n + 1,) + x.shape)  # noqa: E731
    state, version, sync, reads = directory
    out = mesi_tick_pallas(tile(state), tile(version), tile(sync),
                           tile(reads), jnp.asarray(acts_b), tile(arts),
                           tile(writes), block_sims=8, interpret=True, **kw)
    cnt, sy = np.asarray(out[4]), np.asarray(out[2])
    miss = np.zeros((n,), bool)
    served = np.zeros((n,), np.int32)
    for j, a in enumerate(order):
        miss[a] = cnt[j + 1, 3] - cnt[j, 3] == 1
        served[a] = sy[j + 1, a, arts[a]]
    return [o[-1] for o in out[:5]], miss, served


def _decide(directory, acts, arts, writes, **kw):
    full, answers = mesi_decision_program(
        *directory, jnp.asarray(acts), jnp.asarray(arts),
        jnp.asarray(writes), signal_tokens=12, interpret=True, **kw)
    return full, np.asarray(answers)


class TestServedDecisionAnswers:
    """The served decision's one-sim kernel writes every request's fill
    bit and served version, as the n+1 prefix sims derived them."""

    @pytest.mark.parametrize("strategy", list(_STRATEGIES))
    def test_one_sim_answers_match_prefix_derivation(self, strategy):
        eager, access_k = _STRATEGIES[strategy]
        kw = dict(artifact_tokens=64, eager=eager, access_k=access_k)
        n, m = 9, 3
        rng = np.random.default_rng(17)
        directory = (jnp.zeros((n, m), jnp.int32), jnp.ones((m,), jnp.int32),
                     jnp.zeros((n, m), jnp.int32),
                     jnp.zeros((n, m), jnp.int32))
        for _ in range(8):
            acts = (rng.random(n) < 0.7).astype(np.int32)
            arts = rng.integers(0, m, n).astype(np.int32)
            writes = (rng.random(n) < 0.3).astype(np.int32)
            last, miss, served = _prefix_answers(directory, acts, arts,
                                                 writes, **kw)
            full, answers = _decide(directory, acts, arts, writes, **kw)
            assert answers.shape == (2, n)
            np.testing.assert_array_equal(answers[0], miss.astype(np.int32))
            np.testing.assert_array_equal(answers[1], served)
            for x, y in zip(full, last):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
            directory = full[:4]

    def test_served_version_precedes_a_later_eager_push(self):
        """Agent 0 reads artifact 0, then agent 2 writes it: the push
        moves agent 0's synced cell to the new version, but agent 0 was
        served the version it read."""
        kw = dict(artifact_tokens=64, eager=True, access_k=0)
        n, m = 4, 2
        directory = (jnp.zeros((n, m), jnp.int32), jnp.ones((m,), jnp.int32),
                     jnp.zeros((n, m), jnp.int32),
                     jnp.zeros((n, m), jnp.int32))
        acts = np.array([1, 0, 1, 0], np.int32)
        arts = np.array([0, 0, 0, 1], np.int32)
        writes = np.array([0, 0, 1, 0], np.int32)
        _, miss, served = _prefix_answers(directory, acts, arts, writes,
                                          **kw)
        full, answers = _decide(directory, acts, arts, writes, **kw)
        final_sync = np.asarray(full[2])
        assert final_sync[0, 0] == 2 and int(full[4][2]) > 0   # pushed
        np.testing.assert_array_equal(answers[1], [1, 0, 2, 0])
        np.testing.assert_array_equal(answers[1], served)
        np.testing.assert_array_equal(answers[0], [1, 0, 1, 0])
        np.testing.assert_array_equal(answers[0], miss.astype(np.int32))

    @pytest.mark.parametrize("strategy", list(_STRATEGIES))
    def test_answers_off_keeps_the_sweep_outputs(self, strategy):
        """At the sweep's shape the kernel without answers returns the
        numpy oracle's transition and (B, n) fill bits, the same the
        answering kernel writes in its first row."""
        eager, access_k = _STRATEGIES[strategy]
        kw = dict(artifact_tokens=4096, eager=eager, access_k=access_k,
                  block_sims=128, interpret=True)
        inputs = _random_tick_inputs(np.random.default_rng(5), 256, 8, 6)
        off = mesi_tick_pallas(*[jnp.asarray(x) for x in inputs], **kw)
        on = mesi_tick_pallas(*[jnp.asarray(x) for x in inputs],
                              answers=True, **kw)
        assert off[5].shape == (256, 8) and on[5].shape == (256, 2, 8)
        for x, y in zip(off[:5], on[:5]):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        np.testing.assert_array_equal(np.asarray(off[5]),
                                      np.asarray(on[5][:, 0]))
        exp = ref.mesi_tick_ref(*inputs, artifact_tokens=4096, eager=eager,
                                access_k=access_k)
        for x, y in zip(off[:4], exp[:4]):
            np.testing.assert_array_equal(np.asarray(x), y)
        np.testing.assert_array_equal(np.asarray(off[4][:, 3]),
                                      exp[4]["n_fetches"])
        np.testing.assert_array_equal(
            np.asarray(off[5]).sum(axis=1), exp[4]["n_fetches"])
