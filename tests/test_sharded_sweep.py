"""Device-sharded sweep engine: bit-exactness + one-compilation.

The tentpole contract of the sharded path (``repro.sim.engine`` on
``jax.shard_map`` over ``repro.launch.mesh.make_sweep_mesh``):

  1. a sweep sharded over N devices produces **bit-identical** token
     ledgers (per-run totals, cache-hit rates, savings) to the
     single-device path - the per-run key schedule is ``fold_in`` on
     the *global* run index, so device-local position never enters it;
  2. the sharded grid is still ONE compiled XLA program, and
     re-sweeping with new volatilities retraces nothing;
  3. every shard plan (runs axis, workloads-axis fallback, padded
     runs) preserves (1);
  4. a sharded grid cell replays bit-exactly through the differential
     oracle (``repro.sim.oracle``), closing the loop to MESI states
     and versions via the four-way conformance harness.

Multi-device cases need forced host devices (CI's ``sharded`` job)::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m pytest tests/test_sharded_sweep.py -q

On a single-device host those cases skip; the plan-logic tests and a
subprocess end-to-end check (marked ``slow``) still run.
"""

import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.sim import (ShardPlan, canonical, compare_workloads, engine,
                       oracle, run_scenario, shard_plan, sweep_volatility,
                       workloads)

N_DEV = jax.local_device_count()

multi_device = pytest.mark.skipif(
    N_DEV < 2,
    reason="needs >=2 local devices (run under XLA_FLAGS="
           "--xla_force_host_platform_device_count=8)")

pytestmark = pytest.mark.sharded


def small(v=0.25, seed=777, n_runs=8, **kw):
    params = dict(n_steps=6, artifact_tokens=64)
    params.update(kw)
    return dataclasses.replace(
        canonical("sharded-test", v, seed, **params), n_runs=n_runs)


def _zoo(n_runs):
    return workloads.zoo(n_agents=4, n_artifacts=3, n_runs=n_runs,
                         artifact_tokens=64, n_steps=5)


class TestShardPlan:
    """Pure planning logic - runs at any device count."""

    def test_single_device_is_unsharded(self):
        assert shard_plan(4, 8, devices=1) == ShardPlan(1, None, 8)

    def test_devices_capped_at_local_count(self):
        plan = shard_plan(4, 8, devices=10_000)
        assert plan.devices <= N_DEV

    def test_env_override_disables_sharding(self):
        """No environment variable steers the plan: ``devices=None``
        plans over every local device, and a caller disables sharding
        with ``devices=1``."""
        assert shard_plan(4, 8, devices=None).devices == N_DEV

    @pytest.mark.skipif(N_DEV != 1, reason="axis rules need a fixed "
                        "device count; covered multi-device below")
    def test_all_plans_degenerate_on_one_device(self):
        for cells, runs in ((1, 3), (6, 7), (4, 8)):
            assert shard_plan(cells, runs).axis is None


@multi_device
class TestShardPlanMultiDevice:
    def test_runs_axis_preferred(self):
        plan = shard_plan(3, 2 * N_DEV, devices=N_DEV)
        assert plan == ShardPlan(N_DEV, "runs", 2 * N_DEV)

    def test_workloads_axis_fallback(self):
        plan = shard_plan(N_DEV, 2 * N_DEV + 1, devices=N_DEV)
        assert plan == ShardPlan(N_DEV, "workloads", 2 * N_DEV + 1)

    def test_padded_runs_last_resort(self):
        plan = shard_plan(N_DEV + 1, N_DEV + 1, devices=N_DEV)
        assert plan.axis == "runs"
        assert plan.pad_runs == 2 * N_DEV
        assert plan.pad_runs % N_DEV == 0


@multi_device
class TestBitExactness:
    """Sharded == single-device, bit for bit, on every ledger metric."""

    def _assert_same(self, a, b):
        assert a.broadcast.total_tokens_mean == b.broadcast.total_tokens_mean
        assert a.coherent.total_tokens_mean == b.coherent.total_tokens_mean
        assert a.coherent.sync_tokens_mean == b.coherent.sync_tokens_mean
        assert a.savings_mean == b.savings_mean
        assert a.savings_std == b.savings_std
        assert a.crr == b.crr
        assert a.chr_mean == b.chr_mean

    def test_sweep_runs_axis(self):
        base = small(n_runs=N_DEV)
        vols = (0.05, 0.25, 0.75, 1.0)
        for sh, ref in zip(sweep_volatility(base, vols, devices=N_DEV),
                           sweep_volatility(base, vols, devices=1)):
            self._assert_same(sh, ref)

    def test_run_scenario_per_run_ledgers(self):
        scn = small(n_runs=2 * N_DEV)
        sh = run_scenario(scn, devices=N_DEV)
        ref = run_scenario(scn, devices=1)
        np.testing.assert_array_equal(sh.per_run_total_tokens,
                                      ref.per_run_total_tokens)
        np.testing.assert_array_equal(sh.per_run_chr, ref.per_run_chr)

    def test_padded_runs_plan(self):
        # n_runs=3 divides nothing -> runs axis padded to a multiple of
        # the device count; padding must not perturb the real runs.
        scn = small(n_runs=3)
        assert shard_plan(1, 3, devices=N_DEV).pad_runs % N_DEV == 0
        sh = run_scenario(scn, devices=N_DEV)
        ref = run_scenario(scn, devices=1)
        np.testing.assert_array_equal(sh.per_run_total_tokens,
                                      ref.per_run_total_tokens)

    def test_workload_zoo_runs_axis(self):
        zoo = _zoo(n_runs=N_DEV)
        for sh, ref in zip(compare_workloads(zoo, devices=N_DEV),
                           compare_workloads(zoo, devices=1)):
            self._assert_same(sh, ref)

    @pytest.mark.content
    def test_content_grid_byte_ledgers_shard_exactly(self):
        """The chunked grid (extra traced locality operand + chunk
        state in the carry) stays bit-identical under sharding - byte
        ledgers included."""
        zoo = [w.with_overrides(chunk_tokens=16)
               for w in _zoo(n_runs=N_DEV)]
        for sh, ref in zip(compare_workloads(zoo, devices=N_DEV),
                           compare_workloads(zoo, devices=1)):
            self._assert_same(sh, ref)
            assert (sh.coherent.delta_bytes_mean
                    == ref.coherent.delta_bytes_mean)
            assert (sh.coherent.full_bytes_mean
                    == ref.coherent.full_bytes_mean)
            assert (sh.coherent.n_chunks_fetched_mean
                    == ref.coherent.n_chunks_fetched_mean)

    def test_workloads_axis_fallback_path(self):
        # 6 zoo families with a run count that does not divide: on 2,
        # 3 or 6 devices the planner shards the workload axis instead.
        for d in (2, 3, 6):
            if d > N_DEV or 6 % d:
                continue
            zoo = _zoo(n_runs=d + 1)
            assert shard_plan(6, d + 1, devices=d).axis == "workloads"
            for sh, ref in zip(compare_workloads(zoo, devices=d),
                               compare_workloads(zoo, devices=1)):
                self._assert_same(sh, ref)
            return
        pytest.skip(f"no divisor of 6 in 2..{N_DEV}")

    @pytest.mark.pallas
    def test_pallas_tick_route_per_device(self):
        """The kernel route under shard_map matches the single-device
        scan path - per-device Pallas routing changes nothing."""
        scn = small(n_runs=2 * N_DEV)
        sh = run_scenario(scn, tick_backend="pallas", devices=N_DEV)
        ref = run_scenario(scn, tick_backend="scan", devices=1)
        np.testing.assert_array_equal(sh.per_run_total_tokens,
                                      ref.per_run_total_tokens)
        np.testing.assert_array_equal(sh.per_run_chr, ref.per_run_chr)

    def test_oracle_replays_sharded_cells(self):
        """Global-run-index schedule: any sharded cell is the trace the
        differential oracle replays for (seed, run) - which ties the
        sharded ledgers to MESI states/versions via the four-way
        harness."""
        scn = small(n_runs=2 * N_DEV)
        sh = run_scenario(scn, devices=N_DEV)
        for r in (0, N_DEV - 1, 2 * N_DEV - 1):
            trace = oracle.sample_trace(
                scn.acs, oracle.episode_key(scn.seed, r))
            ledger, _, _, _ = oracle.replay_vectorized(scn.acs, trace)
            assert int(sh.per_run_total_tokens[r]) == ledger.total_tokens


@multi_device
class TestOneCompilationSharded:
    def test_sharded_sweep_is_one_program(self):
        base = small(seed=1357, n_runs=N_DEV)
        with engine.trace_counter() as tc:
            sweep_volatility(base, (0.05, 0.10, 0.25, 0.50),
                             devices=N_DEV)
            assert tc.count == 1
            sweep_volatility(base, (0.01, 0.33, 0.66, 0.99),
                             devices=N_DEV)
            assert tc.count == 1

    def test_sharded_zoo_is_one_program(self):
        zoo = _zoo(n_runs=N_DEV)
        with engine.trace_counter() as tc:
            compare_workloads(zoo, devices=N_DEV)
            assert tc.count == 1
            compare_workloads(zoo, devices=N_DEV)
            assert tc.count == 1


@pytest.mark.slow
def test_forced_host_devices_end_to_end():
    """Acceptance check runnable on any host: a subprocess with 8
    forced host CPU devices runs the sharded sweep bit-identical to
    the single-device path in one compilation."""
    script = textwrap.dedent("""
        import dataclasses, numpy as np, jax
        assert jax.local_device_count() == 8, jax.local_device_count()
        from repro.sim import canonical, engine, sweep_volatility
        base = dataclasses.replace(
            canonical("ci-sharded", 0.25, 4242, n_steps=6,
                      artifact_tokens=64), n_runs=8)
        vols = (0.05, 0.10, 0.25, 0.50)
        with engine.trace_counter() as tc:
            sh = sweep_volatility(base, vols, devices=8)
            assert tc.count == 1, tc.count
        ref = sweep_volatility(base, vols, devices=1)
        for a, b in zip(sh, ref):
            assert a.broadcast.total_tokens_mean == \\
                b.broadcast.total_tokens_mean
            assert a.coherent.total_tokens_mean == \\
                b.coherent.total_tokens_mean
            assert a.savings_mean == b.savings_mean
        print("SHARDED-OK")
    """)
    env = dict(os.environ)
    env.update({
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": os.pathsep.join(
            [os.path.join(os.path.dirname(__file__), "..", "src")]
            + env.get("PYTHONPATH", "").split(os.pathsep)).rstrip(
                os.pathsep),
    })
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr
    assert "SHARDED-OK" in proc.stdout
