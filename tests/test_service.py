"""Coherence-service tier: the asyncio broker under true interleaving.

Covers the load-bearing properties of ``repro.service`` (see
tests/README.md "Service tier"):

  * invariant safety under concurrency - SWMR / monotonic versioning /
    bounded staleness checked live on every micro-batch, with many
    concurrent clients and adversarial ping-pong rates;
  * the live-service <-> conformance loop - captured ``ServiceTrace``s
    replay bit-exactly through the four-way differential oracle and
    match the live ledger / directory / versions;
  * scan vs Pallas decision backends produce identical ledgers;
  * adapters (framework shims), the sync portal, the TCP frontend and
    the example demo all run without any framework installed.

Async tests run via ``asyncio.run`` inside plain pytest functions (no
pytest-asyncio dependency).
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro.core import acs
from repro.core.states import MESIState
from repro.service import (CoherenceBroker, CoherenceConfig, CoherentClient,
                           CoherentTool, InvariantViolation, ServicePortal,
                           ServiceTrace, autogen_functions, crewai_tool,
                           drive_workload, langgraph_node, verify_broker)
from repro.service import batching
from repro.service.batching import resolve_decide_backend
from repro.sim import engine, workloads

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent

pytestmark = pytest.mark.service


def _names(m: int) -> tuple:
    return tuple(f"artifact-{d}" for d in range(m))


def _config(n: int = 8, m: int = 4, tokens: int = 64,
            **kw) -> CoherenceConfig:
    return CoherenceConfig.make(n, _names(m), artifact_tokens=tokens, **kw)


def _workload(family: str, n: int = 8, m: int = 4, tokens: int = 64,
              **kw):
    return workloads.make(family, n_agents=n, n_artifacts=m,
                          artifact_tokens=tokens, n_steps=10, **kw)


# ---------------------------------------------------------------------------
# Broker semantics.


def test_read_write_semantics():
    async def main():
        async with CoherenceBroker(_config()) as broker:
            r = await broker.read(0, "artifact-0")
            assert not r.hit and r.version == 1
            assert len(r.content) == 64
            r = await broker.read(0, "artifact-0")
            assert r.hit          # coherent copy: free
            w = await broker.write(1, "artifact-0",
                                   content=[7] * 64)
            assert w.version == 2
            r = await broker.read(0, "artifact-0")
            assert not r.hit and r.version == 2   # invalidated by peer
            assert r.content == (7,) * 64
            r = await broker.read(1, "artifact-0")
            assert r.hit          # the writer keeps a coherent copy (S)
        led = broker.ledger
        assert led.n_reads == 4 and led.n_writes == 1
        assert led.n_hits == 2 and led.n_fetches == 3
    asyncio.run(main())


def test_concurrent_requests_coalesce():
    """Concurrent distinct-agent requests land in one micro-batch; a
    same-agent duplicate spills to the next batch (one serialized slot
    per agent per pass)."""
    async def main():
        async with CoherenceBroker(_config()) as broker:
            await asyncio.gather(*(
                broker.read(a, "artifact-1") for a in range(8)))
            assert broker.n_batches == 1
            assert broker.trace.steps[0].agents == tuple(range(8))
            # two in-flight requests from one agent -> two batches
            await asyncio.gather(broker.read(3, "artifact-0"),
                                 broker.read(3, "artifact-2"))
            assert broker.n_batches == 3
    asyncio.run(main())


def test_rejects_bad_requests():
    async def main():
        async with CoherenceBroker(_config()) as broker:
            with pytest.raises(KeyError):
                await broker.read(0, "nope")
            with pytest.raises(ValueError):
                await broker.read(99, "artifact-0")
            with pytest.raises(ValueError):
                await broker.write(0, "artifact-0", content=[1, 2])
    asyncio.run(main())


@pytest.mark.parametrize("artifacts,knobs,match", [
    (("a",), dict(strategy="broadcast"), "broker serves"),
    (("a",), dict(strategy="ttl"), "broker serves"),
    (("a", "a"), {}, "duplicate artifact"),
    (("a",), dict(strategy="eager", chunk_tokens=24), "chunked broker"),
    # a chunked broker with K-staleness on could never be oracle-verified
    # (the content harness covers K=0 only), so it must be
    # unconstructible rather than silently unverifiable
    (("a",), dict(chunk_tokens=24, max_stale_steps=2), "K-staleness"),
], ids=["broadcast", "ttl", "duplicate", "eager-chunked",
        "chunked-k-staleness"])
def test_config_rejects_unservable(artifacts, knobs, match):
    with pytest.raises(ValueError, match=match):
        CoherenceConfig.make(2, artifacts, artifact_tokens=96, **knobs)


# ---------------------------------------------------------------------------
# Invariant safety under concurrency.


def test_stress_concurrent_ping_pong_invariants():
    """Many clients, adversarial ping-pong write rates, jittered
    open-loop interleaving: per-batch invariant checks stay green and
    the captured trace replays bit-exactly through the oracle."""
    async def main():
        w = _workload("ping_pong", n=16, m=4)
        cfg = _config(n=16, m=4, check_invariants=True)
        async with CoherenceBroker(cfg) as broker:
            rep = await drive_workload(broker, w, n_rounds=12, seed=11,
                                       lockstep=False,
                                       think_time_s=0.002)
            assert rep.n_actions > 50
            assert broker.n_batches > 12   # interleaving split rounds
            # quiescent directory: no E/M persists, versions monotone
            assert (broker.directory_state < int(MESIState.E)).all()
            assert (broker.versions >= 1).all()
            report = verify_broker(broker, name="stress:ping_pong")
            assert set(report.implementations) >= {
                "protocol", "vectorized", "pallas", "model_check"}
        return broker
    asyncio.run(main())


def test_stress_bounded_staleness_enforced():
    """K-staleness enforcement on the live broker: the served-hit
    staleness metric never exceeds K (the per-batch invariant check
    raises otherwise)."""
    async def main():
        w = _workload("rag", n=12, m=4)
        cfg = _config(n=12, m=4, max_stale_steps=3, backend="scan")
        async with CoherenceBroker(cfg) as broker:
            await drive_workload(broker, w, n_rounds=15, seed=2,
                                 lockstep=False, think_time_s=0.001)
            consumed = int(broker.decider.metrics.max_consumed_staleness)
            assert consumed <= 3
    asyncio.run(main())


def test_invariant_checker_fires_on_corruption():
    """White-box: corrupt the directory (two M holders) and the next
    flush must raise InvariantViolation - proving the checks are armed,
    not decorative."""
    async def main():
        async with CoherenceBroker(_config()) as broker:
            await broker.read(0, "artifact-0")
            a = broker.decider.arrays
            broker.decider.arrays = a._replace(
                state=a.state.at[0:2, 0].set(int(MESIState.M)))
            with pytest.raises(InvariantViolation):
                await broker.read(1, "artifact-1")
    asyncio.run(main())


# ---------------------------------------------------------------------------
# The live-service <-> conformance loop.


@pytest.mark.differential
@pytest.mark.parametrize("strategy", ["lazy", "eager", "access_count"])
def test_oracle_replay_lockstep(strategy):
    async def main():
        w = _workload("hierarchical", n=8, m=4)
        cfg = _config(strategy=strategy, access_k=3)
        async with CoherenceBroker(cfg) as broker:
            await drive_workload(broker, w, n_rounds=10, seed=4)
            report = verify_broker(broker, name=f"lockstep:{strategy}")
            assert report.strategy == strategy
    asyncio.run(main())


@pytest.mark.differential
def test_trace_roundtrip_and_replay():
    """ServiceTrace JSON round-trips and the deserialized trace replays
    to the same ledger as the live broker charged."""
    async def main():
        w = _workload("pipeline", n=6, m=3)
        async with CoherenceBroker(_config(n=6, m=3)) as broker:
            await drive_workload(broker, w, n_rounds=8, seed=6)
            return broker
    broker = asyncio.run(main())
    trace = ServiceTrace.from_json(broker.trace.to_json())
    assert trace.n_actions == broker.trace.n_actions
    from repro.service.trace import replay_trace
    report = replay_trace(trace, name="roundtrip")
    assert report.ledger.fetch_tokens == broker.ledger.fetch_tokens
    assert report.ledger.n_hits == broker.ledger.n_hits


@pytest.mark.pallas
def test_pallas_backend_matches_scan():
    """Identical lockstep load through both decision routes: ledgers,
    directory, versions and traces must agree bit-for-bit (and both
    replay through the oracle)."""
    async def run(backend):
        w = _workload("bursty", n=8, m=4)
        cfg = _config(strategy="eager", backend=backend)
        async with CoherenceBroker(cfg) as broker:
            await drive_workload(broker, w, n_rounds=10, seed=9)
            verify_broker(broker, name=f"backend:{backend}")
            return broker

    b_scan = asyncio.run(run("scan"))
    b_pal = asyncio.run(run("pallas"))
    assert b_pal.decider.backend == "pallas"
    assert (dataclasses.astuple(b_scan.ledger)
            == dataclasses.astuple(b_pal.ledger))
    assert np.array_equal(b_scan.directory_state, b_pal.directory_state)
    assert np.array_equal(b_scan.versions, b_pal.versions)
    # identical decisions step for step (latencies are wall-clock and
    # excluded)
    for s1, s2 in zip(b_scan.trace.steps, b_pal.trace.steps):
        assert (s1.agents, s1.arts, s1.writes, s1.miss, s1.version) == \
               (s2.agents, s2.arts, s2.writes, s2.miss, s2.version)


#: directory fields both decision routes maintain (``last_validate`` and
#: ``agent_actions`` are scan-route staleness diagnostics)
_ROUTE_FIELDS = ("state", "version", "last_sync", "reads_since_fetch",
                 "chunk_version", "chunk_sync", "chunk_dirty")


@pytest.mark.pallas
@pytest.mark.parametrize("strategy,chunk_tokens", [
    ("lazy", 0), ("eager", 0), ("access_count", 0), ("lazy", 16)])
def test_kernel_route_matches_scan_batch_for_batch(strategy, chunk_tokens):
    """One seeded batch sequence through both deciders: every request's
    miss and served version, every batch's ledger (and wire) deltas and
    fetched chunks, and the final directory agree bit for bit."""
    from repro.service.batching import (_LEDGER_FIELDS, _WIRE_FIELDS,
                                        BatchDecider)

    n, m = 9, 4
    cfg = _config(n=n, m=m, tokens=64, strategy=strategy, access_k=2,
                  chunk_tokens=chunk_tokens).acs_config()
    scan = BatchDecider(cfg, backend="scan")
    kernel = BatchDecider(cfg, backend="pallas")
    assert (scan.backend, kernel.backend) == ("scan", "pallas")
    rng = np.random.default_rng(2026)
    for _ in range(12):
        acts = rng.random(n) < 0.7
        arts = rng.integers(0, m, n).astype(np.int32)
        writes = rng.random(n) < 0.3
        chunks = ((rng.random((n, 64 // chunk_tokens)) < 0.4)
                  & (acts & writes)[:, None] if chunk_tokens else None)
        a = scan.decide(acts, arts, writes, chunks)
        b = kernel.decide(acts, arts, writes, chunks)
        assert np.array_equal(a.miss[acts], b.miss[acts])
        assert np.array_equal(a.version[acts], b.version[acts])
        assert a.ledger_delta == b.ledger_delta
        assert a.wire_delta == b.wire_delta
        if chunk_tokens:
            assert np.array_equal(a.fetched_chunks[acts],
                                  b.fetched_chunks[acts])
    for f in _ROUTE_FIELDS:
        x, y = getattr(scan.arrays, f), getattr(kernel.arrays, f)
        assert (x is None) == (y is None), f
        assert x is None or np.array_equal(np.asarray(x), np.asarray(y)), f
    fields = _LEDGER_FIELDS + (_WIRE_FIELDS if chunk_tokens else ())
    assert ([int(getattr(scan.metrics, f)) for f in fields]
            == [int(getattr(kernel.metrics, f)) for f in fields])


def test_backend_resolution_guards():
    cfg = _config(max_stale_steps=2).acs_config()
    assert resolve_decide_backend(cfg, "auto") == "scan"
    with pytest.raises(ValueError):
        resolve_decide_backend(cfg, "pallas")


@pytest.mark.parametrize("resolve,strategy,arg,tpu,route", [
    ("decide", acs.LAZY, "auto", True, "pallas"),
    ("decide", acs.LAZY, "auto", False, "scan"),
    ("decide", acs.LAZY, "scan", True, "scan"),
    ("tick", acs.LAZY, engine.PALLAS_MIN_BATCH - 1, True, "scan"),
    ("tick", acs.LAZY, engine.PALLAS_MIN_BATCH, True, "pallas"),
    ("tick", acs.BROADCAST, 10_000, True, "scan"),
], ids=["decide-auto-tpu", "decide-auto-cpu", "decide-scan-tpu",
        "tick-below-min-batch", "tick-at-min-batch", "tick-broadcast"])
def test_route_follows_arguments_and_platform(monkeypatch, resolve,
                                              strategy, arg, tpu, route):
    """A route is a function of the caller's argument, the strategy, the
    batch size and the platform, and of nothing else."""
    for module in (batching, engine):
        monkeypatch.setattr(module, "interpret_default", lambda: not tpu)
    cfg = acs.ACSConfig(n_agents=8, n_artifacts=4, artifact_tokens=64,
                        n_steps=1, strategy=strategy)
    if resolve == "decide":
        assert batching.resolve_decide_backend(cfg, arg) == route
    else:
        assert engine.resolve_tick_backend(cfg, arg) == route


# ---------------------------------------------------------------------------
# Adapters + portal.


def test_adapters_over_one_portal():
    config = _config(n=4, m=3, tokens=32)
    with ServicePortal(config) as portal:
        # CrewAI-style sync tool
        tool = crewai_tool(portal.client(0))
        out = tool.run("write", "artifact-0", "hello coherence")
        assert "version 2" in out
        # the committed writer keeps a coherent (S) copy
        assert "coherent cache" in tool.run("read", "artifact-0")

        # AutoGen-style function map (sync flavor); first read from a
        # peer agent is a coherence fill
        schemas, fmap = autogen_functions(portal.client(1))
        assert {s["name"] for s in schemas} == {"read_artifact",
                                                "write_artifact"}
        assert "authority fetch" in fmap["read_artifact"]("artifact-0")
        assert "coherent cache" in fmap["read_artifact"]("artifact-0")
        assert "v2" in fmap["read_artifact"]("artifact-0")

        # LangGraph-style async node, driven on the portal loop
        node = langgraph_node(CoherentClient(portal.broker, 2),
                              reads=("artifact-0", "artifact-1"))
        update = portal.call(node({"artifact_updates":
                                   {"artifact-1": "notes v1"}}))
        assert update["artifact_versions"]["artifact-1"] == 2
        assert update["artifacts"]["artifact-0"][:5] == (104, 101, 108,
                                                         108, 111)
        # framework-neutral tool spec is OpenAI-function shaped
        spec = CoherentTool(portal.client(3)).spec
        assert spec["parameters"]["required"] == ["operation",
                                                  "artifact"]
        verify_broker(portal.broker, name="adapters")


def test_coherent_tool_async_guard():
    async def main():
        async with CoherenceBroker(_config(n=2, m=2, tokens=16)) as broker:
            tool = CoherentTool(CoherentClient(broker, 0))
            with pytest.raises(TypeError):
                tool("read", "artifact-0")     # sync call on async client
            res = await tool.acall("read", "artifact-0")
            assert res.version == 1 and not res.hit
    asyncio.run(main())


# ---------------------------------------------------------------------------
# TCP frontend + entry point + example.


def test_tcp_frontend_smoke():
    from repro.launch.service import serve_tcp

    async def main():
        async with CoherenceBroker(_config(n=4, m=2, tokens=16)) as broker:
            server = await serve_tcp(broker, "127.0.0.1", 0)
            port = server.sockets[0].getsockname()[1]
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", port)

            async def rpc(obj):
                writer.write(json.dumps(obj).encode() + b"\n")
                await writer.drain()
                return json.loads(await reader.readline())

            r = await rpc({"op": "read", "agent": 0,
                           "artifact": "artifact-0"})
            assert r["ok"] and r["version"] == 1 and not r["hit"]
            w = await rpc({"op": "write", "agent": 1,
                           "artifact": "artifact-0"})
            assert w["ok"] and w["version"] == 2
            r = await rpc({"op": "read", "agent": 0,
                           "artifact": "artifact-0"})
            assert r["version"] == 2 and not r["hit"]
            s = await rpc({"op": "stats"})
            assert s["stats"]["decision"]["n_actions"] == 3
            bad = await rpc({"op": "read", "agent": 0,
                             "artifact": "nope"})
            assert not bad["ok"] and "unknown artifact" in bad["error"]
            writer.close()
            server.close()
            await server.wait_closed()
    asyncio.run(main())


def test_launch_cli_verify_smoke():
    from repro.launch import service as launch_service
    summary = launch_service.main([
        "--family", "uniform", "--clients", "6", "--artifacts", "3",
        "--artifact-tokens", "32", "--rounds", "6", "--verify"])
    assert summary["oracle"]["bit_exact"]
    assert summary["actions"] == summary["oracle"]["n_actions"]
    assert 0.0 <= summary["savings_vs_broadcast"] <= 1.0


@pytest.mark.slow
def test_example_demo_smoke():
    proc = subprocess.run(
        [sys.executable, str(REPO_ROOT / "examples" /
                             "coherent_service_demo.py"), "--smoke"],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": str(REPO_ROOT / "src"),
             "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"},
        cwd=str(REPO_ROOT))
    assert proc.returncode == 0, proc.stderr
    assert "oracle replay: bit-exact" in proc.stdout


# ---------------------------------------------------------------------------
# Perf-gate plumbing for BENCH_service.json.


def _gate(argv):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_gate", REPO_ROOT / "scripts" / "bench_gate.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.main(argv)


@pytest.mark.slow
def test_bench_gate_service_replay_and_injection(capsys):
    assert _gate(["--replay-baseline"]) == 0
    assert _gate(["--replay-baseline",
                  "--inject-latency-regression", "4.0"]) == 1
    assert _gate(["--replay-baseline",
                  "--inject-savings-drift", "0.05"]) == 1
    out = capsys.readouterr().out
    assert "service.p99_ms" in out
    assert "service.savings" in out
