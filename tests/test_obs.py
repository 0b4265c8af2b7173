"""Observability tier: metrics registry, MESI perf counters, span
tracing and the metrics-conformance oracle leg.

Covers the load-bearing properties of ``repro.obs`` (see
tests/README.md "Observability tier"):

  * the registry is exact - counters are plain Python ints, label
    cells never alias, snapshots round-trip through JSON and the
    Prometheus rendering is parseable line-oriented text;
  * metrics conformance - replaying the captured ``ServiceTrace``
    through a fresh telemetry plane reproduces every replayable
    counter bit-identically, for the plain broker AND the K-shard
    plane, on every workload family; a white-box corruption of a
    single live counter cell makes the oracle go red;
  * span lifecycle under true concurrency - adversarial ping-pong
    clients produce request + decide spans whose Chrome-trace JSON
    round-trips with the documented schema;
  * phase spans: one record per committed batch (one per sweep call),
    whose phases tile the batch, whose build time is charged to the
    batch that paid it, and which reach a profiler trace nested in the
    benchmark's annotations; the record ring keeps every request of
    the batches it holds;
  * the unified stats schema, trace schema v4 round-trips (any other
    schema version is refused), the ``metrics`` TCP verb, and the
    build-event log.

Async tests run via ``asyncio.run`` inside plain pytest functions (no
pytest-asyncio dependency).
"""

from __future__ import annotations

import asyncio
import json

import numpy as np
import pytest

from repro.obs import (MetricsConformanceError, MetricsRegistry,
                       Telemetry, check_metrics_conformance)
from repro.obs import runtime as obs_runtime
from repro.service import (CoherenceBroker, CoherenceConfig, ServiceTrace,
                           connect, drive_workload)
from repro.sim import workloads

pytestmark = pytest.mark.obs

FAMILIES = tuple(workloads.FAMILIES)


def _names(m: int) -> tuple:
    return tuple(f"artifact-{d}" for d in range(m))


def _config(n: int = 6, m: int = 4, tokens: int = 64,
            **kw) -> CoherenceConfig:
    return CoherenceConfig.make(n, _names(m), artifact_tokens=tokens, **kw)


def _workload(family: str, n: int = 6, m: int = 4, tokens: int = 64,
              **kw):
    return workloads.make(family, n_agents=n, n_artifacts=m,
                          artifact_tokens=tokens, n_steps=8, **kw)


# ---------------------------------------------------------------------------
# Metrics registry.


def test_counter_exact_and_labeled():
    reg = MetricsRegistry()
    c = reg.counter("coh_test_total", "help text")
    c.inc(3, shard=0)
    c.inc(shard=0)
    c.inc(5, shard=1)
    assert reg.counter_value("coh_test_total", shard=0) == 4
    assert reg.counter_value("coh_test_total", shard=1) == 5
    assert reg.counter_total("coh_test_total") == 9
    assert isinstance(reg.counter_total("coh_test_total"), int)
    # label order must not mint a second cell
    c.inc(1, a=1, b=2)
    c.inc(1, b=2, a=1)
    assert reg.counter_value("coh_test_total", a=1, b=2) == 2
    # get-or-create returns the same object; a kind clash is an error
    assert reg.counter("coh_test_total") is c
    with pytest.raises(TypeError):
        reg.gauge("coh_test_total")


def test_histogram_window_and_percentiles():
    reg = MetricsRegistry()
    h = reg.histogram("coh_lat", window=8)
    for v in range(100):
        h.observe(float(v))
    cell = h.cell()
    assert cell.count == 100 and cell.sum == sum(range(100))
    assert len(cell.ring) == 8          # bounded memory
    assert cell.min == 0.0 and cell.max == 99.0
    assert cell.percentile(50) >= 92.0  # window keeps the newest values


def test_snapshot_and_prometheus_round_trip():
    reg = MetricsRegistry()
    reg.counter("coh_a_total", "a").inc(7, shard=0)
    reg.gauge("coh_g", "g").set(2.5)
    reg.histogram("coh_h", "h").observe(1.0)
    snap = json.loads(json.dumps(reg.snapshot()))
    assert snap["counters"]["coh_a_total"]["values"][0]["value"] == 7
    assert snap["gauges"]["coh_g"]["values"][0]["value"] == 2.5
    assert snap["histograms"]["coh_h"]["values"][0]["count"] == 1
    prom = reg.to_prometheus()
    assert '# TYPE coh_a_total counter' in prom
    assert 'coh_a_total{shard="0"} 7' in prom
    for line in prom.splitlines():
        assert line.startswith("#") or len(line.rsplit(" ", 1)) == 2


def _batches(config: CoherenceConfig, rounds: int, per_round: int,
             capacity: int = 1 << 14) -> CoherenceBroker:
    """``rounds`` batches of ``per_round`` reads each, on a broker whose
    span records hold ``capacity`` batches."""
    async def main():
        tel = Telemetry(config.n_agents, span_capacity=capacity)
        async with CoherenceBroker(config, telemetry=tel) as broker:
            for r in range(rounds):
                await asyncio.gather(*(
                    broker.read(a, f"artifact-{(a + r) % len(config.artifacts)}")
                    for a in range(per_round)))
            return broker
    return asyncio.run(main())


def test_span_recorder_bounded():
    broker = _batches(_config(n=6, m=2), rounds=10, per_round=3,
                      capacity=4)
    rec = broker.telemetry.spans
    assert broker.n_batches == 10
    # exact count survives eviction: 10 batch spans + 30 request spans
    assert rec.n_recorded == 40
    assert len(rec.records) == 4
    trace = rec.chrome_trace()
    cats = [e["cat"] for e in trace["traceEvents"]]
    assert cats.count("batch") == 4 and cats.count("request") == 12
    ev = json.loads(rec.to_chrome_json())["traceEvents"][0]
    assert ev["ph"] == "X" and {"name", "cat", "ts", "dur", "pid",
                                "tid"} <= set(ev)


def test_request_views_count_every_request_past_the_span_count():
    """The ring counts batches, not spans: 40 requests in 5 batches fit
    a ring of 8, and every one of them is a request span."""
    broker = _batches(_config(n=8, m=3), rounds=5, per_round=8,
                      capacity=8)
    rec = broker.telemetry.spans
    reqs = [s for s in rec.spans if s.cat == "request"]
    assert len(reqs) == 40 == broker.ledger.n_reads
    assert rec.n_recorded == 45
    assert all(s.args["queue_s"] >= 0.0 for s in reqs)
    assert len({(s.tid, s.ts_s) for s in reqs}) == 40


# ---------------------------------------------------------------------------
# Metrics conformance: live counters == trace replay, bit for bit.


def test_metrics_conformance_all_families_plain():
    async def run(family):
        w = _workload(family)
        async with CoherenceBroker(_config()) as broker:
            await drive_workload(broker, w, 8, seed=11)
            return check_metrics_conformance(broker, name=family)
    for family in FAMILIES:
        report = run_ = asyncio.run(run(family))
        assert report["bit_exact"], (family, run_)
        assert report["counters_compared"] >= 15
        assert report["histograms_compared"] == 2


@pytest.mark.sharded
def test_metrics_conformance_all_families_sharded():
    cfg = CoherenceConfig.make(6, _names(5), artifact_tokens=64,
                               shards=4, hosts=2)

    async def run(family):
        w = _workload(family, m=5)
        async with connect(cfg) as broker:
            await drive_workload(broker, w, 8, seed=11)
            return check_metrics_conformance(broker, name=family)
    for family in FAMILIES:
        report = asyncio.run(run(family))
        assert report["bit_exact"], (family, report)
        assert report["l1_fills_conserved"], (family, report)


def test_metrics_corruption_goes_red():
    """White-box: bump one live counter cell by one - the conformance
    oracle must refuse to call the registry bit-exact."""
    async def main():
        w = _workload("uniform" if "uniform" in FAMILIES else
                      FAMILIES[0])
        async with CoherenceBroker(_config()) as broker:
            await drive_workload(broker, w, 8, seed=3)
            broker.telemetry.registry.counter(
                "coh_fetch_tokens_total").inc(1, shard=0)
            with pytest.raises(MetricsConformanceError):
                check_metrics_conformance(broker)
    asyncio.run(main())


def test_conformance_requires_telemetry_and_capture():
    async def main():
        async with CoherenceBroker(_config(telemetry=False)) as broker:
            await broker.read(0, "artifact-0")
            assert broker.telemetry is None
            with pytest.raises(ValueError):
                check_metrics_conformance(broker)
    asyncio.run(main())


# ---------------------------------------------------------------------------
# MESI perf counters + spans under adversarial concurrency.


def test_pingpong_spans_and_detectors():
    """Two writers flip one artifact while readers hammer it: the
    ping-pong detector fires, every request gets a span, and the
    Chrome trace round-trips."""
    async def main():
        async with CoherenceBroker(_config(n=6, m=2)) as broker:
            for _ in range(6):
                await asyncio.gather(
                    broker.write(0, "artifact-0"),
                    broker.write(1, "artifact-0"),
                    *(broker.read(a, "artifact-0") for a in (2, 3, 4)))
            # sequential tail: agent 5's fill is invalidated by the
            # next write in a LATER batch, so the batch-granular
            # valid->I transition becomes observable
            await broker.read(5, "artifact-0")
            await broker.write(0, "artifact-0")
            return broker
    broker = asyncio.run(main())
    tel = broker.telemetry
    reg = tel.registry
    assert reg.counter_total("coh_pingpong_alternations_total") > 0
    assert reg.counter_total("coh_invalidation_events_total") > 0
    n_reqs = broker.ledger.n_reads + broker.ledger.n_writes
    trace = tel.chrome_trace()
    reqs = [e for e in trace["traceEvents"] if e["cat"] == "request"]
    decides = [e for e in trace["traceEvents"] if e["cat"] == "batch"]
    assert len(reqs) == n_reqs
    assert len(decides) == broker.n_batches
    for ev in reqs:
        assert ev["args"]["queue_s"] >= 0.0
        assert ev["args"]["decide_s"] >= 0.0
    json.loads(tel.spans.to_chrome_json())    # schema is valid JSON
    assert check_metrics_conformance(broker)["bit_exact"]


def test_staleness_counter_matches_versions():
    """Sequential requests are always served the authority head, so
    staleness-at-serve is exactly 0 for every read; one observation
    per served read either way."""
    async def main():
        async with CoherenceBroker(_config(n=3, m=1)) as broker:
            await broker.read(0, "artifact-0")
            for _ in range(3):
                await broker.write(1, "artifact-0")
            await broker.read(0, "artifact-0")
            return broker.telemetry.registry.histogram_totals(
                "coh_staleness_at_serve")
    totals = asyncio.run(main())
    (count, total), = totals.values()
    assert count == 2 and total == 0


# ---------------------------------------------------------------------------
# Unified stats schema.


def test_stats_nested_schema():
    async def main():
        async with CoherenceBroker(_config()) as broker:
            await broker.read(0, "artifact-0")
            await broker.write(1, "artifact-0")
            return broker.stats()
    stats = asyncio.run(main())
    assert stats["schema_version"] == 1
    for section in ("topology", "decision", "ledger", "latency",
                    "telemetry", "mesi"):
        assert section in stats, section
    assert stats["decision"]["n_actions"] == 2
    assert stats["decision"]["n_batches"] == 2
    assert stats["ledger"]["n_reads"] == 1
    # the protocol's state plane is S/I-valued (writers retain S)
    assert stats["mesi"]["occupancy"]["S"] >= 1
    assert stats["mesi"]["occupancy"]["I"] >= 1
    assert stats["mesi"]["invalidation_events"] >= 1


# ---------------------------------------------------------------------------
# Trace schema v4.


def test_trace_v4_round_trip_and_older_schemas_refused():
    async def main():
        async with CoherenceBroker(_config()) as broker:
            await asyncio.gather(*(
                broker.read(a, "artifact-1") for a in range(6)))
            await broker.write(0, "artifact-1")
            return broker.trace
    trace = asyncio.run(main())
    payload = json.loads(trace.to_json())
    assert payload["schema_version"] == 4
    assert payload["steps"][0]["batch_size"] == 6
    assert payload["steps"][0]["decide_s"] > 0.0
    back = ServiceTrace.from_json(trace.to_json())
    assert [s.decide_s for s in back.steps] == \
        [s.decide_s for s in trace.steps]
    rep = back.latency_report()
    assert rep["n_steps"] == 2 and rep["max_batch"] == 6
    assert rep["decide_s_total"] > 0.0
    # a trace of any other schema version is outside input: refused
    for step in payload["steps"]:
        del step["decide_s"], step["batch_size"]
    payload["schema_version"] = 3
    with pytest.raises(ValueError, match="schema_version 3"):
        ServiceTrace.from_json(json.dumps(payload))


# ---------------------------------------------------------------------------
# TCP frontend `metrics` verb + launcher --verify-metrics.


def test_tcp_metrics_verb():
    from repro.launch.service import serve_tcp

    async def rpc(reader, writer, obj):
        writer.write(json.dumps(obj).encode() + b"\n")
        await writer.drain()
        return json.loads(await reader.readline())

    async def main():
        async with CoherenceBroker(_config(n=4, m=2, tokens=16)) as broker:
            server = await serve_tcp(broker, "127.0.0.1", 0)
            port = server.sockets[0].getsockname()[1]
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", port)
            await rpc(reader, writer, {"op": "read", "agent": 0,
                                       "artifact": "artifact-0"})
            m = await rpc(reader, writer, {"op": "metrics"})
            assert m["ok"]
            assert "coh_fetch_tokens_total" in m["prometheus"]
            assert m["snapshot"]["counters"]["coh_reads_total"][
                "values"][0]["value"] == 1
            writer.close()
            server.close()
            await server.wait_closed()

    async def disabled():
        cfg = _config(n=4, m=2, tokens=16, telemetry=False)
        async with CoherenceBroker(cfg) as broker:
            server = await serve_tcp(broker, "127.0.0.1", 0)
            port = server.sockets[0].getsockname()[1]
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", port)
            m = await rpc(reader, writer, {"op": "metrics"})
            assert not m["ok"] and "telemetry" in m["error"]
            writer.close()
            server.close()
            await server.wait_closed()
    asyncio.run(main())
    asyncio.run(disabled())


def test_launch_verify_metrics_smoke():
    from repro.launch import service as launch_service
    summary = launch_service.main([
        "--family", "uniform", "--clients", "5", "--artifacts", "3",
        "--artifact-tokens", "32", "--rounds", "5", "--verify-metrics"])
    report = summary["metrics_conformance"]
    assert report["bit_exact"]
    assert report["counters_compared"] >= 15


# ---------------------------------------------------------------------------
# Phase spans and build accounting.

#: the broker's phases directly under broker.batch, and the decider's
#: under broker.decide
BATCH_PHASES = ("broker.cut", "broker.stage", "broker.decide",
                "broker.checks", "broker.respond", "broker.telemetry")
DECIDE_PHASES = ("broker.decide.stage", "broker.decide.call",
                 "broker.decide.readback", "broker.decide.outcomes")


def test_compile_log_records_fresh_trace():
    before = obs_runtime.compile_count("broker.decide.call")
    # a shape no other test uses -> guaranteed fresh jit trace
    broker = _batches(_config(n=11, m=3, tokens=48), rounds=1,
                      per_round=1)
    assert obs_runtime.compile_count("broker.decide.call") >= before + 1
    built = [e for e in obs_runtime.compile_events()
             if e["route"] == "broker.decide.call"]
    assert {"trace", "lower", "compile"} <= {e["kind"] for e in built}
    assert all(e["dur_s"] > 0.0 for e in built)
    assert broker.stats()["telemetry"]["compile_traces"] >= 1
    compiles = [e for e in broker.telemetry.chrome_trace()["traceEvents"]
                if e["cat"] == "compile"]
    assert compiles and all(e["tid"] == "jit" for e in compiles)


def test_phases_tile_the_batch():
    """Scan route: the broker's phases cover its batch, and the
    decider's its decide, to within 3 %."""
    broker = _batches(_config(n=48, m=5), rounds=8, per_round=48)
    records = list(broker.telemetry.spans.records)
    assert len(records) == broker.n_batches == 8
    steady = records[1:]                # the first batch compiles
    for rec in steady:
        assert set(BATCH_PHASES + DECIDE_PHASES) <= set(rec.phases)
        for name in BATCH_PHASES:
            assert rec.phases[name][3] == "broker.batch"
        for name in DECIDE_PHASES:
            assert rec.phases[name][3] == "broker.decide"
        assert sum(rec.seconds(name) for name in BATCH_PHASES) \
            <= rec.flush_s
        assert rec.seconds("broker.decide") >= rec.decide_s
        assert rec.self_seconds("broker.decide") >= 0.0
    flush = sum(rec.flush_s for rec in steady)
    decide = sum(rec.seconds("broker.decide") for rec in steady)
    assert sum(rec.self_seconds("broker.batch") for rec in steady) \
        <= 0.03 * flush
    assert sum(rec.self_seconds("broker.decide") for rec in steady) \
        <= 0.03 * decide


def test_build_time_is_charged_to_the_batch_that_paid_it():
    """Scan route: the first batch of a fresh shape traces, lowers and
    compiles its decider; every later batch builds nothing."""
    broker = _batches(_config(n=13, m=5, tokens=40), rounds=4,
                      per_round=13)
    first, *later = broker.telemetry.spans.records
    assert first.trace_s > 0 and first.lower_s > 0 and first.compile_s > 0
    assert first.n_builds >= 1
    assert first.build_s <= first.seconds("broker.decide")
    for rec in later:
        assert rec.build_s == 0.0 and rec.n_builds == 0


@pytest.mark.pallas
@pytest.mark.parametrize("chunk_tokens", [0, 20], ids=["plain", "content"])
def test_kernel_route_builds_once(chunk_tokens):
    """Kernel route (interpret mode here): the first batch of a fresh
    shape builds the decision programs; every later batch, reads and
    writes alike, dispatches them from the jit cache."""
    config = _config(n=10, m=5, tokens=80, chunk_tokens=chunk_tokens,
                     backend="pallas")
    calls = []

    async def main():
        tel = Telemetry(config.n_agents)
        async with CoherenceBroker(config, telemetry=tel) as broker:
            for r in range(4):
                await asyncio.gather(*(
                    broker.write(a, f"artifact-{(a + r) % 5}",
                                 [r * 100 + a] * 80)
                    if (a + r) % 3 == 0 else
                    broker.read(a, f"artifact-{(a + r) % 5}")
                    for a in range(10)))
                calls.append(obs_runtime.compile_count("broker.decide.call"))
            return broker

    broker = asyncio.run(main())
    assert broker.decider.backend == "pallas"
    first, *later = broker.telemetry.spans.records
    assert len(later) == 3
    assert first.n_builds >= 1
    for rec in later:
        assert rec.build_s == 0.0 and rec.n_builds == 0
    assert calls[1:] == calls[:1] * 3


def test_phases_reach_the_profiler_trace(tmp_path):
    """A CPU profiler trace holds the phase annotations nested in the
    benchmark's ``broker.flush``, and the benchmark's gap labelling
    names the innermost phase."""
    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    from bench import tracing

    config = _config(n=9, m=3, tokens=32)

    async def main():
        async with CoherenceBroker(config) as broker:
            flush = broker._flush_once

            def annotated():
                with TraceAnnotation("broker.flush"):
                    flush()
            broker._flush_once = annotated
            await broker.read(0, "artifact-0")          # warm
            jax.profiler.start_trace(str(tmp_path))
            try:
                for a in range(3):
                    await broker.read(a, "artifact-1")
            finally:
                jax.profiler.stop_trace()
    asyncio.run(main())
    space = ProfileData.from_file(
        str(sorted(tmp_path.rglob("*.xplane.pb"))[-1]))
    labels = [(ev.start_ns, ev.end_ns, ev.name)
              for plane in space.planes if plane.name == tracing.HOST_PLANE
              for line in plane.lines for ev in line.events
              if ev.name.startswith(tracing.LABEL_PREFIXES)]
    flushes = [iv for iv in labels if iv[2] == "broker.flush"]
    calls = [iv for iv in labels if iv[2] == "broker.decide.call"]
    assert len(flushes) == 3 and len(calls) == 3
    for s, e, _ in calls:
        assert any(fs <= s and e <= fe for fs, fe, _ in flushes)
        assert tracing._label((s + e) / 2, labels) == "broker.decide.call"
    names = {iv[2] for iv in labels}
    assert set(BATCH_PHASES + DECIDE_PHASES) | {"broker.batch"} <= names


def test_sweep_call_leaves_one_record():
    from repro.sim import compare_workloads
    ws = [workloads.make("zipf", n_agents=4, n_artifacts=3,
                         artifact_tokens=32, n_steps=3, n_runs=2,
                         seed=s) for s in (5, 6)]
    n0 = len(obs_runtime.sweep_records())
    for _ in range(2):
        compare_workloads(ws, tick_backend="scan")
    records = obs_runtime.sweep_records()[-2:]
    assert len(obs_runtime.sweep_records()) == n0 + 2
    sweep = {"sweep.operands", "sweep.dispatch", "sweep.readback",
             "sweep.results"}
    for rec in records:
        assert set(rec.phases) == sweep
        assert all(cell[3] is None for cell in rec.phases.values())
        assert sum(rec.seconds(name) for name in sweep) <= rec.wall_s
    assert records[1].build_s <= records[0].build_s
