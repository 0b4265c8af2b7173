"""Sharded-service tier: the K-shard authority plane + host L1s.

Covers the load-bearing properties of ``repro.service.sharding`` and
the layered config surface (see tests/README.md "Sharded-service
tier"):

  * hash-of-artifact routing is stable and partitions the directory;
  * K in {1, 2, 4} produce **bit-identical** token ledgers, MESI
    directories and versions on an adversarial cross-shard ping-pong
    workload, and the K=4 trace survives the full conformance closure
    (four-way oracle + cross-shard decomposition + L1/L2 legs);
  * the chunked content plane survives sharding byte-exactly;
  * L1 fill attribution and the explicit L1-invalidation path behave,
    and a stale L1 entry past the version-lag bound raises
    ``InvariantViolation`` (white-box);
  * ``connect(...)`` resolves topologies to the right implementation,
    and ``CoherenceConfig.make`` routes each knob to its layer.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json

import numpy as np
import pytest

from repro.configs import (CoherenceConfig, CoherenceCore, ServiceLayer,
                           ShardTopology, shard_of_artifact)
from repro.service import (CoherenceBroker, HostL1Directory,
                           InvariantViolation, ServicePortal,
                           ShardedCoherenceBroker, connect, resolve_broker,
                           verify_broker)
from repro.service.trace import verify_sharded_broker
from repro.sim import oracle

pytestmark = [pytest.mark.service, pytest.mark.sharded]


def _names(m: int) -> tuple:
    return tuple(f"artifact-{d}" for d in range(m))


def _config(n: int = 4, m: int = 6, tokens: int = 32,
            **kw) -> CoherenceConfig:
    return CoherenceConfig.make(n, _names(m), artifact_tokens=tokens,
                                **kw)


def _ping_pong_schedule(n: int, m: int, rounds: int, seed: int = 7):
    """Adversarial cross-shard ping-pong: every agent alternates
    between writing its 'own' artifact and reading its neighbor's, so
    ownership bounces between shards every round and every read is a
    fresh invalidation miss."""
    rng = np.random.default_rng(seed)
    schedule = []
    for r in range(rounds):
        actions = []
        for a in range(n):
            if (r + a) % 2 == 0:
                actions.append((a, a % m, True))
            else:
                actions.append((a, (a + 1) % m, False))
        if rng.random() < 0.5:          # occasional contended artifact
            actions.append((n - 1, 0, bool(rng.random() < 0.5)))
        schedule.append(actions[:n])    # at most one action per agent
        # dedupe agents (the contended extra may collide)
        seen, uniq = set(), []
        for a, d, w in schedule[-1]:
            if a not in seen:
                seen.add(a)
                uniq.append((a, d, w))
        schedule[-1] = uniq
    return schedule


async def _drive(broker, schedule, names):
    for actions in schedule:
        await asyncio.gather(*(
            broker.write(a, names[d]) if w else broker.read(a, names[d])
            for a, d, w in actions))


def _run_topology(shards: int, hosts: int, rounds: int = 12,
                  verify: bool = False, **kw):
    async def go():
        cfg = _config(shards=shards, hosts=hosts, **kw)
        async with connect(cfg) as broker:
            schedule = _ping_pong_schedule(cfg.n_agents,
                                           len(cfg.artifacts), rounds)
            await _drive(broker, schedule, cfg.artifacts)
            led = dataclasses.astuple(broker.ledger)
            state = np.array(broker.directory_state)
            version = np.array(broker.versions)
            if verify:
                verify_broker(broker)
            return led, state, version, broker.stats()
    return asyncio.run(go())


# ---------------------------------------------------------------------------
# Routing.


def test_shard_routing_stable_and_partitioning():
    # crc32 routing is process-independent: pin the actual values so a
    # refactor to Python's randomized hash() can never slip through
    assert shard_of_artifact("artifact-0", 1) == 0
    for k in (2, 4, 8):
        vals = [shard_of_artifact(f"artifact-{d}", k) for d in range(16)]
        assert all(0 <= v < k for v in vals)
        assert vals == [shard_of_artifact(f"artifact-{d}", k)
                        for d in range(16)]
    cfg = _config(m=6, shards=4)
    owned = cfg.shard_artifact_indices()
    flat = sorted(d for cols in owned for d in cols)
    assert flat == list(range(6))
    for d, s in enumerate(cfg.artifact_shards()):
        assert d in owned[s]


def test_explicit_assignment_overrides_hash():
    cfg = _config(m=4, shards=2, assignment=(0, 0, 1, 1))
    assert cfg.artifact_shards() == (0, 0, 1, 1)
    with pytest.raises(ValueError):
        _config(m=4, shards=2, assignment=(0, 2, 1, 1))


def test_sharded_forbids_simulator_staleness():
    with pytest.raises(ValueError, match="K-staleness|staleness"):
        _config(shards=2, max_stale_steps=2)
    # trivial topology keeps supporting it
    cfg = _config(max_stale_steps=2)
    assert cfg.core.max_stale_steps == 2


# ---------------------------------------------------------------------------
# The tentpole property: sharding changes NOTHING observable.


def test_cross_shard_ping_pong_bit_exact():
    """K in {1, 2, 4} on the adversarial ping-pong: bit-identical
    ledgers, directories and versions; K=4 survives the full
    conformance closure (global four-way + cross-shard + L1/L2)."""
    led1, st1, ver1, _ = _run_topology(1, 1)
    led2, st2, ver2, _ = _run_topology(2, 2)
    led4, st4, ver4, stats4 = _run_topology(4, 2, verify=True)
    assert led1 == led2 == led4
    np.testing.assert_array_equal(st1, st2)
    np.testing.assert_array_equal(st1, st4)
    np.testing.assert_array_equal(ver1, ver2)
    np.testing.assert_array_equal(ver1, ver4)
    assert stats4["topology"]["n_shards"] == 4
    assert sum(stats4["topology"]["shard_artifacts"]) == 6
    assert stats4["l1"]["l1_fills"] + stats4["l1"]["l2_fills"] > 0


@pytest.mark.slow
def test_sharded_chunked_byte_exact():
    """The chunk-granular content plane survives sharding: summed wire
    ledgers equal the single broker's, and the K=2 run passes the
    byte-exact content leg of the sharded verifier."""
    async def go(shards, hosts):
        cfg = _config(n=4, m=6, tokens=64, chunk_tokens=16,
                      shards=shards, hosts=hosts)
        # writers edit ONE 16-token chunk per commit, so the measured
        # dirty set (and hence delta traffic) stays chunk-granular
        docs = {nm: list(range(64)) for nm in cfg.artifacts}
        contents = {nm: list(v) for nm, v in docs.items()}
        async with connect(cfg, contents=contents) as broker:
            for r in range(8):
                jobs = []
                for a in range(4):
                    name = cfg.artifacts[(a + r) % 6]
                    if (r + a) % 3 == 0:
                        lo = ((r + a) % 4) * 16
                        doc = list(docs[name])
                        doc[lo:lo + 16] = [1000 * r + a] * 16
                        docs[name] = doc
                        jobs.append(broker.write(a, name, doc))
                    else:
                        jobs.append(broker.read(a, name))
                await asyncio.gather(*jobs)
            wire = dict(broker.wire)
            led = dataclasses.astuple(broker.ledger)
            if shards > 1:
                verify_sharded_broker(broker)
            return wire, led

    wire1, led1 = asyncio.run(go(1, 1))
    wire2, led2 = asyncio.run(go(2, 2))
    assert led1 == led2
    assert wire1 == wire2
    assert wire2["delta_bytes"] < wire2["full_bytes"]


def test_sharded_trace_records_global_commit_order():
    async def go():
        cfg = _config(m=6, shards=2)
        async with connect(cfg) as broker:
            schedule = _ping_pong_schedule(4, 6, 6)
            await _drive(broker, schedule, cfg.artifacts)
            return broker
    broker = asyncio.run(go())
    trace = broker.trace
    assert trace.n_shards == 2
    assert trace.artifact_shards == broker.artifact_shards
    shards_seen = {s.shard for s in trace.steps}
    assert shards_seen <= {0, 1} and len(shards_seen) == 2
    # every step is homogeneous: one shard's artifacts only
    for step in trace.steps:
        owners = {trace.artifact_shards[d] for d in step.arts}
        assert owners == {step.shard}
    # the cross-shard oracle leg accepts the global order
    oracle.check_sharded_trace(trace.acs_config(),
                               trace.to_oracle_trace(),
                               trace.artifact_shards, name="unit")


def test_shard_subtrace_projection():
    acts = np.array([[1, 1], [1, 0], [0, 1]], bool)
    arts = np.array([[0, 1], [2, 0], [0, 3]], np.int32)
    writes = np.array([[1, 0], [0, 0], [0, 1]], bool)
    trace = oracle.Trace(acts=acts, arts=arts, writes=writes)
    sub, cols = oracle.shard_subtrace(trace, (0, 1, 0, 1), 1)
    np.testing.assert_array_equal(cols, [1, 3])
    # steps 0 (agent 1 -> artifact 1) and 2 (agent 1 -> artifact 3)
    np.testing.assert_array_equal(sub.acts,
                                  [[False, True], [False, True]])
    np.testing.assert_array_equal(sub.arts[sub.acts], [0, 1])
    np.testing.assert_array_equal(sub.writes[sub.acts], [False, True])


# ---------------------------------------------------------------------------
# L1 plane.


def test_l1_attribution_and_invalidation():
    """Same-host re-fills are L1-attributed; a commit invalidates every
    other host's entry, so their next fill crosses to L2 again."""
    async def go():
        # agents 0,1 -> host 0; agents 2,3 -> host 1; one shard so the
        # schedule below is exactly the serialization order
        cfg = _config(m=2, shards=1, hosts=2, placement=(0, 0, 1, 1))
        async with ShardedCoherenceBroker(cfg) as broker:
            name = cfg.artifacts[0]
            await broker.write(2, name)        # v2: host 1 holds a copy
            await broker.read(0, name)         # host 0 cold -> L2 fill
            assert broker.l1_wire["l2_fills"] == 1
            await broker.read(1, name)         # same host, same version
            assert broker.l1_wire["l1_fills"] == 1
            await broker.write(3, name)        # invalidates host 0's L1
            assert broker.l1[0].lookup(name) is None
            # writer's host adopted the committed copy...
            entry = broker.l1[1].lookup(name)
            assert entry is not None and entry.version == 3
            await broker.read(0, name)         # host 0 must go to L2
            assert broker.l1_wire["l2_fills"] == 2
            await broker.read(2, name)         # host 1 serves locally
            assert broker.l1_wire["l1_fills"] == 2
            return dict(broker.l1_wire)
    wire = asyncio.run(go())
    assert wire["l1_bytes"] + wire["l2_bytes"] > 0


def test_l1_staleness_whitebox():
    """A valid L1 entry past the version-lag bound is an invariant
    violation - both at fill-attribution time and in the sweep."""
    async def go():
        cfg = _config(m=2, shards=1, hosts=2, placement=(0, 0, 1, 1))
        async with ShardedCoherenceBroker(cfg) as broker:
            name = cfg.artifacts[0]
            await broker.write(0, name)            # v2, host 0 adopts
            # white-box corruption: resurrect a stale entry on host 1,
            # as if the invalidation signal had been lost
            broker.l1[1].fill(name, 1, tuple(broker.brokers[0]
                                             .store.get(name)))
            await broker.write(0, name)            # v3 -> lag now 2
            broker.l1[1].fill(name, 1, (0,) * 32)  # re-lose the signal
            with pytest.raises(InvariantViolation, match="L1 staleness"):
                broker.check_l1()
            # the read path catches it too, before attributing the fill
            with pytest.raises(InvariantViolation, match="L1 staleness"):
                await broker.read(2, name)
            broker.l1[1].invalidate(name)          # heal for clean stop
    asyncio.run(go())


def test_l1_directory_unit():
    l1 = HostL1Directory(0, max_version_lag=1)
    l1.fill("a", 3, (1, 2))
    assert l1.lookup("a").version == 3
    l1.check("a", 4)                     # lag 1 == bound: fine
    with pytest.raises(InvariantViolation):
        l1.check("a", 5)                 # lag 2 > bound
    l1.invalidate("a")
    assert l1.lookup("a") is None
    assert l1.n_invalidations == 1
    l1.check("a", 99)                    # no entry, nothing to violate


# ---------------------------------------------------------------------------
# connect() resolver + config layering.


def test_connect_resolves_topology():
    trivial = connect(n_agents=2, artifacts=("a",), artifact_tokens=16)
    assert type(trivial) is CoherenceBroker
    sharded = connect(n_agents=2, artifacts=_names(4),
                      artifact_tokens=16, shards=2)
    assert isinstance(sharded, ShardedCoherenceBroker)
    l1_only = connect(n_agents=4, artifacts=("a",), artifact_tokens=16,
                      hosts=2)
    assert isinstance(l1_only, ShardedCoherenceBroker)
    with pytest.raises(TypeError):
        connect()
    with pytest.raises(TypeError):
        connect(_config(), n_agents=3)
    with pytest.raises(TypeError):
        connect(n_agents=2, artifacts=("a",), no_such_knob=1)


def test_connect_sync_portal_roundtrip():
    with connect(n_agents=2, artifacts=_names(2), artifact_tokens=16,
                 shards=2, sync=True) as portal:
        assert isinstance(portal, ServicePortal)
        assert isinstance(portal.broker, ShardedCoherenceBroker)
        client = portal.client(0)
        r = client.read("artifact-0")
        assert not r.hit
        w = client.write("artifact-1")
        assert w.version == 2


def test_adapters_flat_config_reads_over_sharded_broker():
    # regression: CoherentTool reads the slot size from broker.config,
    # which is the same CoherenceConfig on either broker flavor
    # (examples/coherent_service_demo.py hit this).
    from repro.service import CoherentClient, CoherentTool

    async def go():
        async with connect(n_agents=2, artifacts=_names(4),
                           artifact_tokens=16, shards=2,
                           hosts=2) as broker:
            tool = CoherentTool(CoherentClient(broker, 0))
            assert tool._tokens == 16
            await tool.acall("write", "artifact-1", "v2")
            r = await tool.acall("read", "artifact-1")
            assert r.version == 2
            assert broker.config.core == CoherenceCore(
                artifact_tokens=16)
            assert all(b.config.core == broker.config.core
                       for b in broker.brokers)

    asyncio.run(go())


def test_make_routes_knobs_to_layers():
    cfg = CoherenceConfig.make(
        4, _names(2), artifact_tokens=64, strategy="eager",
        batch_window=0.01, shards=2, hosts=2, l1_max_version_lag=1)
    assert cfg.core == CoherenceCore(artifact_tokens=64,
                                     strategy="eager")
    assert cfg.service == ServiceLayer(batch_window=0.01)
    assert cfg.topology == ShardTopology(n_shards=2, n_hosts=2,
                                         l1_max_version_lag=1)
    with pytest.raises(TypeError, match="unknown coherence knob"):
        CoherenceConfig.make(4, _names(2), tokens=64)


# ---------------------------------------------------------------------------
# Trace schema: shard stamping round-trips; other versions are refused.


def test_trace_shard_roundtrip_and_old_schema_refused():
    async def go():
        cfg = _config(m=6, shards=2)
        async with connect(cfg) as broker:
            await _drive(broker, _ping_pong_schedule(4, 4, 4),
                         cfg.artifacts)
            return broker.trace
    trace = asyncio.run(go())
    payload = json.loads(trace.to_json())
    # v4 adds decide_s/batch_size (tests/test_obs.py covers those);
    # the shard stamping introduced in v3 must still round-trip
    assert payload["schema_version"] == 4
    assert payload["n_shards"] == 2
    restored = type(trace).from_json(trace.to_json())
    assert restored == trace
    # a v2 payload (no shard or timing fields) is refused by version
    for step in payload["steps"]:
        step.pop("shard")
        step.pop("decide_s")
        step.pop("batch_size")
    payload.pop("n_shards")
    payload.pop("artifact_shards")
    payload["schema_version"] = 2
    with pytest.raises(ValueError, match="schema_version 2"):
        type(trace).from_json(json.dumps(payload))


# ---------------------------------------------------------------------------
# The flush rounds: every shard's batch in flight at once.


def _serial_round(self):
    """The plane as it was before its rounds: one whole flush per shard
    in turn, each shard's batch resolved before the next is cut."""
    for broker in self.brokers:
        if broker._pending:
            broker._flush_once()


def _waves(n: int, m: int, rounds: int, seed: int = 11):
    """Mixed reads and writes over every shard, one request per agent
    per wave, a wave submitted at once."""
    rng = np.random.default_rng(seed)
    return [[(a, int(rng.integers(m)), bool(rng.random() < 0.3))
             for a in range(n) if rng.random() < 0.8]
            for _ in range(rounds)]


def _serve_waves(cfg, waves, contents=None):
    async def go():
        async with connect(cfg, contents=contents) as broker:
            answers = []
            for wave in waves:
                answers += await asyncio.gather(*(
                    broker.write(a, cfg.artifacts[d]) if w
                    else broker.read(a, cfg.artifacts[d])
                    for a, d, w in wave))
            return broker, answers
    return asyncio.run(go())


def _observed(broker, answers) -> tuple:
    return ([(r.version, getattr(r, "hit", None)) for r in answers],
            dataclasses.astuple(broker.ledger),
            np.asarray(broker.directory_state).tolist(),
            np.asarray(broker.versions).tolist(),
            dict(broker.l1_wire), dict(broker.wire))


@pytest.mark.parametrize("route,chunk", [("scan", 0), ("pallas", 0),
                                         ("scan", 8)],
                         ids=["scan", "pallas", "chunked"])
def test_rounds_match_serial_flushes(monkeypatch, route, chunk):
    """The flush rounds and one whole flush per shard in turn give
    bit-identical answers, ledgers, directories, versions, L1
    attribution and wire bytes."""
    cfg = _config(n=8, m=8, tokens=32, shards=4, hosts=4, backend=route,
                  chunk_tokens=chunk)
    waves = _waves(8, 8, 10)
    broker, answers = _serve_waves(cfg, waves)
    assert broker._flusher_task is None and all(
        b._flusher_task is None for b in broker.brokers)
    driven = _observed(broker, answers)
    monkeypatch.setattr(ShardedCoherenceBroker, "_round", _serial_round)
    serial = _observed(*_serve_waves(cfg, waves))
    assert driven == serial
    assert driven[4]["l1_fills"] + driven[4]["l2_fills"] > 0


def test_round_trace_passes_the_sharded_oracle():
    cfg = _config(n=8, m=8, tokens=32, shards=4, hosts=4)
    broker, _ = _serve_waves(cfg, _waves(8, 8, 12, seed=5))
    trace = broker.trace
    assert {s.shard for s in trace.steps} == {0, 1, 2, 3}
    oracle.check_sharded_trace(trace.acs_config(), trace.to_oracle_trace(),
                               trace.artifact_shards, name="rounds")
    verify_broker(broker)


def _rounds_of_records(monkeypatch):
    """Wrap the plane's round so that each round's committed batch
    records are kept together."""
    rounds = []
    do_round = ShardedCoherenceBroker._round

    def round_(self):
        before = len(self.telemetry.spans.records)
        do_round(self)
        rounds.append(list(self.telemetry.spans.records)[before:])
    monkeypatch.setattr(ShardedCoherenceBroker, "_round", round_)
    return rounds


def test_round_dispatches_every_shard_before_any_readback(monkeypatch):
    rounds = _rounds_of_records(monkeypatch)
    cfg = _config(n=8, m=8, tokens=32, shards=4, hosts=4,
                  backend="pallas")
    broker, _ = _serve_waves(cfg, _waves(8, 8, 4, seed=3))
    full = [r for r in rounds if len(r) == 4]
    assert full, [len(r) for r in rounds]
    for records in full:
        assert [r.shard for r in records] == [0, 1, 2, 3]
        calls = [r.phases["broker.decide.call"][0] for r in records]
        readbacks = [r.phases["broker.decide.readback"][0]
                     for r in records]
        assert max(calls) < min(readbacks)
    snap = broker.telemetry.registry
    assert snap.counter_total("coh_shard_rounds_total") == len(rounds)
    assert snap.histogram_totals("coh_shard_round_batches")[()] == (
        len(rounds), sum(len(r) for r in rounds))


def test_batch_record_holds_only_its_own_phases(monkeypatch):
    rounds = _rounds_of_records(monkeypatch)
    cfg = _config(n=8, m=8, tokens=32, shards=4, hosts=4)
    broker, _ = _serve_waves(cfg, _waves(8, 8, 6, seed=9))
    records = [r for rnd in rounds for r in rnd]
    assert len(records) == len(broker.trace.steps)
    # commits reach the global trace in resolve order
    assert [r.shard for r in records] == [s.shard
                                          for s in broker.trace.steps]
    interleaved = 0
    for rec, step in zip(records, broker.trace.steps):
        assert not any(name.startswith("broker.round")
                       for name in rec.phases)
        # its first block's start, to the end of its last
        assert rec.t0 == rec.phases["broker.batch"][0]
        assert rec.t0 + rec.wall_s >= rec.phases["broker.batch"][1]
        # its decide is its own two halves' stage, call, readback and
        # outcomes, inside its two broker.decide blocks
        assert rec.decide_s == step.decide_s
        assert rec.decide_s <= rec.seconds("broker.decide")
        assert all(rec.seconds(f"broker.decide.{p}") > 0
                   for p in ("stage", "call", "readback", "outcomes"))
        # the other shards' work between its halves is not its own
        interleaved += rec.flush_s < rec.wall_s - 1e-9
        assert rec.flush_s <= rec.wall_s
    assert interleaved > 0


def test_k1_flushes_once_per_batch_in_order(monkeypatch):
    """A plain broker keeps its own flush task and runs each batch's
    phases in one block, in the order it always did."""
    calls = []
    flush_once = CoherenceBroker._flush_once

    def counted(self):
        calls.append(1)
        flush_once(self)
    monkeypatch.setattr(CoherenceBroker, "_flush_once", counted)

    async def go():
        async with connect(n_agents=4, artifacts=("a", "b"),
                           artifact_tokens=32, backend="scan") as broker:
            assert type(broker) is CoherenceBroker
            assert broker._flusher_task is not None
            await asyncio.gather(broker.read(0, "a"), broker.write(1, "b"),
                                 broker.read(2, "b"))
            return broker
    broker = asyncio.run(go())
    (rec,) = broker.telemetry.spans.records
    assert len(calls) >= 1 and broker.n_batches == 1
    assert list(rec.phases) == [
        "broker.cut", "broker.stage", "broker.decide.readback",
        "broker.decide.stage", "broker.decide.call",
        "broker.decide.outcomes", "broker.decide", "broker.checks",
        "broker.respond", "broker.telemetry", "broker.batch"]
    assert rec.t0 == rec.phases["broker.batch"][0]
    assert 0 < rec.flush_s <= rec.wall_s
