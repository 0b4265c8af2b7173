"""CPU tests of the four-shard cell's benchmark files: the shard-plane
reductions on synthetic records, small four-shard runs through the
``open_loop_shards`` runner checked against the reference, the shard
plane's faults, and every metric the manifest gives
``fleet256_k4.ycsb_b.steady`` read on a program whose flush rounds run the
shards' batches at once and on one that flushes each shard in turn.

Nothing here is a device measurement: the runs drive the CPU (the scan
decision route), and the device trace is a stand-in.
"""

from __future__ import annotations

import json
import pathlib
import shutil

import pytest

from bench import harness, shards
from bench.test_perfbench import (PEAKS, TINY_SERVED, _commits_misfiled,
                                  _ledger_of_one_shard)
from repro.obs.spans import BatchRecord

ROOT = pathlib.Path(__file__).resolve().parents[1]
CELL = "fleet256_k4.ycsb_b.steady"
TINY_K4 = dict(TINY_SERVED, name="tiny_k4", deployment=dict(
    TINY_SERVED["deployment"], n_artifacts=8, shards=4, hosts=4))
TINY_K4_PLAIN = dict(TINY_K4, name="tiny_k4_plain", write_span_chunks=0,
                     deployment=dict(TINY_K4["deployment"], chunk_tokens=0))
TINY_SHARDS = {"runner": "open_loop_shards", "rate_per_s": 300}


# ---------------------------------------------------------------------------
# The reductions on synthetic records.


def _record(shard: int, call: float, readback_end: float) -> BatchRecord:
    rec = BatchRecord(shard)
    rec.add("broker.decide.call", "broker.decide", call, call + 0.001)
    rec.add("broker.decide.readback", "broker.decide", call + 0.002,
            readback_end)
    return rec


@pytest.mark.parametrize("spans,expected", [
    # one shard after another: never more than one in flight
    ([(0, 0.0, 1.0), (1, 1.0, 2.0), (2, 2.5, 3.0), (3, 3.0, 4.0)], 1.0),
    # four at once over the same second
    ([(s, 0.0, 1.0) for s in range(4)], 4.0),
    # two overlapping by half: 3 s of flight over 2 s covered
    ([(0, 0.0, 2.0), (1, 1.0, 2.0)], 1.5),
    ([(0, 0.0, 1.0), (1, 0.5, 1.5), (2, 3.0, 4.0)], 3.0 / 2.5),
])
def test_shards_in_flight_reads_the_overlap(spans, expected):
    records = [_record(*s) for s in spans]
    assert shards.shards_in_flight({"phases": records}) == \
        pytest.approx(expected)


def test_shard_readers_return_none_only_without_their_key():
    assert shards.shards_in_flight({}) is None
    assert shards.hot_shard_share({}) is None
    assert shards.l1_fill_share({}) is None
    # a record without a device call (an empty cut) is not in flight
    assert shards.shards_in_flight({"phases": [BatchRecord(0)]}) == 0.0
    assert shards.hot_shard_share({"shard_requests": [0, 0, 0, 0]}) == 0.0
    assert shards.l1_fill_share({"l1_wire": {"l1_fills": 0,
                                             "l2_fills": 0}}) == 0.0


def test_hot_shard_and_l1_shares():
    assert shards.hot_shard_share(
        {"shard_requests": [106, 301, 122, 471]}) == pytest.approx(47.1)
    assert shards.l1_fill_share(
        {"l1_wire": {"l1_fills": 3, "l2_fills": 1, "l1_bytes": 0,
                     "l2_bytes": 0}}) == pytest.approx(75.0)


# ---------------------------------------------------------------------------
# Small four-shard runs through the runner.


def _k4_root(tmp_path: pathlib.Path) -> harness.Manifest:
    """A checkout holding the two small four-shard configurations, the
    runner's traffic and the existing readers."""
    bench = tmp_path / "bench"
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    shutil.copytree(ROOT / "bench" / "metrics", bench / "metrics")
    data = json.loads((ROOT / "BENCHMARK.json").read_text())
    data["configs"], data["workloads"] = [], []
    for config in (TINY_K4, TINY_K4_PLAIN):
        name = config["name"]
        (bench / "configs" / f"{name}.json").write_text(json.dumps(config))
        data["configs"].append({
            "name": name, "source": "small CPU stand-in",
            "file": f"bench/configs/{name}.json", "reduced": [],
            "why": "CPU test"})
        data["workloads"].append({
            "name": f"{name}.open", "config": name, "traffic": "tiny_shards",
            "chips": 1, "why": "CPU test"})
    (bench / "traffic" / "tiny_shards.json").write_text(
        json.dumps(TINY_SHARDS))
    cells = [w["name"] for w in data["workloads"]]
    data["end_to_end"] = [dict(m, workloads=cells)
                          for m in data["end_to_end"]
                          if m["name"] == "decide_p50_ms"] + [
        m for m in data["end_to_end"] if m["name"] == "setup_s"]
    data["per_layer"] = []
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))
    return harness.Manifest(tmp_path)


def _run(manifest, cell: str, seed: int) -> dict:
    import jax
    return harness.run_cell(manifest, cell, seed=seed, seconds=1.0,
                            traced=False, devices=jax.devices()[:1],
                            t_start=0.0, peaks=PEAKS)


@pytest.mark.parametrize("config", ["tiny_k4", "tiny_k4_plain"])
def test_four_shard_run_is_correct(tmp_path, config):
    result = _run(_k4_root(tmp_path), f"{config}.open", seed=3_000_000_067)
    assert result["correct"], result["checks"]
    checks = result["checks"]
    assert {"shard_misplaced", "shards_sharing_a_chip", "ledger_diff",
            "decision_mismatch", "directory_diff"} <= set(checks)
    assert all(c["limit"] == 0 and c["value"] == 0
               for c in checks.values())
    assert result["attempted"] == 300 and result["failed"] == 0


@pytest.mark.parametrize("fault,attr", [(_ledger_of_one_shard, "ledger"),
                                        (_commits_misfiled, "_commit")])
def test_four_shard_faults_are_not_correct(tmp_path, monkeypatch, fault,
                                           attr):
    from repro.service.sharding import ShardedCoherenceBroker
    monkeypatch.setattr(ShardedCoherenceBroker, attr,
                        fault(ShardedCoherenceBroker))
    result = _run(_k4_root(tmp_path), "tiny_k4.open", seed=3_000_000_071)
    assert not result["correct"]


def _one_shard_at_a_time(self):
    """The plane before its flush rounds: each shard's whole flush in
    turn (what a program without the rounds does)."""
    for broker in self.brokers:
        if broker._pending:
            broker._flush_once()


@pytest.mark.parametrize("plane", ["rounds", "one_shard_at_a_time"])
def test_every_metric_of_the_cell_reads_a_number(tmp_path, monkeypatch,
                                                 plane):
    """Every metric the manifest gives the four-shard cell reads a
    number from what the runner hands its readers, on the program as it
    is and on one that flushes each shard in turn: a metric that read
    nothing there would stop that program's traced run."""
    import jax

    from repro.service.sharding import ShardedCoherenceBroker
    if plane != "rounds":
        monkeypatch.setattr(ShardedCoherenceBroker, "_round",
                            _one_shard_at_a_time)
    manifest = _k4_root(tmp_path)
    cell = manifest.cell("tiny_k4_plain.open")
    traffic = manifest.traffic(cell)
    run = manifest.runner(traffic).Run(
        manifest.config(cell), traffic, seed=3_000_000_073, seconds=1.0,
        devices=jax.devices()[:1], compiles=harness.CompileCounter())
    run.setup()
    obs = run.window(None)
    assert all(c.ok for c in run.check().checks)
    # the device trace's reduction, as a four-chip trace would give it
    obs["trace"] = {"window_s": obs["window_s"],
                    "busy_s": 0.5 * obs["window_s"], "devices": 4,
                    "kernel_s": {"mesi_tick": 1e-3 * obs["batches"],
                                 "chunk_tick": 0.0},
                    "kernel_events": {"mesi_tick": obs["batches"],
                                      "chunk_tick": 0}}
    obs["peaks"] = PEAKS
    real = harness.Manifest(ROOT)
    values = {m["name"]: real.reader(m["name"])(obs)
              for traced in (False, True)
              for m in real.metrics(CELL, traced) if m["name"] != "setup_s"}
    assert all(isinstance(v, (int, float)) for v in values.values()), values
    # the requests of the window's batches (those after its close drain)
    assert 0 < sum(obs["shard_requests"]) <= len(run.sched)
    assert len(obs["shard_requests"]) == 4
    in_flight = values["shards_in_flight.k4"]
    if plane == "rounds":
        assert in_flight >= 1.0 - 1e-9
    else:       # one shard's batch in flight at a time
        assert in_flight == pytest.approx(1.0)
