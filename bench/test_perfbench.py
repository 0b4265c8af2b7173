"""CPU tests of the chip benchmark: the manifest, lookup by name, the
seeded traffic, the work functions, the trace reduction, the refusal to
run without a TPU, and whole runs of small cells with the reference
check, its control and the faults it must catch.

The small runs skip the harness's look for a chip and drive the CPU
(the scan decision route); nothing here is a device measurement.
"""

from __future__ import annotations

import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import harness, reference, tracing, work, workload

ROOT = pathlib.Path(__file__).resolve().parents[1]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PEAKS = {"hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def manifest():
    return harness.Manifest(ROOT)


# ---------------------------------------------------------------------------
# The manifest and lookup by name.


def test_manifest_names_and_units(manifest):
    data = manifest.data
    assert set(data) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in data["configs"]]
    names += [w["name"] for w in data["workloads"]]
    names += [w["traffic"] for w in data["workloads"]]
    names += [m["name"] for m in data["end_to_end"] + data["per_layer"]]
    names += [k for c in data["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    for m in data["end_to_end"] + data["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in data["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for text in ([c["why"] for c in data["configs"] + data["workloads"]]
                 + [m["layer"] for m in data["per_layer"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text
    assert len(json.dumps(data)) < 64 * 1024


def test_every_cell_finds_its_files_by_name(manifest):
    for cell in manifest.data["workloads"]:
        config = manifest.config(cell)
        assert config["name"] == cell["config"]
        traffic = manifest.traffic(cell)
        assert hasattr(manifest.runner(traffic), "Run")
        for traced in (False, True):
            for m in manifest.metrics(cell["name"], traced):
                if m["name"] != "setup_s":
                    assert callable(manifest.reader(m["name"]))


def test_every_per_layer_metric_moves_a_reported_metric(manifest):
    data = manifest.data
    e2e = {m["name"]: m for m in data["end_to_end"]}
    cells = {c["name"] for c in data["workloads"]}
    for m in data["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in moved.get("workloads", cells), (m["name"], cell)
    for cell in cells:
        reported = {m["name"] for m in manifest.metrics(cell, False)}
        assert "setup_s" in reported and len(reported) >= 2
        assert manifest.metrics(cell, True)


# ---------------------------------------------------------------------------
# Traffic, work functions and the trace reduction.


def _schedule(seed: int):
    rates = workload.rate_matrices(
        256, 16, {"family": "zipf", "skew": 0.99, "write_rate": 0.05,
                  "p_act": 0.75})
    return workload.open_loop_schedule(
        np.random.default_rng(workload.seed_sequence(seed)), rates,
        800.0, 2.0)


def test_open_loop_schedule_follows_the_seed():
    a, b, c = _schedule(3_000_000_019), _schedule(3_000_000_019), \
        _schedule(3_000_000_021)
    for field in ("due_s", "agent", "artifact", "write"):
        assert np.array_equal(getattr(a, field), getattr(b, field))
    assert len(a) == len(c) == 1600
    assert not np.array_equal(a.agent, c.agent)
    assert np.all(np.diff(a.due_s) >= 0) and a.due_s[-1] < 2.0
    # zipf 0.99 over 16 artifacts: rank 0 is the hottest
    assert np.bincount(a.artifact).argmax() == 0
    assert 0.02 < a.write.mean() < 0.08


def test_work_counts_from_the_deployment_shapes():
    # one directory tick, whatever implements it: the decision batch's
    # n+1 prefix replicas and its padded lanes never enter the count
    n, m = 256, 16
    per_tick = work.mesi_tick_bytes(n, m)
    assert per_tick == 4 * (2 * (2 * n * m + m) + 5 * n)
    assert work.mesi_tick_bytes(n, m) < work.mesi_tick_bytes(n + 1, m)
    assert per_tick * (n + 1) != per_tick
    assert work.chunk_tick_bytes(32, 6, 16) == 4 * (
        2 * (2 * 6 * 16 + 32 * 6 * 16) + 3 * 32 + 2 * 32 * 16)


_SPACE = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 40000000 duration_ps: 30000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "broker.flush" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 20000000 duration_ps: 20000000 }
    events { metadata_id: 1 offset_ps: 90000000 duration_ps: 20000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 0 duration_ps: 100000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.7" } }
  event_metadata { key: 2 value { id: 2 name: "%k = (s32[9,1,8]{2,1,0}, s32[9]{0}) custom-call(s32[9]{0} %p), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step" } }
}planes {
  id: 3 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 50000000 duration_ps: 10000000 }
  }
  event_metadata { key: 2 value { id: 2 name: "%k = (s32[9,1,8]{2,1,0}, s32[9]{0}) custom-call(s32[9]{0} %p), custom_call_target=\\"tpu_custom_call\\"" } }
}
planes {
  id: 4 name: "/device:TPU:2"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.9" } }
}
"""


def test_kernel_calls_are_held_to_the_window():
    harness.check_kernel_calls({"mesi_tick": 301}, {"mesi_tick": 300}, 1)
    harness.check_kernel_calls({"mesi_tick": 0, "chunk_tick": 0},
                               {"chunk_tick": 0}, 1)
    # a kernel out of the trace's sight, or another operation taken for it
    for found in ({"mesi_tick": 0}, {"mesi_tick": 600}, {}):
        with pytest.raises(harness.BenchError):
            harness.check_kernel_calls(found, {"mesi_tick": 300}, 1)


def test_trace_reduction_on_a_recorded_trace():
    from jax.profiler import ProfileData
    space = ProfileData.from_text_proto(_SPACE)
    kernels = {"mesi_tick": 2, "chunk_tick": 5}
    out = tracing.reduce_space(space, kernels, [0])
    # window 0-100 us; ops 10-30, 20-40, 90-110 (clipped to 100):
    # busy 10-40 and 90-100 = 40 us; the module line is not an op, and
    # chips the cell does not use are not read
    assert out["window_s"] == pytest.approx(100e-6)
    assert out["busy_s"] == pytest.approx(40e-6)
    assert out["kernel_s"]["mesi_tick"] == pytest.approx(20e-6)
    assert out["kernel_events"] == {"mesi_tick": 1, "chunk_tick": 0}
    assert out["device_ops"][0] == ["fusion.7", pytest.approx(30e-6)]
    assert out["device_ops"][1] == ["%k custom-call mesi_tick",
                                    pytest.approx(20e-6)]
    # gaps: 40-90 (50 us, mid 65: inside broker.flush 40-70), 0-10
    assert out["idle_gaps"][0] == ["broker.flush", pytest.approx(50e-6)]
    assert out["idle_gaps"][1] == ["bench.window", pytest.approx(10e-6)]
    # two chips: busy is their mean, kernel time their sum
    two = tracing.reduce_space(space, kernels, [0, 1])
    assert two["busy_s"] == pytest.approx((40e-6 + 10e-6) / 2)
    assert two["kernel_s"]["mesi_tick"] == pytest.approx(30e-6)
    assert two["devices"] == 2


# ---------------------------------------------------------------------------
# The command refuses hosts it cannot measure on.


def _run_command(cwd: pathlib.Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "team32c.sweep",
         "--seed", "3000000019", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    proc = _run_command(ROOT)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "no TPU" in proc.stderr


def test_run_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_command(tmp_path)
    assert proc.returncode != 0
    assert "{" not in proc.stdout


# ---------------------------------------------------------------------------
# Whole runs of small cells on the CPU.

TINY_SERVED = {
    "name": "tiny", "source": "small CPU stand-in",
    "deployment": {"n_agents": 8, "n_artifacts": 3, "artifact_tokens": 64,
                   "strategy": "lazy", "chunk_tokens": 16, "shards": 1,
                   "hosts": 1, "telemetry": True, "check_invariants": True},
    "mix": {"family": "zipf", "skew": 0.5, "write_rate": 0.3,
            "p_act": 0.75},
    "write_span_chunks": 3,
}
TINY_SHARDED = dict(TINY_SERVED, name="tiny_k2", deployment=dict(
    TINY_SERVED["deployment"], n_artifacts=4, shards=2, hosts=2))
TINY_OPEN = {"runner": "open_loop", "rate_per_s": 300}
TINY_SWEEP = {"runner": "sweep", "volatilities": [0.1, 0.5], "runs": 4,
              "steps": 6, "write_span_chunks": 2}


def _tiny_root(tmp_path: pathlib.Path, extra_metric: str = "") -> \
        harness.Manifest:
    """A checkout holding one new configuration, two new mixes and the
    existing readers - plus, optionally, one new metric reader."""
    bench = tmp_path / "bench"
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    shutil.copytree(ROOT / "bench" / "metrics", bench / "metrics")
    (bench / "configs" / "tiny.json").write_text(json.dumps(TINY_SERVED))
    (bench / "configs" / "tiny_k2.json").write_text(json.dumps(TINY_SHARDED))
    (bench / "traffic" / "tiny_open.json").write_text(json.dumps(TINY_OPEN))
    (bench / "traffic" / "tiny_sweep.json").write_text(
        json.dumps(TINY_SWEEP))
    data = json.loads((ROOT / "BENCHMARK.json").read_text())
    data["configs"] = [{"name": name, "source": "small CPU stand-in",
                        "file": f"bench/configs/{name}.json", "reduced": [],
                        "why": "CPU test"} for name in ("tiny", "tiny_k2")]
    data["workloads"] = [
        {"name": "tiny.open", "config": "tiny", "traffic": "tiny_open",
         "chips": 1, "why": "CPU test"},
        {"name": "tiny.sweep", "config": "tiny", "traffic": "tiny_sweep",
         "chips": 1, "why": "CPU test"},
        {"name": "tiny_k2.open", "config": "tiny_k2", "traffic": "tiny_open",
         "chips": 1, "why": "CPU test"}]
    for m in data["end_to_end"] + data["per_layer"]:
        m.pop("workloads", None)
    cells = {"decide_p50_ms": ["tiny.open", "tiny_k2.open"],
             "episodes_per_s": ["tiny.sweep"]}
    data["end_to_end"] = [dict(m, **({"workloads": cells[m["name"]]}
                                     if m["name"] in cells else {}))
                          for m in data["end_to_end"]
                          if m["name"] in ("setup_s", *cells)]
    data["per_layer"] = [m for m in data["per_layer"]
                         if m["name"] == "compiles_in_window.steady"]
    if extra_metric:
        (bench / "metrics" / f"{extra_metric}.py").write_text(
            "def read(obs):\n    return len(obs['latency_ms'])\n")
        data["end_to_end"].append(
            {"name": extra_metric, "unit": "requests", "better": "higher",
             "bound": 0.05, "source": "host_clock",
             "workloads": ["tiny.open"]})
        (bench / "metrics" / f"{extra_metric}.layer.py").write_text(
            "def read(obs):\n    return obs['batches']\n")
        data["per_layer"].append(
            {"name": f"{extra_metric}.layer", "unit": "batches",
             "better": "lower", "source": "program_counter",
             "layer": "broker", "moves": extra_metric,
             "workloads": ["tiny.open"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))
    return harness.Manifest(tmp_path)


def _run(manifest, cell: str, seed: int, traced: bool = False):
    import jax
    return harness.run_cell(manifest, cell, seed=seed, seconds=1.0,
                            traced=traced, devices=jax.devices()[:1],
                            t_start=0.0, peaks=PEAKS)


def test_new_files_and_entries_are_picked_up(tmp_path):
    manifest = _tiny_root(tmp_path, extra_metric="answered_requests")
    result = _run(manifest, "tiny.open", seed=3_000_000_019)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"setup_s", "decide_p50_ms",
                                      "answered_requests"}
    assert result["metrics"]["answered_requests"]["value"] == 300
    assert result["attempted"] == 300 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    # the new per-layer metric is found by name, reported by traced runs
    layer = [m["name"] for m in manifest.metrics("tiny.open", True)]
    assert "answered_requests.layer" in layer
    assert manifest.reader("answered_requests.layer")({"batches": 7}) == 7


def test_a_metric_that_reads_nothing_stops_the_run(tmp_path):
    manifest = _tiny_root(tmp_path, extra_metric="answered_requests")
    (tmp_path / "bench" / "metrics" / "answered_requests.py").write_text(
        "def read(obs):\n    return None\n")
    with pytest.raises(harness.BenchError, match="answered_requests"):
        _run(manifest, "tiny.open", seed=3_000_000_053)


def _served_run(tmp_path, seed, observed=None):
    manifest = _tiny_root(tmp_path)
    cell = manifest.cell("tiny.open")
    run = manifest.runner(manifest.traffic(cell)).Run(
        manifest.config(cell), manifest.traffic(cell), seed=seed,
        seconds=1.0, devices=None,
        compiles=harness.CompileCounter())
    run.setup()
    obs = run.window(None)
    if observed is not None:
        observed.update(obs)
    return run


def test_served_layer_timings_reach_the_readers(tmp_path):
    """The broker's flush (the one name the benchmark wraps, since no
    public record times a whole batch) and the program's own per-batch
    decide stamps are both read for every batch of the window."""
    from bench import readers
    obs: dict = {}
    _served_run(tmp_path, seed=3_000_000_059, observed=obs)
    assert obs["batches"] > 0
    assert len(obs["decide_s"]) == obs["batches"]
    assert abs(len(obs["flush_s"]) - obs["batches"]) <= 1
    assert obs["kernel_calls"] == {"mesi_tick": obs["batches"],
                                   "chunk_tick": obs["batches"]}
    decide = readers.decide_ms_per_batch(obs)
    host = readers.broker_host_ms_per_batch(obs)
    assert decide > 0 and host > 0


def test_served_control_is_not_correct(tmp_path):
    run = _served_run(tmp_path, seed=3_000_000_023)
    assert all(c.ok for c in run.check().checks)
    control = {c.name: c.value for c in run.check(control=True).checks}
    assert control["decision_mismatch"] > 0
    assert control["ledger_diff"] > 0


def _flip_first_answer(decide):
    def call(self, acts, *args, **kw):
        out = decide(self, acts, *args, **kw)
        a = int(np.flatnonzero(acts)[0])
        miss = np.array(out.miss, bool)
        miss[a] = not miss[a]
        return out._replace(miss=miss)
    return call


def _drop_half_the_batch(decide):
    def call(self, acts, *args, **kw):
        kept = np.array(acts, bool)
        kept[np.flatnonzero(kept)[len(np.flatnonzero(kept)) // 2:]] = False
        return decide(self, kept, *args, **kw)
    return call


def _state_unchanged(decide):
    def call(self, *args, **kw):
        arrays, metrics = self.arrays, self.metrics
        out = decide(self, *args, **kw)
        self.arrays, self.metrics = arrays, metrics
        return out
    return call


def _after_warm_up(fault, decide):
    """The fault from the window's first batch on: the warm-up decides
    three batches on a deployment of its own."""
    faulty, calls = fault(decide), []

    def call(self, *args, **kw):
        calls.append(1)
        return (faulty if len(calls) > 3 else decide)(self, *args, **kw)
    return call


@pytest.mark.parametrize("fault", [_flip_first_answer, _drop_half_the_batch,
                                   _state_unchanged])
def test_served_faults_are_not_correct(tmp_path, monkeypatch, fault):
    from repro.service.batching import BatchDecider
    monkeypatch.setattr(BatchDecider, "decide",
                        _after_warm_up(fault, BatchDecider.decide))
    result = _run(_tiny_root(tmp_path), "tiny.open", seed=3_000_000_029)
    assert not result["correct"]


def test_sharded_run_checks_the_shard_plane(tmp_path):
    result = _run(_tiny_root(tmp_path), "tiny_k2.open", seed=3_000_000_043)
    assert result["correct"], result["checks"]
    assert {"shard_misplaced", "shards_sharing_a_chip"} <= set(
        result["checks"])


def _ledger_of_one_shard(cls):
    """The exchange between shards left out: the global ledger is the
    first shard's alone."""
    return property(lambda self: self.brokers[0].ledger)


def _commits_misfiled(cls):
    """Each shard's commits are filed under the next shard."""
    commit = cls._commit
    return lambda self, shard, sub, c: commit(
        self, (shard + 1) % self.n_shards, sub, c)


@pytest.mark.parametrize("fault,attr", [(_ledger_of_one_shard, "ledger"),
                                        (_commits_misfiled, "_commit")])
def test_sharded_faults_are_not_correct(tmp_path, monkeypatch, fault,
                                        attr):
    from repro.service.sharding import ShardedCoherenceBroker
    monkeypatch.setattr(ShardedCoherenceBroker, attr,
                        fault(ShardedCoherenceBroker))
    result = _run(_tiny_root(tmp_path), "tiny_k2.open", seed=3_000_000_047)
    assert not result["correct"]


def test_sweep_run_is_correct_and_its_control_is_not(tmp_path):
    manifest = _tiny_root(tmp_path)
    result = _run(manifest, "tiny.sweep", seed=3_000_000_031)
    assert result["correct"], result["checks"]
    assert result["metrics"]["episodes_per_s"]["value"] > 0
    cell = manifest.cell("tiny.sweep")
    run = manifest.runner(manifest.traffic(cell)).Run(
        manifest.config(cell), manifest.traffic(cell), seed=3_000_000_037,
        seconds=0.5, devices=None,
        compiles=harness.CompileCounter())
    run.setup()
    run.window(None)
    assert all(c.ok for c in run.check().checks)
    control = {c.name: c.value for c in run.check(control=True).checks}
    assert control["run_total_mismatch"] > 0


def _sweep_state_unchanged(apply_actions):
    def call(cfg, arrays, met, *args, **kw):
        _, met2, out = apply_actions(cfg, arrays, met, *args, **kw)
        return arrays, met2, out
    return ("repro.core.acs", "apply_actions", call)


def _sweep_answer_altered(apply_actions):
    def call(cfg, arrays, met, *args, **kw):
        arrays2, met2, out = apply_actions(cfg, arrays, met, *args, **kw)
        return arrays2, met2._replace(
            fetch_tokens=met2.fetch_tokens + 1), out
    return ("repro.core.acs", "apply_actions", call)


def _sweep_half_the_runs(_):
    """Half of each cell's runs left out, the means taken over the
    rest (the kept half stands in for the whole, so shapes agree)."""
    from repro.sim import engine
    cell = engine._cell

    def call(out, variant, v):
        got = cell(out, variant, v)
        return {k: np.concatenate([a[:len(a) // 2]] * 2) if a.ndim
                else a for k, a in got.items()}
    return ("repro.sim.engine", "_cell", call)


@pytest.mark.parametrize("fault", [_sweep_state_unchanged,
                                   _sweep_answer_altered,
                                   _sweep_half_the_runs])
def test_sweep_faults_are_not_correct(tmp_path, monkeypatch, fault):
    import importlib
    from repro.core import acs
    from repro.sim import clear_compile_cache
    module, name, call = fault(acs.apply_actions)
    monkeypatch.setattr(importlib.import_module(module), name, call)
    clear_compile_cache()
    try:
        result = _run(_tiny_root(tmp_path), "tiny.sweep", seed=3_000_000_041)
    finally:
        monkeypatch.undo()
        clear_compile_cache()
    assert not result["correct"]


def test_sweep_reference_matches_the_counting_rules():
    """Two agents, one artifact: a read miss, a peer's write that
    invalidates it, and a refill of the 2 dirtied chunks."""
    acts = np.ones((1, 3, 2), np.int32)
    arts = np.zeros((1, 3, 2), np.int32)
    writes = np.array([[[0, 0], [0, 1], [0, 0]]], np.int32)
    wch = np.zeros((1, 3, 2, 4), bool)
    wch[0, 1, 1, :2] = True
    out = reference.episodes(acts, arts, writes, wch, m=1, tokens=64,
                             chunk_tokens=16)
    sig = reference.SIGNAL_TOKENS
    assert out["n_fetches"][0] == 3         # both cold, then agent 0
    assert out["n_hits"][0] == 3
    assert out["signal_tokens"][0] == sig   # one valid peer invalidated
    assert out["total_tokens"][0] == 3 * (64 + sig) + sig
    assert out["n_chunks_fetched"][0] == 4 + 4 + 2
