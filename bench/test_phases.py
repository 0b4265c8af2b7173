"""CPU tests of the phase metrics: the program's batch and sweep-call
records of a measured window, and the readers over them.

The runners do not hand the records to the readers yet (the manifest
lists none of these metrics); here each test gathers the window's
records with ``bench.phases`` the way a runner would, from the same
window bounds as its other observations.  Nothing here is a device
measurement.
"""

from __future__ import annotations

import pathlib
import time

import pytest

from bench import harness, phases
from bench.runners import open_loop, sweep
from bench.test_perfbench import TINY_SERVED, TINY_SWEEP

ROOT = pathlib.Path(__file__).resolve().parents[1]
SERVED_METRICS = ("stage_ms_per_batch", "kernel_build_ms_per_batch",
                  "readback_ms_per_batch", "respond_ms_per_batch",
                  "telemetry_ms_per_batch")
TINY_PLAIN = dict(TINY_SERVED, name="tiny_plain", write_span_chunks=0,
                  deployment=dict(TINY_SERVED["deployment"],
                                  chunk_tokens=0))


def _readers(names) -> dict:
    manifest = harness.Manifest(ROOT)
    return {name: manifest.reader(name) for name in names}


def _served_window(config: dict, seed: int) -> dict:
    run = open_loop.Run(config, {"runner": "open_loop", "rate_per_s": 300},
                        seed=seed, seconds=1.0, devices=None,
                        compiles=harness.CompileCounter())
    run.setup()
    obs = run.window(None)
    obs["phases"] = phases.served(run.broker, run.t0,
                                  run.t0 + obs["window_s"])
    return obs


@pytest.mark.parametrize("config", [TINY_SERVED, TINY_PLAIN],
                         ids=["chunked", "plain"])
def test_phase_readers_read_a_served_run(config):
    obs = _served_window(config, seed=3_000_000_061)
    # one record for every batch the window counts, and only those
    assert obs["batches"] > 0 and len(obs["phases"]) == obs["batches"]
    assert [r.decide_s for r in obs["phases"]] == obs["decide_s"]
    assert all(r.flush_s > 0 for r in obs["phases"])
    for cell in ("steady", "over"):
        names = [f"{m}.{cell}" for m in SERVED_METRICS]
        values = {n: read(obs) for n, read in _readers(names).items()}
        assert all(isinstance(v, float) and v >= 0.0
                   for v in values.values()), values
        # the warm-up built every program the window runs
        assert values[f"kernel_build_ms_per_batch.{cell}"] == 0.0
        assert values[f"respond_ms_per_batch.{cell}"] > 0.0
        assert values[f"stage_ms_per_batch.{cell}"] > 0.0


def test_phase_readers_read_a_sweep_run():
    run = sweep.Run(TINY_SERVED,
                    TINY_SWEEP, seed=3_000_000_067, seconds=0.5,
                    devices=None, compiles=harness.CompileCounter())
    run.setup()
    t0 = time.perf_counter()
    obs = run.window(None)
    obs["phases"] = phases.sweep(t0, time.perf_counter())
    assert obs["calls"] > 0 and len(obs["phases"]) == obs["calls"]
    (read,) = _readers(["sweep_host_ms_per_call.sweep"]).values()
    host_ms = read(obs)
    assert 0.0 < host_ms <= 1e3 * obs["elapsed_s"] / obs["calls"]


def test_phase_readers_read_nothing_without_records():
    names = [f"{m}.{cell}" for m in SERVED_METRICS
             for cell in ("steady", "over")]
    names.append("sweep_host_ms_per_call.sweep")
    for name, read in _readers(names).items():
        assert read({"batches": 3}) is None, name
        assert read({"phases": None}) is None, name
    # a program without the records: the runner's lookup finds none
    assert phases.served(object(), 0.0, 1.0) is None
