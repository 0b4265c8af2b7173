"""The harness: finds a cell's files by name, runs its runner, reads its
metrics and prints the result line.

Nothing here knows a configuration, a traffic mix or a metric by name
except ``setup_s``, which the harness measures itself.  A runner (one
per traffic ``runner`` kind, ``bench/runners/<kind>.py``) builds the
deployment from the configuration file, warms it, runs the measured
window and checks what the window produced against the plain
reference; each metric is a reader, ``bench/metrics/<name>.py``, with
one function ``read(obs)`` over the observations the run collected.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import pathlib
import sys
import time
from typing import Callable, Optional

ROOT = pathlib.Path(__file__).resolve().parents[1]

#: JAX's persistent compilation cache: a fixed path inside the checkout,
#: so only the first run of a cell in a checkout compiles.
CACHE_DIRNAME = ".jax_compile_cache"

#: JAX monitoring event fired once per backend compile *or* persistent
#: cache load of a lowered program (``compile_or_get_cached``).
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, missing file, ...)."""


def load_json(path: pathlib.Path) -> dict:
    try:
        return json.loads(pathlib.Path(path).read_text())
    except FileNotFoundError:
        raise BenchError(f"missing benchmark file {path}") from None


class Manifest:
    """``BENCHMARK.json`` and the files its names lead to."""

    def __init__(self, root: pathlib.Path = ROOT) -> None:
        self.root = pathlib.Path(root)
        self.data = load_json(self.root / "BENCHMARK.json")

    def cell(self, name: str) -> dict:
        for cell in self.data["workloads"]:
            if cell["name"] == name:
                return cell
        raise BenchError(f"no cell {name!r} in BENCHMARK.json")

    def config(self, cell: dict) -> dict:
        for cfg in self.data["configs"]:
            if cfg["name"] == cell["config"]:
                return load_json(self.root / cfg["file"])
        raise BenchError(f"no configuration {cell['config']!r}")

    def traffic(self, cell: dict) -> dict:
        return load_json(self.root / "bench" / "traffic"
                         / f"{cell['traffic']}.json")

    def metrics(self, cell_name: str, traced: bool) -> list:
        """The metrics a run of this cell reports: its end-to-end
        metrics untraced, its per-layer metrics traced."""
        group = self.data["per_layer" if traced else "end_to_end"]
        return [m for m in group
                if cell_name in m.get("workloads", (cell_name,))]

    def reader(self, metric: str) -> Callable:
        path = self.root / "bench" / "metrics" / f"{metric}.py"
        if not path.exists():
            raise BenchError(f"no reader {path} for metric {metric!r}")
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + metric.replace(".", "_"), path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read

    def runner(self, traffic: dict):
        return importlib.import_module(f"bench.runners.{traffic['runner']}")


@dataclasses.dataclass
class Check:
    """One number compared with the reference, beside its limit."""

    name: str
    value: float
    limit: float

    def __post_init__(self) -> None:
        # numpy scalars print as plain numbers in the result line
        self.value = getattr(self.value, "item", lambda: self.value)()

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a runner's check found."""

    attempted: int
    failed: int
    checks: list
    #: why requests failed, for standard error
    notes: list = dataclasses.field(default_factory=list)


# ---------------------------------------------------------------------------
# Device and compile plumbing.


def require_chips(n: int) -> list:
    """The first ``n`` TPU devices; raises on any other host."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX sees {devices}")
    if len(devices) < n:
        raise BenchError(f"the cell needs {n} chips, JAX sees "
                         f"{len(devices)}")
    return devices[:n]


def enable_compile_cache(root: pathlib.Path) -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(pathlib.Path(root) / CACHE_DIRNAME))
    # the Pallas kernels compile in well under JAX's 1 s default
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts every program the process compiles or loads from the
    persistent cache, by a ``jax.monitoring`` listener."""

    def __init__(self) -> None:
        import jax
        self.events: list = []      # (end time, program name, seconds)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_event)

    def _on_event(self, event: str, duration: float, *,
                  fun_name: str = "?", **_) -> None:
        if event == COMPILE_EVENT:
            self.events.append((time.perf_counter(), fun_name, duration))

    def between(self, t0: float, t1: float) -> list:
        """The (end time, program, seconds) of each compile or cache
        load that ended inside [t0, t1]."""
        return [e for e in self.events if t0 <= e[0] <= t1]


def device_info(devices: list) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": peak}


def load_peaks(root: pathlib.Path, device_kind: str) -> dict:
    table = load_json(pathlib.Path(root) / "bench" / "peaks.json")
    if device_kind not in table:
        raise BenchError(f"no published peaks for device kind "
                         f"{device_kind!r} in bench/peaks.json")
    return table[device_kind]


# ---------------------------------------------------------------------------
# One run of one cell.


def run_cell(manifest: Manifest, cell_name: str, *, seed: int,
             seconds: float, traced: bool, devices: list,
             t_start: float, peaks: Optional[dict] = None) -> dict:
    """Set up, warm, measure, check; returns the result object."""
    from bench import tracing

    cell = manifest.cell(cell_name)
    config = manifest.config(cell)
    traffic = manifest.traffic(cell)
    metrics = manifest.metrics(cell_name, traced)
    readers = {m["name"]: manifest.reader(m["name"])
               for m in metrics if m["name"] != "setup_s"}
    counter = CompileCounter()
    run = manifest.runner(traffic).Run(
        config, traffic, seed=seed, seconds=seconds, devices=devices,
        compiles=counter)
    run.setup()
    setup_s = time.perf_counter() - t_start
    capture = tracing.Capture() if traced else None
    obs = run.window(capture)
    info = device_info(devices)
    if capture is not None:
        reduced = capture.reduce(run.kernel_names(),
                                 [d.id for d in devices])
        check_kernel_calls(reduced["kernel_events"],
                           obs.get("kernel_calls", {}), len(devices))
        obs["trace"] = reduced
        info["busy_s"] = reduced["busy_s"]
        info["window_s"] = reduced["window_s"]
    obs["peaks"] = (peaks if peaks is not None
                    else load_peaks(manifest.root, info["kind"]))
    outcome = run.check()
    values = {"setup_s": setup_s}
    for name, read in readers.items():
        value = read(obs)
        if value is not None:
            values[name] = float(value)
    # every metric the manifest gives this cell has to read a number:
    # one that finds nothing has lost sight of its layer
    missing = [m["name"] for m in metrics if m["name"] not in values]
    if missing:
        raise BenchError(f"metrics of {cell_name} not measured: {missing}")
    result = {
        "correct": (all(c.ok for c in outcome.checks)
                    and outcome.failed == 0),
        "attempted": int(outcome.attempted),
        "failed": int(outcome.failed),
        "metrics": {m["name"]: {"value": values[m["name"]],
                                "unit": m["unit"]}
                    for m in metrics if m["name"] in values},
        "device": info,
    }
    if capture is not None:
        result["breakdown"] = {"device_ops": obs["trace"]["device_ops"],
                               "idle_gaps": obs["trace"]["idle_gaps"]}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in outcome.checks}
    for note in outcome.notes:
        print(f"bench: {note}", file=sys.stderr)
    return result


#: kernel calls a trace may miss or gain against the window's count, per
#: chip: a call that straddles either end of the window
KERNEL_CALL_SLACK = 2


def check_kernel_calls(found: dict, expected: dict, chips: int) -> None:
    """Each kernel ran in the trace as often as the window called it:
    a kernel that the trace's marks no longer find, or another device
    operation counted as one, stops the run."""
    for kernel, calls in expected.items():
        if abs(found.get(kernel, 0) - calls) > KERNEL_CALL_SLACK * chips:
            raise BenchError(
                f"the trace shows {found.get(kernel, 0)} {kernel} kernel "
                f"operations where the window made {calls} calls")


def check_lines(result: dict) -> list:
    """The compared numbers beside their limits, one per line."""
    return [f"check {name}: {c['value']} (limit {c['limit']})"
            for name, c in result["checks"].items()]


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default) of a non-empty
    sample."""
    import numpy as np
    arr = np.asarray(values, np.float64)
    if arr.size == 0:
        raise BenchError("percentile of an empty sample")
    return float(np.percentile(arr, q))
