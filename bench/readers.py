"""Shared reductions behind the metric readers in ``bench/metrics/``.

Each takes the run's observations (``obs``) and returns the metric, or
``None`` where the run has nothing to read, in which case the harness
leaves the metric out of the result line.
"""

from __future__ import annotations

from bench.harness import percentile


def _pct(values, q: float):
    return percentile(values, q) if len(values) else None


def latency_p50_ms(obs):
    return _pct(obs["latency_ms"], 50)


def latency_p99_ms(obs):
    return _pct(obs["latency_ms"], 99)


def decisions_per_s(obs):
    return obs["completed_in_window"] / obs["window_s"]


def episodes_per_s(obs):
    return obs["episodes"] / obs["elapsed_s"]


def gen_lag_p99_ms(obs):
    return _pct(obs["gen_lag_ms"], 99)


def queue_wait_p50_ms(obs):
    return _pct(obs["queue_wait_ms"], 50)


def _mean_ms(values):
    return 1e3 * sum(values) / len(values) if values else None


def decide_ms_per_batch(obs):
    """The decide time the program's trace records for each batch of
    the window (it ends in readback)."""
    return _mean_ms(obs.get("decide_s") or ())


def broker_host_ms_per_batch(obs):
    """Flush time outside the decider, per batch: cut, staging,
    invariant checks, apply/respond and telemetry."""
    flush, decide = (_mean_ms(obs.get("flush_s") or ()),
                     decide_ms_per_batch(obs))
    return None if flush is None or decide is None else flush - decide


def compiles_in_window(obs):
    return len(obs["compiles_in_window"])


def kernel_ms_per_batch(kernel: str):
    def read(obs):
        trace = obs.get("trace")
        if not trace or not trace["kernel_events"][kernel] \
                or not obs["batches"]:
            return None
        return 1e3 * trace["kernel_s"][kernel] / obs["batches"]
    return read


def roofline(kernel: str):
    """Share of the HBM roofline: the least time the kernel's work
    needs at the chip's published HBM bandwidth over its device time."""
    def read(obs):
        trace = obs.get("trace")
        if not trace or not trace["kernel_events"][kernel]:
            return None
        least_s = obs["work"][kernel] / obs["peaks"]["hbm_bytes_per_s"]
        return 100.0 * least_s / trace["kernel_s"][kernel]
    return read


def kernel_busy_share(obs):
    trace = obs.get("trace")
    if not trace or not any(trace["kernel_events"].values()):
        return None
    busy = trace["busy_s"] * trace["devices"]
    return 100.0 * sum(trace["kernel_s"].values()) / busy


def device_idle_share(obs):
    trace = obs.get("trace")
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
