"""The program's own phase records of a measured window, and the
reductions behind the phase metrics in ``bench/metrics/``.

The program keeps one record per committed micro-batch
(``broker.telemetry.spans.records``, ``repro.obs.spans.BatchRecord``)
and one per sweep call (``repro.obs.runtime.sweep_records()``): the
seconds of each phase span, and the trace / lower / compile seconds
charged to it.  A runner hands the window's records to the readers as
``obs["phases"]``; a program without the records gives ``None`` there,
and each reader then returns ``None``.
"""

from __future__ import annotations

#: the served phases a batch spends before its decider runs (the
#: broker's staging, then the decider's host-to-device staging)
STAGE = ("broker.stage", "broker.decide.stage")
#: the sweep call's host phases: everything but the gather
SWEEP_HOST = ("sweep.operands", "sweep.dispatch", "sweep.results")


def served(broker, t0: float, t1: float):
    """The batch records of every shard of ``broker`` whose batch began
    inside ``[t0, t1]``; ``None`` where the program keeps none."""
    telemetry = getattr(broker, "telemetry", None)
    records = getattr(getattr(telemetry, "spans", None), "records", None)
    if records is None:
        return None
    return [r for r in records if t0 <= r.t0 <= t1]


def sweep(t0: float, t1: float):
    """The sweep-call records of calls that began inside ``[t0, t1]``;
    ``None`` where the program keeps none."""
    try:
        from repro.obs.runtime import sweep_records
    except ImportError:
        return None
    return [r for r in sweep_records() if t0 <= r.t0 <= t1]


def _mean_ms(values: list):
    return 1e3 * sum(values) / len(values) if values else None


def phase_ms(*names: str):
    """Reader: mean milliseconds per record of the window in the named
    phases (summed)."""
    def read(obs):
        records = obs.get("phases")
        if not records:
            return None
        return _mean_ms([sum(r.seconds(n) for n in names)
                         for r in records])
    return read


def build_ms(obs):
    """Mean milliseconds per batch of tracing, lowering and compiling
    (or loading) programs: 0.0 when the window built nothing."""
    records = obs.get("phases")
    if not records:
        return None
    return _mean_ms([r.build_s for r in records])
