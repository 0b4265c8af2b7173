"""Reductions behind the shard-plane metrics in ``bench/metrics/``,
over the observations ``bench/runners/open_loop_shards.py`` adds.

Each returns ``None`` only where the run has no such observation.
"""

from __future__ import annotations

#: a batch is in flight from the start of its device call to the end
#: of its readback
CALL, READBACK = "broker.decide.call", "broker.decide.readback"


def in_flight_intervals(records) -> list:
    """``(start, end)`` of each batch record's time in flight."""
    out = []
    for r in records:
        call, back = r.phases.get(CALL), r.phases.get(READBACK)
        if call is not None and back is not None:
            out.append((call[0], back[1]))
    return out


def mean_overlap(intervals) -> float:
    """How many intervals are open on average, over the time at least
    one is: their summed length over the length of their union."""
    covered, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > end:
            covered += e - max(s, end)
            end = e
    total = sum(e - s for s, e in intervals)
    return total / covered if covered > 0 else 0.0


def shards_in_flight(obs):
    """Mean number of shards with a batch between its device call and
    the end of its readback, over the time at least one has."""
    records = obs.get("phases")
    if records is None:
        return None
    return mean_overlap(in_flight_intervals(records))


def hot_shard_share(obs):
    """The busiest shard's share of the window's decided requests (%)."""
    counts = obs.get("shard_requests")
    if counts is None:
        return None
    total = sum(counts)
    return 100.0 * max(counts) / total if total else 0.0


def l1_fill_share(obs):
    """Share of coherence fills a host L1 served (%)."""
    wire = obs.get("l1_wire")
    if wire is None:
        return None
    fills = wire["l1_fills"] + wire["l2_fills"]
    return 100.0 * wire["l1_fills"] / fills if fills else 0.0
