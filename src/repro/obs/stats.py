"""One ``stats()`` schema for every broker flavor.

``CoherenceBroker.stats()`` and ``ShardedCoherenceBroker.stats()`` both
delegate here: :func:`unified_stats` builds one **nested canonical
schema**, with the same key set for both flavors.

Canonical schema (``schema_version`` 1)::

    strategy, backend                      # deployment
    topology:  n_shards, n_hosts, shard_artifacts
    decision:  n_actions, n_batches, mean_batch, decide_busy_s,
               decide_busy_max_s, decisions_per_s
    ledger:    total/fetch/signal/push tokens, fills, hits, reads,
               writes, invalidation signals, cache_hit_rate
    latency:   p50_ms, p99_ms, n_samples
    telemetry: enabled, spans_recorded, compile_traces
    mesi:      invalidation events/storms, writer flips, ping-pong
               alternations, staleness-at-serve mean  (telemetry on)
    wire:      delta/full bytes, chunks fetched, savings, unique
               chunks                                  (content plane)
    l1:        l1/l2 fills + bytes, fill rate, invalidations
                                                       (sharded plane)
"""

from __future__ import annotations

import numpy as np


def _percentiles(latencies) -> dict:
    lat = (np.asarray(latencies, float) if len(latencies)
           else np.zeros(1))
    return {"p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p99_ms": float(np.percentile(lat, 99) * 1e3),
            "n_samples": int(len(latencies))}


def _mesi_section(tel) -> dict:
    reg = tel.registry
    stale = reg.histogram_totals("coh_staleness_at_serve")
    count = sum(c for c, _ in stale.values())
    total = sum(s for _, s in stale.values())
    occ = {}
    for key, value in reg.counter_cells(
            "coh_state_occupancy_total").items():
        state = dict(key).get("state", "?")
        occ[state] = occ.get(state, 0) + value
    return {
        "invalidation_events": reg.counter_total(
            "coh_invalidation_events_total"),
        "invalidation_storms": reg.counter_total(
            "coh_invalidation_storms_total"),
        "writer_flips": reg.counter_total("coh_writer_flips_total"),
        "pingpong_alternations": reg.counter_total(
            "coh_pingpong_alternations_total"),
        "staleness_served_mean": total / max(count, 1),
        "occupancy": occ,
    }


def unified_stats(broker) -> dict:
    """Build the canonical nested stats mapping for a plain or sharded
    broker."""
    sharded = getattr(broker, "is_sharded", False)
    led = broker.ledger
    tel = getattr(broker, "telemetry", None)

    if sharded:
        backend = broker.brokers[0].decider.backend
        n_shards = broker.n_shards
        n_hosts = broker.config.topology.n_hosts
        shard_artifacts = [len(c) for c in broker._shard_cols]
        busy = broker.decision_busy()
        latencies = [x for b in broker.brokers for x in b.latencies]
        chunked = broker.chunked
        unique_chunks = (sum(b.chunks.n_unique_chunks
                             for b in broker.brokers)
                         if chunked else 0)
    else:
        backend = broker.decider.backend
        n_shards, n_hosts = 1, 1
        shard_artifacts = [len(broker.names)]
        busy = (broker.decide_busy_s,)
        latencies = list(broker.latencies)
        chunked = broker.chunks is not None
        unique_chunks = (broker.chunks.n_unique_chunks
                         if chunked else 0)

    n_actions = led.n_reads + led.n_writes
    out = {
        "schema_version": 1,
        "strategy": broker.config.core.strategy,
        "backend": backend,
        "topology": {"n_shards": n_shards, "n_hosts": n_hosts,
                     "shard_artifacts": shard_artifacts},
        "decision": {
            "n_actions": n_actions,
            "n_batches": broker.n_batches,
            "mean_batch": n_actions / max(broker.n_batches, 1),
            "decide_busy_s": sum(busy),
            "decide_busy_max_s": max(busy),
            "decisions_per_s": n_actions / max(max(busy), 1e-12),
        },
        "ledger": {
            "total_tokens": led.total_tokens,
            "fetch_tokens": led.fetch_tokens,
            "signal_tokens": led.signal_tokens,
            "push_tokens": led.push_tokens,
            "n_fetches": led.n_fetches,
            "n_hits": led.n_hits,
            "n_reads": led.n_reads,
            "n_writes": led.n_writes,
            "n_invalidation_signals": led.n_invalidation_signals,
            "cache_hit_rate": led.n_hits / max(led.n_hits
                                               + led.n_fetches, 1),
        },
        "latency": _percentiles(latencies),
        "telemetry": {
            "enabled": tel is not None,
            "spans_recorded": (tel.spans.n_recorded if tel else 0),
            "compile_traces": 0,
        },
    }
    if tel is not None:
        from repro.obs import runtime
        out["telemetry"]["compile_traces"] = runtime.compile_count()
        out["mesi"] = _mesi_section(tel)
    if chunked:
        wire = dict(broker.wire)
        wire["bytes_savings_vs_full"] = 1.0 - (
            wire["delta_bytes"] / max(wire["full_bytes"], 1))
        wire["unique_chunks"] = unique_chunks
        out["wire"] = wire
    if sharded:
        l1 = dict(broker.l1_wire)
        fills = l1["l1_fills"] + l1["l2_fills"]
        l1["l1_fill_rate"] = l1["l1_fills"] / max(fills, 1)
        l1["l1_invalidations"] = sum(h.n_invalidations
                                     for h in broker.l1)
        out["l1"] = l1
    return out
