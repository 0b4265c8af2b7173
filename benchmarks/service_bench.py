"""Coherence-service load benchmark: concurrent-client throughput,
decision latency and token savings vs broadcast.

Drives the asyncio broker (``repro.service``) with 32 concurrent
clients per workload family in lockstep rounds (a round = one SS8.1
orchestration step, which makes the broadcast baseline exact and the
coherent token totals deterministic for a fixed seed).  The
``uniform`` row is the paper's homogeneous scenario at V=0.10 under
the lazy strategy - the acceptance row: its savings must clear 80%
and its captured decision trace must replay **bit-exactly** through
the four-way differential oracle (protocol / vectorized ACS / Pallas
kernel / model checker).

The sharded section re-runs every family on the K=4 authority plane
(4 directory shards, 4 L1 hosts, via the topology-neutral
``service.connect``) and asserts the token ledger is **bit-identical**
to the plain broker's - sharding is a deployment knob, not a semantics
knob - then sweeps K in {1, 2, 4} on the uniform family to show
decision-plane *capacity* (actions / max-over-shards decide-busy, the
makespan metric from ``LoadReport.capacity_dps``) scaling with K at
unchanged savings.

The telemetry-overhead section runs the uniform family twice - once
with the observability plane (``repro.obs``) disabled, once with it on
- and records both latency profiles; the perf gate's ``[telemetry]``
section enforces that telemetry-on p50/p99 stay within tolerance of
telemetry-off on the same machine (``--telemetry`` picks which
variants to measure).

Writes ``BENCH_service.json`` at the repo root (schema v4 in
``benchmarks/README.md``) so service latency/savings/capacity and the
telemetry overhead are tracked and perf-gated across PRs
(``scripts/bench_gate.py``).
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import pathlib

import jax

from benchmarks.common import (BenchRow, PhaseClock, bench_iters,
                               bench_steps, fast_mode, fmt_pct, md_table,
                               provenance, write_results)
from repro.service import (CoherenceBroker, CoherenceConfig, connect,
                           drive_workload, verify_broker)
from repro.service.batching import resolve_decide_backend

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCH_JSON = REPO_ROOT / "BENCH_service.json"

#: the measured service grid (fast mode shrinks rounds, never clients -
#: the acceptance criterion is >= 32 *concurrent* clients).
N_CLIENTS = 32
N_ARTIFACTS = 6
N_ROUNDS = 40
ARTIFACT_TOKENS = 4096
STRATEGY = "lazy"
MIN_ACCEPT_SAVINGS = 0.80

#: sharded authority plane: K values for the uniform capacity sweep and
#: the per-family bit-identity pass (always at SHARD_KS[-1]).
SHARD_KS = (1, 2, 4)
N_HOSTS = 4

#: benchmark families: the acceptance row plus the structured zoo.
FAMILIES = ("uniform", "bursty", "zipf", "hierarchical", "rag",
            "pipeline", "ping_pong")
FAMILY_SEEDS = {f: 20260701 + i for i, f in enumerate(FAMILIES)}


def _workload(family: str, n_rounds: int):
    from repro.launch.service import build_workload
    return build_workload(
        family, n_clients=N_CLIENTS, n_artifacts=N_ARTIFACTS,
        artifact_tokens=ARTIFACT_TOKENS, n_rounds=n_rounds,
        seed=FAMILY_SEEDS[family])


def _broker_config(telemetry: bool = True) -> CoherenceConfig:
    return CoherenceConfig.make(
        N_CLIENTS, tuple(f"artifact-{d}" for d in range(N_ARTIFACTS)),
        artifact_tokens=ARTIFACT_TOKENS, strategy=STRATEGY,
        telemetry=telemetry)


def _coherence_config(shards: int) -> CoherenceConfig:
    """Layered config for the sharded rows: K directory shards, N_HOSTS
    L1 placement domains, same core knobs as the plain rows."""
    return CoherenceConfig.make(
        N_CLIENTS, tuple(f"artifact-{d}" for d in range(N_ARTIFACTS)),
        artifact_tokens=ARTIFACT_TOKENS, strategy=STRATEGY,
        shards=shards, hosts=N_HOSTS)


async def _measure_family(family: str, n_rounds: int,
                          keep_broker: bool = False) -> tuple:
    w = _workload(family, n_rounds)
    async with CoherenceBroker(_broker_config()) as broker:
        rep = await drive_workload(broker, w, n_rounds,
                                   seed=FAMILY_SEEDS[family])
        stats = broker.stats()
        row = {
            "family": family,
            "name": w.name,
            "description": w.description,
            "effective_volatility": w.effective_volatility(),
            "actions": rep.n_actions,
            "batches": stats["decision"]["n_batches"],
            "mean_batch": stats["decision"]["mean_batch"],
            "throughput_dps": rep.throughput_dps,
            "p50_ms": rep.latency_ms(50),
            "p99_ms": rep.latency_ms(99),
            "coherent_tokens": rep.coherent_tokens,
            "broadcast_tokens": rep.broadcast_tokens,
            "savings_vs_broadcast": rep.savings_vs_broadcast,
            "cache_hit_rate": stats["ledger"]["cache_hit_rate"],
        }
        return (row, dataclasses.astuple(broker.ledger),
                broker if keep_broker else None)


async def _measure_sharded(family: str, n_rounds: int, shards: int,
                           plain_ledger: tuple,
                           keep_broker: bool = False) -> tuple:
    """One family on the K-shard authority plane via ``connect``.

    Asserts the token ledger is bit-identical to the plain broker's run
    of the same workload (sharding must not change a single accounting
    bit) and reports the capacity metric + L1/L2 fill split."""
    w = _workload(family, n_rounds)
    async with connect(_coherence_config(shards)) as broker:
        rep = await drive_workload(broker, w, n_rounds,
                                   seed=FAMILY_SEEDS[family])
        stats = broker.stats()
        ledger = dataclasses.astuple(broker.ledger)
        if ledger != plain_ledger:
            raise AssertionError(
                f"sharded K={shards} {family}: ledger diverged from the "
                f"plain broker ({ledger} vs {plain_ledger})")
        l1 = stats.get("l1", {})
        row = {
            "family": family,
            "shards": shards,
            "hosts": N_HOSTS,
            "actions": rep.n_actions,
            "coherent_tokens": rep.coherent_tokens,
            "savings_vs_broadcast": rep.savings_vs_broadcast,
            "capacity_dps": rep.capacity_dps,
            "decide_busy_s": list(rep.decide_busy_s),
            "l1_fills": l1.get("l1_fills", 0),
            "l2_fills": l1.get("l2_fills", 0),
            "l1_fill_rate": l1.get("l1_fill_rate", 0.0),
            "bit_identical_to_plain": True,
        }
        return row, broker if keep_broker else None


async def _measure_overhead(n_rounds: int, telemetry: bool) -> dict:
    """One uniform-family run with the observability plane on or off.

    Telemetry changes no static shapes, so both variants reuse the
    decide program compiled by ``_warmup`` - the delta is pure Python
    bookkeeping (counter increments, span records) on the hot path."""
    w = _workload("uniform", n_rounds)
    cfg = _broker_config(telemetry=telemetry)
    async with CoherenceBroker(cfg) as broker:
        rep = await drive_workload(broker, w, n_rounds,
                                   seed=FAMILY_SEEDS["uniform"])
        return {
            "telemetry": telemetry,
            "actions": rep.n_actions,
            "throughput_dps": rep.throughput_dps,
            "p50_ms": rep.latency_ms(50),
            "p99_ms": rep.latency_ms(99),
            "decide_busy_s": broker.decide_busy_s,
            "savings_vs_broadcast": rep.savings_vs_broadcast,
        }


def _overhead_section(n_rounds: int, mode: str) -> dict:
    """The telemetry-overhead rows: uniform family, telemetry off vs on,
    median-of-repeats per variant.  ``mode`` in {both, on, off} picks
    the variants; overhead ratios need both.  Each latency/throughput
    field is the component-wise median across repeats - the tail (p99)
    sees ~ms GC/scheduler spikes on either variant, and inheriting a
    single row's unlucky tail would make the gate flap."""
    variants = {"both": (False, True),
                "off": (False,), "on": (True,)}[mode]
    iters = bench_iters(5)
    rows = []
    for on in variants:
        repeats = [asyncio.run(_measure_overhead(n_rounds, on))
                   for _ in range(iters)]
        mid = len(repeats) // 2
        med = dict(sorted(repeats, key=lambda r: r["p50_ms"])[mid])
        for field in ("p50_ms", "p99_ms", "throughput_dps"):
            med[field] = sorted(r[field] for r in repeats)[mid]
        med["repeats"] = len(repeats)
        med["p50_ms_all"] = [r["p50_ms"] for r in repeats]
        med["p99_ms_all"] = [r["p99_ms"] for r in repeats]
        rows.append(med)
    section = {"family": "uniform", "n_rounds": n_rounds,
               "mode": mode, "rows": rows}
    if len(variants) == 2:
        off, on = rows[0], rows[1]
        section["p50_overhead_frac"] = (on["p50_ms"] / off["p50_ms"]) - 1.0
        section["p99_overhead_frac"] = (on["p99_ms"] / off["p99_ms"]) - 1.0
        section["throughput_overhead_frac"] = (
            1.0 - on["throughput_dps"] / off["throughput_dps"])
    return section


async def _warmup() -> None:
    """Compile the plain decision program outside the timed runs (the
    jit cache is keyed on the static broker config, so the measured
    brokers reuse it)."""
    w = _workload("uniform", 2)
    async with CoherenceBroker(_broker_config()) as broker:
        await drive_workload(broker, w, 2, seed=0)


async def _warmup_sharded(shards: int) -> None:
    """Per-K warmup: each shard decides over its own artifact subset -
    a different static shape, so a separate jit-cache entry."""
    w = _workload("uniform", 2)
    async with connect(_coherence_config(shards)) as broker:
        await drive_workload(broker, w, 2, seed=0)


def _oracle_row(broker, name: str) -> dict:
    report = verify_broker(broker, name=name)
    return {
        "bit_exact": True,
        "implementations": list(report.implementations),
        "n_actions": report.trace.n_actions,
    }


def run(telemetry_mode: str = "both") -> list:
    n_rounds = bench_steps(N_ROUNDS)
    cfg = _broker_config()
    decide_backend = resolve_decide_backend(cfg.acs_config())
    clock = PhaseClock()
    with clock.phase("warmup"):
        asyncio.run(_warmup())

    rows_payload, plain_ledgers = [], {}
    uniform_broker = None
    with clock.phase("families"):
        for family in FAMILIES:
            row, ledger, broker = asyncio.run(_measure_family(
                family, n_rounds, keep_broker=(family == "uniform")))
            rows_payload.append(row)
            plain_ledgers[family] = ledger
            uniform_broker = uniform_broker or broker

    # telemetry overhead while the plain decide program is still warm
    # (same static shape with telemetry on or off, so no extra compile).
    with clock.phase("telemetry"):
        telemetry_overhead = _overhead_section(n_rounds, telemetry_mode)

    # sharded plane: every family at K=SHARD_KS[-1] must be
    # bit-identical to its plain run (asserted inside), the uniform
    # family additionally sweeps K for the capacity-scaling rows.
    # Caches are cleared between sections: a full run compiles the
    # plain program + one decide program per shard shape + the oracle
    # replay legs, which together can exhaust the CPU LLVM code arena
    # in one process (same reason tests/conftest.py clears caches
    # between modules).  Each section re-warms its own programs, so
    # the timed rows never include a compile.
    k_max = SHARD_KS[-1]
    with clock.phase("sharded"):
        jax.clear_caches()
        asyncio.run(_warmup_sharded(k_max))
        sharded_rows, sharded_uniform_broker = [], None
        for family in FAMILIES:
            row, broker = asyncio.run(_measure_sharded(
                family, n_rounds, k_max, plain_ledgers[family],
                keep_broker=(family == "uniform")))
            sharded_rows.append(row)
            sharded_uniform_broker = sharded_uniform_broker or broker
        scaling_rows = []
        for k in SHARD_KS:
            if k == k_max:
                continue
            jax.clear_caches()
            asyncio.run(_warmup_sharded(k))
            scaling_rows.append(asyncio.run(_measure_sharded(
                "uniform", n_rounds, k, plain_ledgers["uniform"]))[0])
        scaling_rows.append(sharded_rows[0])
        scaling_rows.sort(key=lambda r: r["shards"])

    # oracle replays last, each against a fresh code arena: the
    # four-way legs (pallas interpret + model check) are the biggest
    # compiles of the whole bench.
    with clock.phase("oracle"):
        jax.clear_caches()
        rows_payload[0]["oracle_replay"] = _oracle_row(
            uniform_broker, "service:uniform")
        jax.clear_caches()
        sharded_rows[0]["oracle_replay"] = _oracle_row(
            sharded_uniform_broker, f"service:uniform:K{k_max}")

    accept_row = rows_payload[0]
    assert accept_row["family"] == "uniform"
    if accept_row["savings_vs_broadcast"] < MIN_ACCEPT_SAVINGS:
        raise AssertionError(
            f"acceptance: uniform V=0.10 lazy savings "
            f"{accept_row['savings_vs_broadcast']:.3f} < "
            f"{MIN_ACCEPT_SAVINGS}")

    payload = {
        "schema_version": 4,
        "fast_mode": fast_mode(),
        "provenance": provenance(),
        "phases": clock.report(),
        "backend": jax.default_backend(),
        "decide_backend": decide_backend,
        "grid": {
            "families": list(FAMILIES),
            "n_clients": N_CLIENTS,
            "n_artifacts": N_ARTIFACTS,
            "n_rounds": n_rounds,
            "artifact_tokens": ARTIFACT_TOKENS,
            "strategy": STRATEGY,
        },
        "families": rows_payload,
        "sharded": {
            "ks": list(SHARD_KS),
            "n_hosts": N_HOSTS,
            "families": sharded_rows,
            "uniform_scaling": scaling_rows,
        },
        "telemetry_overhead": telemetry_overhead,
        "acceptance": {
            "family": "uniform",
            "volatility": 0.10,
            "strategy": STRATEGY,
            "n_clients": N_CLIENTS,
            "min_savings": MIN_ACCEPT_SAVINGS,
            "savings": accept_row["savings_vs_broadcast"],
            "oracle_replay": accept_row["oracle_replay"],
        },
    }
    if not fast_mode():
        # repo-root artifact = cross-PR trajectory; smoke runs must not
        # clobber it.
        BENCH_JSON.write_text(json.dumps(payload, indent=2,
                                         default=float))

    table = [[r["family"], f"{r['effective_volatility']:.3f}",
              f"{r['throughput_dps']:,.0f}",
              f"{r['p50_ms']:.2f} / {r['p99_ms']:.2f}",
              fmt_pct(r["savings_vs_broadcast"]),
              fmt_pct(r["cache_hit_rate"])]
             for r in rows_payload]
    shard_table = [[f"K={r['shards']}",
                    f"{r['capacity_dps']:,.0f}",
                    fmt_pct(r["savings_vs_broadcast"]),
                    str(r["l1_fills"]), str(r["l2_fills"]),
                    fmt_pct(r["l1_fill_rate"])]
                   for r in scaling_rows]
    accept_oracle = accept_row["oracle_replay"]
    md = ("### Coherence service - concurrent-client load benchmark\n\n"
          + md_table(["family", "eff. V", "decisions/s",
                      "p50/p99 ms", "savings", "CHR"], table)
          + f"\n{N_CLIENTS} concurrent clients x {n_rounds} rounds per "
          f"family, strategy {STRATEGY}, decide backend "
          f"{decide_backend}.  Acceptance: uniform V=0.10 savings "
          f"{accept_row['savings_vs_broadcast']:.1%} (floor "
          f"{MIN_ACCEPT_SAVINGS:.0%}); captured trace replayed "
          f"bit-exactly through "
          f"{', '.join(accept_oracle['implementations'])}.\n"
          "\n### Sharded authority plane - uniform capacity sweep\n\n"
          + md_table(["shards", "capacity dec/s", "savings",
                      "L1 fills", "L2 fills", "L1 rate"], shard_table)
          + f"\nK directory shards x {N_HOSTS} L1 hosts; capacity = "
          f"actions / max-over-shards decide-busy (the decision-plane "
          f"makespan under shard-per-host deployment).  Every family's "
          f"K={k_max} token ledger is bit-identical to its plain-broker "
          f"run; the uniform K={k_max} trace additionally replayed "
          f"through the cross-shard + L1/L2 conformance legs.\n")

    tel_table = [[("on" if r["telemetry"] else "off"),
                  f"{r['throughput_dps']:,.0f}",
                  f"{r['p50_ms']:.3f}", f"{r['p99_ms']:.3f}",
                  f"{r['decide_busy_s']:.3f}"]
                 for r in telemetry_overhead["rows"]]
    md += ("\n### Telemetry overhead - uniform family, obs plane "
           "off vs on\n\n"
           + md_table(["telemetry", "decisions/s", "p50 ms", "p99 ms",
                       "decide busy s"], tel_table))
    if "p50_overhead_frac" in telemetry_overhead:
        md += (f"\np50 overhead "
               f"{telemetry_overhead['p50_overhead_frac']:+.1%}, p99 "
               f"{telemetry_overhead['p99_overhead_frac']:+.1%} "
               f"(median of {telemetry_overhead['rows'][0]['repeats']} "
               f"repeats; gate: within 10% + absolute epsilon, "
               f"``scripts/bench_gate.py [telemetry]``).\n")

    rows = [BenchRow(
        name=f"service/{r['family']}",
        us_per_call=1e6 / max(r["throughput_dps"], 1e-9),
        derived=(f"savings={r['savings_vs_broadcast'] * 100:.1f}% "
                 f"p99={r['p99_ms']:.2f}ms"))
        for r in rows_payload]
    rows += [BenchRow(
        name=f"service/uniform@K{r['shards']}",
        us_per_call=1e6 / max(r["capacity_dps"], 1e-9),
        derived=(f"savings={r['savings_vs_broadcast'] * 100:.1f}% "
                 f"l1_rate={r['l1_fill_rate'] * 100:.1f}%"))
        for r in scaling_rows]
    rows += [BenchRow(
        name=f"service/telemetry_{'on' if r['telemetry'] else 'off'}",
        us_per_call=1e6 / max(r["throughput_dps"], 1e-9),
        derived=f"p50={r['p50_ms']:.3f}ms p99={r['p99_ms']:.3f}ms")
        for r in telemetry_overhead["rows"]]
    write_results("service_bench", rows, md, extra=payload)
    return rows


if __name__ == "__main__":
    import argparse
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument(
        "--telemetry", choices=("both", "on", "off"), default="both",
        help="which observability variants the overhead section "
             "measures (overhead ratios need 'both')")
    args = parser.parse_args()
    for r in run(telemetry_mode=args.telemetry):
        print(r.csv())
