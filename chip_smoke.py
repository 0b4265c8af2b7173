#!/usr/bin/env python3
"""Smoke run of both products on a TPU: the coherence service and the
fleet sweep engine, through the entry points a user calls.

    python3 chip_smoke.py             # one chip: phases a-d
    python3 chip_smoke.py --chips 4   # four chips: the sharded paths only

One chip runs, in one process and in this order:

  a. the served path at the committed service benchmark's size (32
     agents x 6 artifacts x 4096 tokens, uniform V=0.10, 40 lockstep
     rounds) on the default decision route, replayed bit-exactly by the
     four-way oracle and cross-checked against the scan route;
  b. the same deployment with the content plane (256-token chunks),
     writes rewriting seeded chunk spans, replayed by the byte-exact
     content oracle;
  c. a 256-agent x 16-artifact x 4096-token zipf fleet, 10 rounds, whose
     kernel-route ledger must equal the scan route's;
  d. the fused sweep (4 volatilities x 64 runs), whose per-run token
     totals must equal the scan tick's.

``--chips 4`` runs phase a on a K=4 sharded authority (one shard per
device) against the K=1 broker, and the sweep sharded over 4 devices
against one device.

Every phase asserts that it took the Pallas route.  The script fails,
and prints no result, on a host without a TPU, when a route-forcing
variable is set, or when run without the rest of the repository.  The
times it prints are smoke numbers, not benchmark metrics.  The last
line of standard output is the JSON verdict.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
ROUTE_VARS = ("REPRO_SIM_TICK", "REPRO_SERVICE_DECIDE", "REPRO_CHUNK_DIFF",
              "REPRO_KERNEL_BACKEND")

#: phase c: the MESI kernel's documented fleet limit (m <= 16)
FLEET_AGENTS, FLEET_ARTIFACTS, FLEET_ROUNDS, FLEET_SEED = 256, 16, 10, 7
#: phase b: chunk size of the content plane (16 chunks of 4096 tokens)
CHUNK_TOKENS = 256
#: phase d: runs per volatility (4 x 64 = 256 episodes, PALLAS_MIN_BATCH)
SWEEP_RUNS = 64


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


# ---------------------------------------------------------------------------
# Served path.


class SpanWriter:
    """A client whose writes rewrite a seeded span of 1-3 chunks, so the
    content plane has real deltas to ship."""

    def __init__(self, client, broker, seed: int, chunk_tokens: int):
        import numpy as np
        self.client, self.broker = client, broker
        self.rng = np.random.default_rng((seed, client.agent_id))
        self.chunk_tokens = chunk_tokens

    async def read(self, name):
        return await self.client.read(name)

    async def write(self, name):
        cur = list(self.broker.store.get(name))
        n_chunks = -(-len(cur) // self.chunk_tokens)
        lo = int(self.rng.integers(n_chunks)) * self.chunk_tokens
        hi = min(len(cur), lo + int(self.rng.integers(1, 4))
                 * self.chunk_tokens)
        cur[lo:hi] = self.rng.integers(0, 1 << 20, hi - lo).tolist()
        return await self.client.write(name, cur)


def serve(workload, n_rounds: int, seed: int, span_writes: bool = False,
          **knobs):
    """Drive one ``connect()`` deployment through a lockstep workload;
    returns (broker, load report, seconds)."""
    from repro.service import connect, drive_workload, make_clients

    acs_cfg = workload.acs

    async def run():
        names = tuple(f"artifact-{d}" for d in range(acs_cfg.n_artifacts))
        broker = connect(n_agents=acs_cfg.n_agents, artifacts=names,
                         artifact_tokens=acs_cfg.artifact_tokens,
                         strategy="lazy", **knobs)
        async with broker:
            clients = None
            if span_writes:
                clients = [SpanWriter(c, broker, seed, knobs["chunk_tokens"])
                           for c in make_clients(broker)]
            rep = await drive_workload(broker, workload, n_rounds,
                                       seed=seed, clients=clients)
        return broker, rep

    t0 = time.perf_counter()
    broker, rep = asyncio.run(run())
    return broker, rep, time.perf_counter() - t0


def deciders(broker) -> list:
    return [b.decider for b in getattr(broker, "brokers", (broker,))]


def served_state(broker) -> tuple:
    """Everything the oracle pins, as comparable host values."""
    import numpy as np
    return (dataclasses.astuple(broker.ledger),
            np.asarray(broker.directory_state).tolist(),
            np.asarray(broker.versions).tolist())


def phase_served(phase: str, workload, n_rounds: int, seed: int, *,
                 verify: bool = True, reference_knobs=None, **knobs):
    """Serve ``workload`` on the default route, assert the kernel route,
    replay it through the oracle, and compare with a reference run."""
    from repro.service import verify_broker

    # a 2-round run of the same deployment compiles every program first
    _, _, compile_s = serve(workload, 2, seed + 1,
                            span_writes="chunk_tokens" in knobs, **knobs)
    broker, rep, wall_s = serve(workload, n_rounds, seed,
                                span_writes="chunk_tokens" in knobs, **knobs)
    routes = sorted({d.backend for d in deciders(broker)})
    check(routes == ["pallas"], f"{phase}: decision route {routes}")
    check(broker.n_batches > 0 and rep.n_actions > 0,
          f"{phase}: nothing was decided")
    cfg = workload.acs
    fields = dict(route="pallas", agents=cfg.n_agents,
                  artifacts=cfg.n_artifacts, tokens=cfg.artifact_tokens,
                  rounds=n_rounds, actions=rep.n_actions,
                  batches=broker.n_batches,
                  savings=f"{rep.savings_vs_broadcast:.4f}",
                  compile_warm_s=f"{compile_s:.2f}", wall_s=f"{wall_s:.2f}")
    if verify:
        report = verify_broker(broker, name=phase)
        check("pallas" in report.implementations,
              f"{phase}: oracle ran no pallas leg")
        fields["oracle"] = "bit_exact:" + "+".join(report.implementations)
    if getattr(broker, "wire", None) and broker.wire.get("full_bytes"):
        wire = broker.wire
        check(0 < wire["delta_bytes"] < wire["full_bytes"],
              f"{phase}: content plane shipped no deltas {wire}")
        fields.update(delta_bytes=wire["delta_bytes"],
                      full_bytes=wire["full_bytes"],
                      chunks_fetched=wire["n_chunks_fetched"])
    if reference_knobs is not None:
        ref, _, ref_s = serve(workload, n_rounds, seed, **reference_knobs)
        check(served_state(ref) == served_state(broker),
              f"{phase}: ledger/directory differ from the reference "
              f"{reference_knobs}: {ref.ledger} vs {broker.ledger}")
        fields["reference"] = "identical:" + (",".join(
            f"{k}={v}" for k, v in reference_knobs.items()) or "K=1")
    say(phase, **fields)
    return broker


# ---------------------------------------------------------------------------
# Sweep engine.


def phase_sweep(phase: str, devices: int, reference: dict) -> None:
    from benchmarks.sweep_engine import VOLATILITIES
    from repro.sim import cliff_scenario, engine, sweep_volatility

    base = cliff_scenario(VOLATILITIES[0])
    batch = len(VOLATILITIES) * SWEEP_RUNS
    route = engine.resolve_tick_backend(base.acs, batch)
    check(route == "pallas", f"{phase}: sweep tick route {route}")
    plan = engine.shard_plan(len(VOLATILITIES), SWEEP_RUNS, devices)
    check(plan.devices == devices, f"{phase}: shard plan {plan}")

    def sweep(**kw):
        t0 = time.perf_counter()
        out = sweep_volatility(base, VOLATILITIES, n_runs=SWEEP_RUNS, **kw)
        return out, time.perf_counter() - t0

    cold, cold_s = sweep(devices=devices)
    warm, warm_s = sweep(devices=devices)
    check(warm == cold, f"{phase}: a warm rerun changed the result")
    ref, _ = sweep(**reference)
    for c, r in zip(cold, ref):
        check(c.coherent_per_run_tokens == r.coherent_per_run_tokens
              and c.broadcast == r.broadcast,
              f"{phase}: V={c.volatility} per-run totals differ from "
              f"{reference}")
        check(len(c.coherent_per_run_tokens) == SWEEP_RUNS,
              f"{phase}: {len(c.coherent_per_run_tokens)} runs")
    say(phase, route=route, devices=devices, shard_axis=plan.axis,
        agents=base.acs.n_agents, artifacts=base.acs.n_artifacts,
        steps=base.acs.n_steps, volatilities=len(VOLATILITIES),
        runs=SWEEP_RUNS, episodes=batch,
        savings="/".join(f"{c.savings_mean:.4f}" for c in cold),
        cold_s=f"{cold_s:.2f}", warm_s=f"{warm_s:.3f}",
        reference="identical:" + ",".join(f"{k}={v}"
                                          for k, v in reference.items()))


# ---------------------------------------------------------------------------


def one_chip() -> None:
    from benchmarks import service_bench as sb
    from repro.kernels.chunk_diff import resolve_chunk_route
    from repro.launch.service import build_workload

    seed = sb.FAMILY_SEEDS["uniform"]
    uniform = build_workload("uniform", sb.N_CLIENTS, sb.N_ARTIFACTS,
                             sb.ARTIFACT_TOKENS, sb.N_ROUNDS, seed=seed)
    phase_served("a:served", uniform, sb.N_ROUNDS, seed,
                 reference_knobs={"backend": "scan"})

    check(resolve_chunk_route("pallas") == "pallas",
          "b: chunk route is not the kernel")
    phase_served("b:content", uniform, sb.N_ROUNDS, seed,
                 chunk_tokens=CHUNK_TOKENS)

    fleet = build_workload("zipf", FLEET_AGENTS, FLEET_ARTIFACTS,
                           sb.ARTIFACT_TOKENS, FLEET_ROUNDS, seed=FLEET_SEED)
    phase_served("c:fleet", fleet, FLEET_ROUNDS, FLEET_SEED, verify=False,
                 reference_knobs={"backend": "scan"})

    phase_sweep("d:sweep", devices=1, reference={"tick_backend": "scan"})


def four_chips() -> None:
    import jax
    from benchmarks import service_bench as sb
    from repro.launch.service import build_workload

    check(len(jax.devices()) == 4, f"--chips 4 sees {jax.devices()}")
    seed = sb.FAMILY_SEEDS["uniform"]
    uniform = build_workload("uniform", sb.N_CLIENTS, sb.N_ARTIFACTS,
                             sb.ARTIFACT_TOKENS, sb.N_ROUNDS, seed=seed)
    broker = phase_served("a:served:K4", uniform, sb.N_ROUNDS, seed,
                          reference_knobs={}, shards=4, hosts=sb.N_HOSTS)
    devices = {d.device for d in deciders(broker)}
    check(len(devices) == 4 and None not in devices,
          f"a:served:K4: shards on devices {devices}")
    say("a:served:K4", shard_devices=sorted(str(d) for d in devices))
    phase_sweep("d:sweep:4dev", devices=4, reference={"devices": 1})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    forced = [v for v in ROUTE_VARS if v in os.environ]
    if forced:
        print(f"route-forcing variables set: {forced}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        import repro.service
    except ImportError as e:
        print(f"the repository is not next to this script: {e}",
              file=sys.stderr)
        return 2
    if ROOT not in pathlib.Path(repro.service.__file__).resolve().parents:
        print(f"repro is imported from {repro.service.__file__}, not this "
              "checkout", file=sys.stderr)
        return 2

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX sees {devices}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    say("device", platform=devices[0].platform,
        kind=repr(devices[0].device_kind), count=len(devices),
        jax=jax.__version__, compile_cache=cache)

    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)()
    say("done", wall_s=f"{time.perf_counter() - t0:.2f}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
