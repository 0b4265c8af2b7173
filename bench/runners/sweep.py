"""Sweep runner: back-to-back ``repro.sim.compare_workloads`` calls over
the configuration's workload at the traffic file's volatilities, each
call with fresh base seeds drawn from ``--seed``.

A call is one compiled grid (broadcast baseline and coherent variant,
every volatility x run) and ends in host readback.  After the window a
seeded sample of the calls is recomputed by the plain reference from
the same seeds: each coherent run's token total, and every per-cell
mean the call reports (fetches, signals, reads, writes, byte ledger)
for both variants.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench import reference, tracing, work, workload
from bench.runners.open_loop import KERNELS
from bench.harness import Check, Outcome

#: coherent-variant means recomputed by the reference (exact: means of
#: integer counters over the runs)
COHERENT_MEANS = ("fetch_tokens", "signal_tokens", "n_fetches", "n_reads",
                  "n_writes", "delta_bytes", "full_bytes",
                  "n_chunks_fetched")
#: calls of the window the check recomputes, drawn from the seed
CHECKED_CALLS = 2


class Run:
    def __init__(self, config: dict, traffic: dict, *, seed: int,
                 seconds: float, devices: list, compiles) -> None:
        dep = config["deployment"]
        self.n, self.m = dep["n_agents"], dep["n_artifacts"]
        self.tokens, self.ct = dep["artifact_tokens"], dep["chunk_tokens"]
        self.strategy = dep["strategy"]
        self.n_chunks = -(-self.tokens // self.ct)
        self.vols = [float(v) for v in traffic["volatilities"]]
        self.runs = int(traffic["runs"])
        self.steps = int(traffic["steps"])
        self.span = int(traffic["write_span_chunks"])
        self.p_act = float(config["mix"]["p_act"])
        self.rates = workload.rate_matrices(self.n, self.m, config["mix"])
        self.seconds = float(seconds)
        self.compiles = compiles
        self.rng = np.random.default_rng(workload.seed_sequence(seed))
        self.check_rng = np.random.default_rng(
            workload.seed_sequence(seed, 1))
        self.calls: list = []

    def _workloads(self, seeds) -> list:
        from repro.core.acs import ACSConfig, STRATEGY_CODES
        from repro.sim import Workload
        acs = ACSConfig(n_agents=self.n, n_artifacts=self.m,
                        artifact_tokens=self.tokens, n_steps=self.steps,
                        strategy=STRATEGY_CODES[self.strategy],
                        chunk_tokens=self.ct)
        return [Workload(
            name=f"V={v}", family="zipf", acs=acs,
            p_act=self.rates.p_act, pick=self.rates.pick,
            write_rate=np.full((self.n, self.m), v), seed=int(s),
            n_runs=self.runs, write_locality=self.span / self.n_chunks)
            for v, s in zip(self.vols, seeds)]

    def _call(self):
        from repro.sim import compare_workloads
        seeds = self.rng.integers(0, 2**31 - 1, size=len(self.vols))
        return seeds, compare_workloads(self._workloads(seeds))

    def setup(self) -> None:
        self._call()        # compiles (or loads) the one grid program

    def kernel_names(self) -> dict:
        return KERNELS

    def window(self, capture) -> dict:
        if capture is not None:
            capture.start()
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < self.seconds:
            with tracing.maybe(capture, "sweep.call"):
                self.calls.append(self._call())
        t_end = time.perf_counter()
        if capture is not None:
            capture.stop()
        episodes = len(self.calls) * len(self.vols) * self.runs
        ticks = episodes * self.steps
        return {
            "episodes": episodes,
            "elapsed_s": t_end - t0,
            "calls": len(self.calls),
            "compiles_in_window": self.compiles.between(t0, t_end),
            "work": {
                "mesi_tick": ticks * work.mesi_tick_bytes(self.n, self.m),
                "chunk_tick": ticks * work.chunk_tick_bytes(
                    self.n, self.m, self.n_chunks)},
            # the coherent variant's scan runs each kernel once a step,
            # over all of the call's episodes
            "kernel_calls": {"mesi_tick": len(self.calls) * self.steps,
                             "chunk_tick": len(self.calls) * self.steps},
        }

    # ------------------------------------------------------------ check
    def _reference(self, seeds, invalidate: bool) -> list:
        """Per-cell reference counters (dict of (R,) arrays) of a call."""
        acts, arts, writes, wch = reference.draw_episodes(
            seeds, self.runs, self.n, self.m, self.steps, self.n_chunks,
            self.p_act, self.rates.pick, self.vols,
            [self.span] * len(self.vols))
        W, R = len(self.vols), self.runs
        flat = lambda x: x.reshape((W * R,) + x.shape[2:])
        out = reference.episodes(
            flat(acts), flat(arts), flat(writes), flat(wch), m=self.m,
            tokens=self.tokens, chunk_tokens=self.ct, invalidate=invalidate)
        return [{k: v.reshape(W, R)[w] for k, v in out.items()}
                for w in range(W)]

    def check(self, control: bool = False) -> Outcome:
        k = min(CHECKED_CALLS, len(self.calls))
        chosen = sorted(self.check_rng.choice(len(self.calls), size=k, replace=False))
        broadcast = self.steps * self.n * self.m * (
            self.tokens + reference.SIGNAL_TOKENS)
        run_mismatch = stat_mismatch = 0
        for c in chosen:
            seeds, comps = self.calls[c]
            ref = self._reference(seeds, invalidate=True)
            ctl = (self._reference(seeds, invalidate=False) if control
                   else None)
            for w, (comp, cell) in enumerate(zip(comps, ref)):
                if ctl is not None:   # the control in the program's place
                    comp = _as_comparison(comp, ctl[w])
                got = np.asarray(comp.coherent_per_run_tokens, np.int64)
                run_mismatch += int((got != cell["total_tokens"]).sum()) \
                    if got.shape == cell["total_tokens"].shape else self.runs
                co, bc = comp.coherent, comp.broadcast
                stat_mismatch += sum(
                    getattr(co, f + "_mean") != float(np.mean(cell[f]))
                    for f in COHERENT_MEANS)
                stat_mismatch += sum((
                    bc.total_tokens_mean != broadcast,
                    bc.n_reads_mean != float(np.mean(cell["n_reads"])),
                    bc.n_writes_mean != float(np.mean(cell["n_writes"])),
                    bc.delta_bytes_mean
                    != broadcast * reference.BYTES_PER_TOKEN))
        return Outcome(
            attempted=len(self.calls), failed=0,
            checks=[Check("run_total_mismatch", run_mismatch, 0),
                    Check("stat_mismatch", stat_mismatch, 0)])


def _as_comparison(comp, cell: dict):
    """``comp`` with its coherent answers replaced by ``cell``'s."""
    means = {f + "_mean": float(np.mean(cell[f])) for f in COHERENT_MEANS}
    return dataclasses.replace(
        comp, coherent=dataclasses.replace(comp.coherent, **means),
        coherent_per_run_tokens=tuple(int(t)
                                      for t in cell["total_tokens"]))
