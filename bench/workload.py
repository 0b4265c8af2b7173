"""Traffic generation from a configuration's rate matrices and a seed.

Copied into the benchmark so that no program change can move the
yardstick:

* ``rate_matrices`` builds the ``zipf`` family the way
  ``repro.sim.workloads.zipf`` does (Zipf(s) artifact pick, uniform
  write rate, ``p_act`` 0.75); ``skew`` 0 is the paper's uniform
  SS8.1 scenario, as in ``repro.launch.service.build_workload``.
* ``open_loop_schedule`` draws each arrival's artifact and read/write
  as ``repro.service.loadgen.sample_round`` draws a round's (categorical
  pick per agent, write Bernoulli at the picked cell), with ``p_act``
  as the agents' weights and Poisson arrivals in place of rounds.
* ``span_write`` rewrites a seeded span of 1-3 chunks, as
  ``chip_smoke.SpanWriter`` does.
"""

from __future__ import annotations

import dataclasses

import numpy as np


def seed_sequence(seed: int, *salt: int) -> np.random.SeedSequence:
    """A numpy seed from a benchmark seed of any size (and a salt)."""
    if seed < 0:
        raise ValueError(f"--seed must be a non-negative integer: {seed}")
    return np.random.SeedSequence([seed, *salt])


def zipf_weights(m: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, m + 1, dtype=np.float64) ** s
    return w / w.sum()


@dataclasses.dataclass(frozen=True)
class Rates:
    p_act: np.ndarray       # (n,) agent activity, the arrivals' weights
    pick: np.ndarray        # (n, m) artifact pick per agent
    write_rate: np.ndarray  # (n, m) P(write | agent picked artifact)


def rate_matrices(n: int, m: int, mix: dict) -> Rates:
    if mix["family"] != "zipf":
        raise ValueError(f"unknown traffic family {mix['family']!r}")
    return Rates(p_act=np.full(n, float(mix["p_act"])),
                 pick=np.tile(zipf_weights(m, float(mix["skew"])), (n, 1)),
                 write_rate=np.full((n, m), float(mix["write_rate"])))


@dataclasses.dataclass(frozen=True)
class Schedule:
    """A fixed list of arrivals, in due order."""

    due_s: np.ndarray      # (N,) seconds after the window opens
    agent: np.ndarray      # (N,) int
    artifact: np.ndarray   # (N,) int
    write: np.ndarray      # (N,) bool

    def __len__(self) -> int:
        return int(self.due_s.size)


def open_loop_schedule(rng: np.random.Generator, rates: Rates,
                       rate_per_s: float, seconds: float) -> Schedule:
    """Poisson arrivals at ``rate_per_s`` over the window, conditioned
    on their count: every seed offers the same number of requests
    (``round(rate * seconds)``) at uniformly scattered due times."""
    count = int(round(rate_per_s * seconds))
    due = np.sort(rng.uniform(0.0, seconds, count))
    agent = rng.choice(rates.p_act.size, size=count,
                       p=rates.p_act / rates.p_act.sum())
    cum = np.cumsum(rates.pick, axis=1)
    cum /= cum[:, -1:]
    u = rng.random(count)
    artifact = np.minimum((u[:, None] >= cum[agent]).sum(axis=1),
                          rates.pick.shape[1] - 1)
    write = rng.random(count) < rates.write_rate[agent, artifact]
    return Schedule(due, agent.astype(np.int64), artifact.astype(np.int64),
                    write)


def span_write(rng: np.random.Generator, content: tuple,
               chunk_tokens: int, max_chunks: int = 3) -> tuple:
    """``content`` with a seeded span of 1..max_chunks whole chunks
    rewritten to fresh tokens."""
    cur = list(content)
    n_chunks = -(-len(cur) // chunk_tokens)
    lo = int(rng.integers(n_chunks)) * chunk_tokens
    hi = min(len(cur), lo + int(rng.integers(1, max_chunks + 1))
             * chunk_tokens)
    cur[lo:hi] = rng.integers(0, 1 << 20, hi - lo).tolist()
    return tuple(cur)


def initial_contents(rng: np.random.Generator, m: int,
                     tokens: int) -> list:
    return [tuple(rng.integers(0, 1 << 20, tokens).tolist())
            for _ in range(m)]
