"""Open-loop runner: requests arrive on a seeded Poisson schedule at a
rate fixed in the traffic file and are served by one ``connect()``
deployment built from the configuration file.

Each request is timed from its due time to its response, so a stall
delays every request due during it.  After the window closes, every
request due in it is awaited (up to ``DRAIN_S``), and the whole window
is replayed through the plain reference in the serialization order the
service logged: every request's hit or miss and served version, a
seeded sample of the contents (and, with the content plane, the delta
payloads) it returned, the token ledger, the final directory and
versions, and the byte ledger.
"""

from __future__ import annotations

import asyncio
import collections
import dataclasses
import threading
import time
import zlib

import numpy as np

from bench import reference, tracing, work, workload
from bench.harness import Check, Outcome

#: the program's kernels by the output count of their device operation:
#: mesi_tick_pallas returns 6 arrays, chunk_tick_pallas 5
KERNELS = {"mesi_tick": 6, "chunk_tick": 5}
#: seconds to wait, after the window, for the answers still due
DRAIN_S = 60.0
#: reads whose contents (and delta payloads) the check compares
CONTENT_SAMPLES = 1024


@dataclasses.dataclass
class View:
    """What one side - the program or the control - answered."""

    answered: np.ndarray     # (N,) bool
    version: np.ndarray      # (N,) served version
    miss: np.ndarray         # (N,) bool, reads only
    samples: dict            # request -> (content, delta)
    ledger: dict
    valid: np.ndarray        # (n, m) final directory: copy valid
    versions: np.ndarray     # (m,) final authority versions
    wire: dict


class Run:
    def __init__(self, config: dict, traffic: dict, *, seed: int,
                 seconds: float, devices: list, compiles) -> None:
        dep = dict(config["deployment"])
        self.n = dep.pop("n_agents")
        self.m = dep.pop("n_artifacts")
        self.tokens = dep["artifact_tokens"]
        self.ct = dep.get("chunk_tokens", 0)
        self.knobs = dep
        self.names = tuple(f"artifact-{d}" for d in range(self.m))
        # the shard each artifact is placed on, by the configuration's
        # rule crc32(name) % shards
        shards = int(dep.get("shards", 1))
        self.home = np.array([zlib.crc32(name.encode()) % shards
                              for name in self.names])
        self.seconds = float(seconds)
        self.devices = devices
        self.compiles = compiles
        span = int(config.get("write_span_chunks", 0))
        self.span = span

        rng = np.random.default_rng(workload.seed_sequence(seed))
        rates = workload.rate_matrices(self.n, self.m, config["mix"])
        self.contents = workload.initial_contents(rng, self.m, self.tokens)
        self.sched = workload.open_loop_schedule(
            rng, rates, float(traffic["rate_per_s"]), self.seconds)
        # a write commits the writer's copy with a span rewritten; the
        # generator knows every write it sends, so each write's content
        # follows from the schedule alone
        self.written: dict = {}
        if span:
            latest = list(self.contents)
            for i in np.flatnonzero(self.sched.write):
                d = int(self.sched.artifact[i])
                latest[d] = workload.span_write(rng, latest[d], self.ct,
                                                span)
                self.written[int(i)] = latest[d]
        reads = np.flatnonzero(~self.sched.write)
        k = min(CONTENT_SAMPLES, reads.size)
        self.sampled = frozenset(
            rng.choice(reads, size=k, replace=False).tolist())
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever,
                                       name="bench-loop", daemon=True)
        self.broker = None
        self.capture = None
        self.flushes: list = []     # (start, seconds) of each flush

    # ------------------------------------------------------------ setup
    def _connect(self):
        from repro.service import connect
        broker = connect(n_agents=self.n, artifacts=self.names,
                         contents={name: list(c) for name, c
                                   in zip(self.names, self.contents)},
                         **self.knobs)
        for sub in getattr(broker, "brokers", (broker,)):
            sub._flush_once = self._timed_flush(sub._flush_once)
        return broker

    def _timed_flush(self, flush):
        """The broker's ``_flush_once`` (one batch: cut, staging,
        decide, checks, respond, telemetry), timed and annotated in the
        trace when tracing.

        The one place the benchmark reaches past the public API: no
        public record times a whole flush (``test_perfbench`` fails if
        the name goes).  Every deployment, the warm-up's too, goes
        through this wrapper and every call runs on the one loop thread:
        the program's kernels are traced again on each call, their
        source locations (the whole Python stack) enter the persistent
        cache's key, and only equal stacks let the window find what the
        warm-up compiled."""
        def call(*args, **kw):
            t = time.perf_counter()
            with tracing.maybe(self.capture, "broker.flush"):
                out = flush(*args, **kw)
            self.flushes.append((t, time.perf_counter() - t))
            return out
        return call

    def _on_loop(self, coro):
        """Run ``coro`` on the loop thread and wait for its result."""
        return asyncio.run_coroutine_threadsafe(coro, self.loop).result()

    async def _warm(self) -> None:
        """Three batches on a throwaway deployment of the same shape:
        all misses, then writes among reads, then reads - every program
        the window runs is compiled or loaded here."""
        rng = np.random.default_rng(0)
        broker = self._connect()
        async with broker:
            for r in range(3):
                reqs = []
                for a in range(self.n):
                    d = (a + r) % self.m
                    if r == 1 and a % 4 == 0:
                        content = (workload.span_write(
                            rng, self.contents[d], self.ct, self.span)
                            if self.span else None)
                        reqs.append(broker.write(a, self.names[d], content))
                    else:
                        reqs.append(broker.read(a, self.names[d]))
                await asyncio.gather(*reqs)

    def setup(self) -> None:
        self.thread.start()
        self._on_loop(self._warm())
        self.broker = self._connect()
        self._on_loop(self.broker.start())

    def kernel_names(self) -> dict:
        return KERNELS

    # ----------------------------------------------------------- window
    def window(self, capture) -> dict:
        self.capture = capture
        try:
            return self._on_loop(self._window())
        finally:
            self.loop.call_soon_threadsafe(self.loop.stop)
            self.thread.join()
            self.loop.close()

    async def _one(self, i: int, coro) -> None:
        try:
            res = await coro
        except Exception as e:  # noqa: BLE001 - a failed request is
            self.errors[i] = f"{type(e).__name__}: {e}"  # counted, not fatal
            return
        self.t_done[i] = time.perf_counter()
        self.version[i] = res.version
        if not self.sched.write[i]:
            self.miss[i] = not res.hit
            if i in self.sampled:
                self.samples[i] = (res.content, res.delta)

    async def _window(self) -> dict:
        broker, sched = self.broker, self.sched
        N = len(sched)
        self.t_done = np.full(N, np.nan)
        self.version = np.full(N, -1, np.int64)
        self.miss = np.zeros(N, bool)
        self.errors: dict = {}
        self.samples: dict = {}
        subs = getattr(broker, "brokers", (broker,))
        capture = self.capture
        if capture is not None:
            capture.start()
        loop = asyncio.get_running_loop()
        tasks = []
        submit = np.empty(N)
        b0 = [b.n_batches for b in subs]
        t0 = self.t0 = time.perf_counter()
        due = t0 + sched.due_s
        for i in range(N):
            delay = due[i] - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            submit[i] = time.perf_counter()
            agent, name = int(sched.agent[i]), self.names[sched.artifact[i]]
            coro = (broker.write(agent, name, self.written.get(i))
                    if sched.write[i] else broker.read(agent, name))
            tasks.append(loop.create_task(self._one(i, coro)))
        await asyncio.sleep(max(0.0, t0 + self.seconds - time.perf_counter()))
        t_close = time.perf_counter()
        batches = [b.n_batches - n0 for b, n0 in zip(subs, b0)]
        if capture is not None:
            capture.close_window()
        _, pending = await asyncio.wait(tasks, timeout=DRAIN_S)
        for task in pending:
            task.cancel()
        await asyncio.gather(*pending, return_exceptions=True)
        await broker.stop()
        if capture is not None:
            # stopping the profiler writes the trace: only once every
            # request due in the window has its answer and its latency
            capture.stop()

        answered = ~np.isnan(self.t_done)
        spans = broker.telemetry.spans.spans if broker.telemetry else ()
        # the window's batches in the program's own trace (ServiceTrace
        # v4 stamps each step with the seconds its decide took)
        first = sum(b0)
        steps = broker.trace.steps[first:first + sum(batches)]
        return {
            "latency_ms": (self.t_done[answered] - due[answered]) * 1e3,
            "gen_lag_ms": (submit - due) * 1e3,
            "window_s": t_close - t0,
            "completed_in_window": int((self.t_done <= t_close).sum()),
            "batches": sum(batches),
            "flush_s": [s for t, s in self.flushes if t0 <= t <= t_close],
            "decide_s": [step.decide_s for step in steps],
            "compiles_in_window": self.compiles.between(t0, t_close),
            "queue_wait_ms": [1e3 * s.args["queue_s"] for s in spans
                              if s.cat == "request"
                              and t0 <= s.ts_s <= t_close],
            # one directory tick per batch, over the shard's artifacts
            "work": {"mesi_tick": sum(
                nb * work.mesi_tick_bytes(self.n, int((self.home == k).sum()))
                for k, nb in enumerate(batches))},
            # each batch runs the MESI kernel once, and with the content
            # plane the chunk kernel once
            "kernel_calls": {"mesi_tick": sum(batches),
                             "chunk_tick": sum(batches) if self.ct else 0},
        }

    # ------------------------------------------------------------ check
    def _replay(self, invalidate: bool):
        """Replay the logged serialization order through the reference;
        returns (reference, answers by request, order mismatches)."""
        sched = self.sched
        ref = reference.ServedReference(self.n, self.m, self.tokens,
                                        self.contents, self.ct,
                                        invalidate=invalidate)
        # an agent's requests to one shard are served in the order it
        # sent them; requests to different shards may overtake
        queues = collections.defaultdict(collections.deque)
        for i, (a, d) in enumerate(zip(sched.agent.tolist(),
                                       sched.artifact.tolist())):
            queues[a, self.home[d]].append(i)
        answers, order_mismatch = {}, 0
        for step in self.broker.trace.steps:
            for a, d, w in zip(step.agents, step.arts, step.writes):
                queue = queues[a, self.home[d]]
                if not queue:
                    order_mismatch += 1
                    continue
                i = queue.popleft()
                d_i, w_i = int(sched.artifact[i]), bool(sched.write[i])
                order_mismatch += (d, w) != (d_i, w_i)
                answers[i] = ref.apply(a, d_i, w_i, self.written.get(i))
        return ref, answers, order_mismatch

    def _program_view(self) -> View:
        broker = self.broker
        return View(
            answered=~np.isnan(self.t_done), version=self.version,
            miss=self.miss, samples=self.samples,
            ledger=dataclasses.asdict(broker.ledger),
            valid=np.asarray(broker.directory_state) != 0,
            versions=np.asarray(broker.versions, np.int64),
            wire=dict(broker.wire))

    def _control_view(self) -> View:
        """The control in the program's place: what the reference
        without write-invalidation answers for the same requests."""
        ref, answers, _ = self._replay(invalidate=False)
        N = len(self.sched)
        view = View(answered=~np.isnan(self.t_done),
                    version=np.full(N, -1, np.int64),
                    miss=np.zeros(N, bool), samples={},
                    ledger=dict(ref.ledger), valid=ref.valid.copy(),
                    versions=ref.version.copy(), wire=dict(ref.wire))
        for i, ans in answers.items():
            view.version[i] = ans.version
            view.miss[i] = ans.miss
            if i in self.samples:
                view.samples[i] = (ans.content, ans.delta)
        return view

    def _shard_checks(self) -> list:
        """The shard plane: every request was decided by the shard its
        artifact is placed on (``crc32(name) % shards``, the placement
        the configuration states), and each shard's directory lives on
        a chip of its own."""
        k = int(self.knobs["shards"])
        misplaced = 0
        for step in self.broker.trace.steps:
            misplaced += int((self.home[list(step.arts)] != step.shard).sum())
        chips = {d for b in self.broker.brokers
                 for d in b.decider.arrays.state.devices()}
        return [Check("shard_misplaced", misplaced, 0),
                Check("shards_sharing_a_chip",
                      min(k, len(self.devices)) - len(chips), 0)]

    def check(self, control: bool = False) -> Outcome:
        view = self._control_view() if control else self._program_view()
        ref, answers, order_mismatch = self._replay(invalidate=True)
        sched = self.sched
        decision = content = delta = 0
        for i in np.flatnonzero(view.answered).tolist():
            ans = answers.get(i)
            if ans is None:
                decision += 1
                continue
            read = not sched.write[i]
            decision += (view.version[i] != ans.version
                         or (read and view.miss[i] != ans.miss))
            if i in view.samples:
                got_content, got_delta = view.samples[i]
                content += tuple(got_content) != ans.content
                delta += self.ct > 0 and tuple(got_delta) != ans.delta
        n_answered = int(view.answered.sum())
        checks = [
            Check("unanswered", len(sched) - n_answered, 0),
            Check("order_mismatch", order_mismatch, 0),
            Check("decision_mismatch", decision, 0),
            Check("content_mismatch", content, 0),
            Check("ledger_diff", sum(abs(view.ledger[f] - ref.ledger[f])
                                     for f in reference.LEDGER_FIELDS), 0),
            Check("directory_diff",
                  int((view.valid != ref.valid).sum()
                      + (view.versions != ref.version).sum()), 0),
        ]
        if self.knobs.get("shards", 1) > 1:
            checks += self._shard_checks()
        if self.ct:
            checks += [
                Check("delta_mismatch", delta, 0),
                Check("wire_diff", sum(abs(view.wire[f] - ref.wire[f])
                                       for f in reference.WIRE_FIELDS), 0)]
        return Outcome(attempted=len(sched),
                       failed=len(sched) - n_answered, checks=checks,
                       notes=[f"request {i}: {e}" for i, e
                              in list(self.errors.items())[:5]])
