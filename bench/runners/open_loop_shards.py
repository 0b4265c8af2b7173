"""Open-loop runner for a sharded deployment: ``open_loop``'s schedule,
window and reference check, and three more observations of the shard
plane for the readers.

- ``phases``: the program's batch records of the window, every shard's
  (``bench.phases.served``);
- ``shard_requests``: the requests each shard decided in the window,
  from the global trace's per-batch ``shard`` and ``batch_size``;
- ``l1_wire``: the plane's fill attribution to the host L1s and the L2
  shards (``l1_wire``).

It reads only what the sharded plane has recorded since its batch
records came in, so it runs on a program with one flush task per shard
as on one whose flush rounds hold every shard's batch in flight at once.
"""

from __future__ import annotations

from bench import phases
from bench.runners import open_loop


class Run(open_loop.Run):
    async def _window(self) -> dict:
        broker = self.broker
        first = len(broker.trace.steps)
        obs = await super()._window()
        steps = broker.trace.steps[first:first + obs["batches"]]
        per_shard = [0] * int(self.knobs.get("shards", 1))
        for step in steps:
            per_shard[step.shard] += step.batch_size
        obs["phases"] = phases.served(broker, self.t0,
                                      self.t0 + obs["window_s"])
        obs["shard_requests"] = per_shard
        obs["l1_wire"] = dict(broker.l1_wire)
        return obs
