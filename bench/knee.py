#!/usr/bin/env python3
"""Knee sweep of an open-loop cell: serve its configuration at each of
a list of offered rates, in one process, and print one JSON line per
rate.

    python3 bench/knee.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 400 800 1200

For each rate: the requests offered, those completed inside the window
per second (and inside its second half, the steady rate), the backlog
at the close (requests due in the window and not yet answered), the
latency median and 99th percentile of all requests, and the medians of
the requests due in the window's second and last fifths.  The knee is
the highest rate whose completions keep up and whose backlog does not
grow (the last fifth's median stays near the second fifth's); a cell's
traffic file fixes its rate as a share of it.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    manifest = harness.Manifest(ROOT)
    cell = manifest.cell(args.workload)
    devices = harness.require_chips(cell["chips"])
    harness.enable_compile_cache(ROOT)
    config, traffic = manifest.config(cell), manifest.traffic(cell)
    counter = harness.CompileCounter()
    for rate in args.rates:
        run = manifest.runner(traffic).Run(
            config, dict(traffic, rate_per_s=rate), seed=args.seed,
            seconds=args.seconds, devices=devices,
            compiles=counter)
        run.setup()
        obs = run.window(None)
        offered = len(run.sched)
        lat = obs["latency_ms"]
        half = run.t0 + args.seconds / 2
        close = run.t0 + obs["window_s"]
        second_half = int(((run.t_done > half) & (run.t_done <= close)).sum())
        # latency of the requests due in the window's second fifth and in
        # its last fifth: a backlog that grows shows as the later median
        # running away from the earlier one
        due = run.sched.due_s[~np.isnan(run.t_done)]
        fifth = args.seconds / 5
        early = lat[(due >= fifth) & (due < 2 * fifth)]
        late = lat[due >= 4 * fifth]
        print(json.dumps({
            "rate_per_s": rate, "offered": offered,
            "completed_per_s": obs["completed_in_window"] / obs["window_s"],
            "second_half_per_s": second_half / (close - half),
            "backlog_at_close": offered - obs["completed_in_window"],
            "batches": obs["batches"],
            "p50_ms": harness.percentile(lat, 50) if len(lat) else None,
            "p99_ms": harness.percentile(lat, 99) if len(lat) else None,
            "p50_second_fifth_ms": (harness.percentile(early, 50)
                                    if len(early) else None),
            "p50_last_fifth_ms": (harness.percentile(late, 50)
                                  if len(late) else None),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
