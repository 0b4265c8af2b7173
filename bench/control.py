#!/usr/bin/env python3
"""Readings of the check that decides ``correct``: the program and its
control, over many seeds of one cell, in one process.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

For each seed the cell's window runs at its own load, as in a run of
``bench/run.py``; then every number compared is read twice: once for
what the program answered, once for the control - the plain reference
with write-invalidation left out, put in the program's place.  One JSON
line per seed, then one with, for each number, the largest program
reading (the lower end of its limit) and the smallest control reading
(the upper end).  The control has to fail at least one number.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    manifest = harness.Manifest(ROOT)
    cell = manifest.cell(args.workload)
    devices = harness.require_chips(cell["chips"])
    harness.enable_compile_cache(ROOT)
    config, traffic = manifest.config(cell), manifest.traffic(cell)
    counter = harness.CompileCounter()
    lower: dict = {}
    upper: dict = {}
    for seed in args.seeds:
        run = manifest.runner(traffic).Run(
            config, traffic, seed=seed, seconds=args.seconds, devices=devices,
            compiles=counter)
        run.setup()
        run.window(None)
        program = {c.name: c.value for c in run.check().checks}
        control = {c.name: c.value for c in run.check(control=True).checks}
        for name, value in program.items():
            lower[name] = max(lower.get(name, value), value)
            upper[name] = min(upper.get(name, control[name]),
                              control[name])
        print(json.dumps({"seed": seed, "program": program,
                          "control": control}), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": len(args.seeds),
                      "program_max": lower, "control_min": upper}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
