#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Sets up and warms the cell's deployment (``setup_s``, from process
start), measures for ``--seconds``, then checks what the window
produced against the plain reference.  The last lines of standard error
are the numbers compared, each beside its limit; the last line of
standard output is the result as one JSON object: with ``--trace 0``
the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics read from a profiler trace of the window.  Exits non-zero and
prints no result on a host without the TPUs the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    try:
        import repro.service  # noqa: F401 - the system under test
    except ImportError as e:
        print(f"bench: the program is not in this checkout: {e}",
              file=sys.stderr)
        return 2
    try:
        manifest = harness.Manifest(ROOT)
        devices = harness.require_chips(
            manifest.cell(args.workload)["chips"])
        harness.enable_compile_cache(ROOT)
        result = harness.run_cell(
            manifest, args.workload, seed=args.seed, seconds=args.seconds,
            traced=bool(args.trace), devices=devices, t_start=T_START)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    print("\n".join(harness.check_lines(result)), file=sys.stderr,
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
