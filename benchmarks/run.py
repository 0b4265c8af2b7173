"""Benchmark harness entry point - one module per paper table/figure.

Usage:
    PYTHONPATH=src python -m benchmarks.run            # all benches
    PYTHONPATH=src python -m benchmarks.run table1 cliff

Prints ``name,us_per_call,derived`` CSV rows; each module also writes
markdown + JSON under ``benchmarks/results/`` (consumed by
EXPERIMENTS.md).
"""

from __future__ import annotations

import os

if os.environ.get("REPRO_BENCH_FAST", "0") == "1":
    # Smoke mode: compile time dominates the suite on CPU; dialing XLA's
    # backend optimization down ~30% per program changes no integer
    # token counters.  Must happen before jax initializes.
    os.environ["XLA_FLAGS"] = ("--xla_backend_optimization_level=0 "
                               + os.environ.get("XLA_FLAGS", ""))

import importlib
import sys
import traceback

#: module name -> short alias
MODULES = {
    "table1_scenarios": "table1",
    "table2_strategies": "table2",
    "table3_agent_scaling": "table3",
    "table4_artifact_size": "table4",
    "table5_step_scaling": "table5",
    "volatility_cliff": "cliff",
    "workload_zoo": "zoo",
    "content_plane": "content",
    "pointer_semantics": "pointer",
    "prompt_cache_amplification": "promptcache",
    "staleness_tradeoff": "staleness",
    "serving_flops": "serving",
    "service_bench": "service",
    "kernel_micro": "kernels",
    # last: its cold-compile measurement clears the jit caches, which
    # would force the modules after it to recompile warm programs.
    "sweep_engine": "sweep",
}


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    selected = set(sys.argv[1:])
    print("name,us_per_call,derived")
    failures = []
    for mod_name, alias in MODULES.items():
        if selected and alias not in selected and mod_name not in selected:
            continue
        try:
            mod = importlib.import_module(f"benchmarks.{mod_name}")
        except ImportError as e:  # optional module not yet available
            print(f"{alias},0.00,SKIPPED import error: {e}")
            continue
        try:
            for row in mod.run():
                print(row.csv())
        except Exception as e:
            failures.append((alias, e))
            traceback.print_exc()
            print(f"{alias},0.00,FAILED {type(e).__name__}: {e}")
    if failures:
        raise SystemExit(f"{len(failures)} benchmark module(s) failed")


if __name__ == "__main__":
    main()
