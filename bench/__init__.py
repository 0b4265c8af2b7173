"""Chip benchmark of the coherence service and the fleet sweep engine.

Run one cell of ``BENCHMARK.json`` with::

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: its configuration under
``bench/configs/``, its traffic mix under ``bench/traffic/`` (whose
``runner`` names a module under ``bench/runners/``) and one reader per
per-layer metric under ``bench/metrics/``.
"""
