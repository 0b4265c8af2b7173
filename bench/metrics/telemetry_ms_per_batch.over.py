"""Audit trace, counters and commit hook per batch (ms):
``broker.telemetry``."""

from bench.phases import phase_ms

read = phase_ms("broker.telemetry")
