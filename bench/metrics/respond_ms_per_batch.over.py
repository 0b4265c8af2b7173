"""Ledger, content plane and responses per batch (ms): ``broker.respond``."""

from bench.phases import phase_ms

read = phase_ms("broker.respond")
