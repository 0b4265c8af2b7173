"""Broker host time per batch outside the decider (ms)."""

from bench.readers import broker_host_ms_per_batch as read  # noqa: F401
