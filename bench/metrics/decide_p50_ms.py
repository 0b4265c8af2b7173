"""Median request latency, due time to response (ms)."""

from bench.readers import latency_p50_ms as read  # noqa: F401
