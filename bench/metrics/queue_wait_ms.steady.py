"""Median submit-to-batch-cut wait from the telemetry request spans (ms)."""

from bench.readers import queue_wait_p50_ms as read  # noqa: F401
