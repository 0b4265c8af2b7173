"""Simulated episodes completed over the window (1/s)."""

from bench.readers import episodes_per_s as read  # noqa: F401
