"""Decisions completed in the window over the window (1/s)."""

from bench.readers import decisions_per_s as read  # noqa: F401
