"""Shards whose batch is between its device call and the end of its
readback, on average over the time at least one is."""

from bench.shards import shards_in_flight as read  # noqa: F401
