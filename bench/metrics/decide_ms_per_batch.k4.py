"""The decide time the program's trace records for each batch: its own
stage, call, readback and outcomes (ms)."""

from bench.readers import decide_ms_per_batch as read  # noqa: F401
