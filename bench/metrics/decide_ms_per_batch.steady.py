"""Host clock around each decider call, ending in readback (ms)."""

from bench.readers import decide_ms_per_batch as read  # noqa: F401
