"""99th-percentile request latency below the knee, recorded only (ms)."""

from bench.readers import latency_p99_ms as read  # noqa: F401
