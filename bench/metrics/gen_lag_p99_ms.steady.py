"""How late the generator submitted against its schedule, p99 (ms)."""

from bench.readers import gen_lag_p99_ms as read  # noqa: F401
