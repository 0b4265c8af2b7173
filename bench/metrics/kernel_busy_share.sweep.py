"""The two kernels' share of device busy time (%)."""

from bench.readers import kernel_busy_share as read  # noqa: F401
