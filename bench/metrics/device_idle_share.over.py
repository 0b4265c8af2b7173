"""Share of the window with no device operation running (%)."""

from bench.readers import device_idle_share as read  # noqa: F401
