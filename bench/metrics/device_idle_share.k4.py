"""Share of the window with no device operation running, averaged over
the four chips (%)."""

from bench.readers import device_idle_share as read  # noqa: F401
