"""Programs compiled or loaded from the cache inside the window."""

from bench.readers import compiles_in_window as read  # noqa: F401
