"""Tracing, lowering and compiling or loading programs per batch (ms),
as JAX reports them and the program charges them to the batch that
paid; 0.0 when nothing was built in the window."""

from bench.phases import build_ms as read  # noqa: F401
