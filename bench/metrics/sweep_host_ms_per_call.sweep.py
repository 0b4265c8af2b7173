"""Host time per sweep call (ms): operands, the grid call until it
returns, and the results (``sweep.operands`` + ``sweep.dispatch`` +
``sweep.results``)."""

from bench.phases import SWEEP_HOST, phase_ms

read = phase_ms(*SWEEP_HOST)
