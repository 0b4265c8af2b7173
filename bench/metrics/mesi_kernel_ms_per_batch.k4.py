"""Device time of the MESI kernel per decided batch, summed over the
four chips (ms)."""

from bench.readers import kernel_ms_per_batch

read = kernel_ms_per_batch("mesi_tick")
