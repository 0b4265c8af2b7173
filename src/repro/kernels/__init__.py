"""Pallas TPU kernels for the performance-critical compute layers.

Kernels (each: pl.pallas_call + explicit BlockSpec VMEM tiling; jit
wrappers in ``ops.py``; pure-jnp oracles in ``ref.py``):

  * ``flash_attention``  - FA-2-style GQA attention (train / prefill)
  * ``decode_attention`` - flash-decode split-K (single-token serving)
  * ``rmsnorm``          - fused RMS normalization
  * ``mesi_tick``        - batched coherence tick (fleet-scale DES)
  * ``chunk_tick``       - batched chunk-diff / delta-coherence tick
                           (content plane; consumes mesi_tick's
                           per-agent miss output)
"""

from repro.kernels.ops import (rmsnorm, flash_attention, decode_attention,
                               mesi_tick)
from repro.kernels import ref
from repro.kernels.backend import interpret_default, resolve_interpret
from repro.kernels.chunk_diff import chunk_tick_pallas, chunk_tick_ref

__all__ = ["rmsnorm", "flash_attention", "decode_attention", "mesi_tick",
           "chunk_tick_pallas", "chunk_tick_ref",
           "ref", "interpret_default", "resolve_interpret"]
