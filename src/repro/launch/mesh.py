"""Production mesh builders.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (the dry-run must set
``--xla_force_host_platform_device_count`` before any jax init).

CPU hook: to exercise the device-sharded sweep path
(``repro.sim.engine``) without accelerators, force a multi-device host
topology *before* the first jax import::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m pytest tests/test_sharded_sweep.py -q

CI's ``sharded`` job does exactly this, so every PR runs the
``shard_map`` grid runners on 8 (virtual) devices.
"""

from __future__ import annotations

from typing import Optional

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (v5e pod slice).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the 'pod' axis is
    an outer data-parallel dim whose collectives ride the inter-pod DCN.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1-device mesh for CPU tests (same axis names)."""
    return jax.make_mesh((1, 1), ("data", "model"))


def shard_devices(n_shards: int, axis_name: str = "shards") -> tuple:
    """Round-robin device assignment for K authority-broker shards.

    Reuses the sweep-mesh machinery: a 1-D mesh over min(K, local
    devices) and a length-K tuple assigning each shard its device, so
    every shard's micro-batch decision (``mesi_decision_dispatch`` /
    ``apply_actions``) runs as its own device program.  On a
    single-device host every shard maps to device 0 - byte-for-byte
    the unpinned behavior (CI forces 8 host devices to exercise the
    real placement; see the module docstring).
    """
    n = max(1, min(int(n_shards), len(jax.devices())))
    mesh = make_sweep_mesh(n, axis_name)
    devices = list(mesh.devices.flat)
    return tuple(devices[s % len(devices)] for s in range(int(n_shards)))


def make_sweep_mesh(n_devices: Optional[int] = None,
                    axis_name: str = "runs"):
    """1-D mesh for the device-sharded fleet sweep engine.

    The sweep grids of ``repro.sim.engine`` are embarrassingly parallel
    along their batch axes, so the engine shards them over a single
    mesh axis - ``"runs"`` normally, ``"workloads"`` when the run axis
    does not divide (see ``engine.shard_plan``).  ``n_devices`` defaults
    to every local device; pass fewer to sweep on a sub-mesh.
    """
    n = len(jax.devices()) if n_devices is None else n_devices
    return jax.make_mesh((n,), (axis_name,))
