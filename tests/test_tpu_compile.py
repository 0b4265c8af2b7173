"""Compile the main path's Pallas kernels and sweep program for a v5e chip.

Interpret mode runs the kernel bodies as plain JAX, so it accepts code
that Mosaic refuses to lower (dynamic slices, scatters, rank-1 vectors)
and blocks that overflow VMEM.  These tests compile every kernel shape
the served path, the content plane and the sweep engine use for a
*described* v5e chip - no chip needed; the TPU compiler is installed.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and under pytest-xdist every
worker imports this file.  All chip compiles of the suite live in this
one file so that they run in one worker.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels import backend
from repro.kernels.chunk_diff import chunk_tick_pallas
from repro.kernels.mesi_transition import mesi_tick_pallas
from repro.sim import cliff_scenario, engine

pytestmark = pytest.mark.pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described chip's compiles cannot be read back from the
        # persistent cache, so it stays off around them
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _i32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _kernel_ops(compiled) -> list:
    return [line for line in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


#: (B sims, n agents, m artifacts, block, answers): the served decision
#: on one directory (answers on) at the service benchmark's 32 agents,
#: the oracle's one sim, the sweep route, and the served decision at the
#: kernel's documented fleet limit.
MESI_SHAPES = {
    "service": (1, 32, 6, 128, True),
    "oracle": (1, 32, 6, 128, False),
    "sweep": (256, 8, 6, 128, False),
    "fleet": (1, 256, 16, 128, True),
    # the four-shard fleet's shards (crc32 placement: 3 or 5 artifacts)
    "shard3": (1, 256, 3, 128, True),
    "shard5": (1, 256, 5, 128, True),
}


@pytest.mark.parametrize("strategy", ["lazy", "eager", "access_count"])
@pytest.mark.parametrize("shape", list(MESI_SHAPES))
def test_mesi_tick_compiles_for_v5e(one_chip, shape, strategy):
    B, n, m, block, answers = MESI_SHAPES[shape]
    kw = dict(artifact_tokens=4096, eager=strategy == "eager",
              access_k=3 if strategy == "access_count" else 0,
              block_sims=block, answers=answers, interpret=False)
    args = [_i32(s, one_chip) for s in
            ((B, n, m), (B, m), (B, n, m), (B, n, m), (B, n), (B, n),
             (B, n))]
    compiled = jax.jit(
        lambda *a: mesi_tick_pallas(*a, **kw)).lower(*args).compile()
    _assert_kernel(compiled)


#: (B, n, m, C, block): the served content plane (one sim, 16 chunks of
#: 4096 tokens) and the content benchmark's flattened grid (6 families
#: x 3 localities x 4 volatilities x 10 runs).
CHUNK_SHAPES = {
    "service": (1, 32, 6, 16, 128),
    "content_bench": (720, 8, 6, 16, 128),
}


@pytest.mark.parametrize("shape", list(CHUNK_SHAPES))
def test_chunk_tick_compiles_for_v5e(one_chip, shape):
    B, n, m, C, block = CHUNK_SHAPES[shape]
    args = [_i32(s, one_chip) for s in
            ((B, m, C), (B, n, m, C), (B, m, C), (B, n), (B, n), (B, n),
             (B, n, C))]
    compiled = jax.jit(lambda *a: chunk_tick_pallas(
        *a, artifact_tokens=4096, chunk_tokens=4096 // C,
        block_sims=block, interpret=False)).lower(*args).compile()
    _assert_kernel(compiled)


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
@pytest.mark.parametrize("devices", [1, 4])
def test_sweep_program_compiles_for_v5e(topo, monkeypatch, devices):
    """The fused sweep grid with the Pallas tick (4 volatilities x 64
    runs of the canonical scenario), on one chip and shard_map'ed over
    the 2x2 host's four chips."""
    # steer the engine at the described chips: the Pallas kernels lower
    # through Mosaic and the sweep mesh holds the described devices
    monkeypatch.setattr(backend, "interpret_default", lambda: False)
    monkeypatch.setattr(engine, "_GRID_CACHE", {})
    monkeypatch.setattr(engine, "make_sweep_mesh",
                        lambda n, axis="runs": Mesh(topo.devices[:n],
                                                    (axis,)))
    V, R = 4, 64
    plan = engine.ShardPlan(devices, "runs" if devices > 1 else None, R)
    fn = engine._grid_fn(cliff_scenario(0.05).acs, True, "pallas", plan)
    if devices == 1:
        cells = runs = SingleDeviceSharding(topo.devices[0])
    else:
        mesh = Mesh(topo.devices[:devices], ("runs",))
        cells, runs = NamedSharding(mesh, P()), NamedSharding(mesh, P("runs"))
    args = [jax.ShapeDtypeStruct((V,), jnp.float32, sharding=cells),
            jax.ShapeDtypeStruct((V,), jnp.float32, sharding=cells),
            jax.ShapeDtypeStruct((V, 2), jnp.uint32, sharding=cells),
            jax.ShapeDtypeStruct((R,), jnp.int32, sharding=runs)]
    _assert_kernel(fn.lower(*args).compile())


@pytest.mark.parametrize("kernel", ["mesi_tick", "chunk_tick"])
def test_kernels_carry_their_names(one_chip, kernel):
    """Each kernel's device operation is named after it, and still reads
    as a Mosaic call with its output count, the mark the benchmark's
    trace reduction finds it by."""
    from bench.runners.open_loop import KERNELS
    from bench.tracing import custom_call_outputs

    if kernel == "mesi_tick":
        B, n, m, block, answers = MESI_SHAPES["fleet"]
        shapes = ((B, n, m), (B, m), (B, n, m), (B, n, m), (B, n), (B, n),
                  (B, n))
        fn = lambda *a: mesi_tick_pallas(    # noqa: E731
            *a, artifact_tokens=4096, block_sims=block, answers=answers,
            interpret=False)
    else:
        B, n, m, C, block = CHUNK_SHAPES["service"]
        shapes = ((B, m, C), (B, n, m, C), (B, m, C), (B, n), (B, n),
                  (B, n), (B, n, C))
        fn = lambda *a: chunk_tick_pallas(   # noqa: E731
            *a, artifact_tokens=4096, chunk_tokens=4096 // C,
            block_sims=block, interpret=False)
    compiled = jax.jit(fn).lower(
        *[_i32(s, one_chip) for s in shapes]).compile()
    (op,) = _kernel_ops(compiled)
    assert op.lstrip().startswith(f"%{kernel}")
    assert custom_call_outputs(op) == KERNELS[kernel]


@pytest.mark.parametrize("program", ["mesi_service", "mesi_fleet",
                                     "mesi_shard3", "mesi_shard5",
                                     "chunk_service"])
def test_served_programs_compile_for_v5e(one_chip, program):
    """The served path's jitted decision programs (the one-sim MESI tick
    with its answers, the content plane's one-sim chunk tick) each hold
    exactly one named kernel call on a v5e chip, with the output count
    the benchmark finds the kernel by."""
    from bench.runners.open_loop import KERNELS
    from bench.tracing import custom_call_outputs
    from repro.kernels.mesi_transition import mesi_decision_program
    from repro.service.batching import _chunk_decider

    if program.startswith("mesi"):
        kernel = "mesi_tick"
        _, n, m, _, _ = MESI_SHAPES[program.split("_")[1]]
        shapes = ((n, m), (m,), (n, m), (n, m), (n,), (n,), (n,))
        lowered = mesi_decision_program.lower(
            *[_i32(s, one_chip) for s in shapes], artifact_tokens=4096,
            eager=False, access_k=0, signal_tokens=12, interpret=False)
    else:
        kernel = "chunk_tick"
        _, n, m, C, _ = CHUNK_SHAPES["service"]
        shapes = ((m, C), (n, m, C), (m, C), (n,), (n,), (n,), (n, C))
        lowered = _chunk_decider.lower(
            *[_i32(s, one_chip) for s in shapes], artifact_tokens=4096,
            chunk_tokens=4096 // C, signal_tokens=12, interpret=False)
    (op,) = _kernel_ops(lowered.compile())
    assert op.lstrip().startswith(f"%{kernel}")
    assert custom_call_outputs(op) == KERNELS[kernel]
