"""The device stages of ``plan="device"`` compile for a TPU v5e chip.

No chip is needed: the TPU compiler is installed, and it compiles for a
described ``v5e:2x2`` topology.  Interpret-mode tests cannot show what
Mosaic refuses (a 1-D gather in a kernel body, a bool broadcast it cannot
lay out); these compiles can.  Shapes are the served ones: a 2**24-slot
arena probed by a 32-query batch at k=16, the sweep at every size bucket,
and the batched ICWS sketch of 32 queries of 512 distinct tokens.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ARENA_SLOTS = 1 << 24
PROBES = 32 * 16            # B queries x k coordinates


@pytest.fixture(scope="module")
def no_persistent_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep these out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def test_resident_probe_compiles_at_deployment_arena(one_chip):
    from repro.core.device_plan import _probe_jit_factory
    u32 = lambda n: _spec(one_chip, (n,), jnp.uint32)
    compiled = _probe_jit_factory().lower(
        u32(ARENA_SLOTS), u32(ARENA_SLOTS), u32(ARENA_SLOTS),
        _spec(one_chip, (ARENA_SLOTS + 1,), jnp.int32),
        u32(PROBES), u32(PROBES), u32(PROBES),
        _spec(one_chip, (PROBES,), jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    # the arena stays in HBM as arguments; nothing arena-sized is staged
    assert mem.argument_size_in_bytes >= 4 * 4 * ARENA_SLOTS
    assert mem.temp_size_in_bytes < 4 * ARENA_SLOTS


@pytest.mark.parametrize("S", [8, 16, 32])
def test_sweep_grid_compiles(one_chip, S):
    from repro.kernels.sweep_grid import sweep_grid
    G = 61                                       # not a multiple of BG
    compiled = jax.jit(
        lambda r, s: sweep_grid(r, s, m=13, interpret=False)).lower(
        _spec(one_chip, (G, S, 4), jnp.int32),
        _spec(one_chip, (G,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()   # a Mosaic kernel


def test_icws_sketch_batch_compiles(one_chip):
    from repro.kernels.icws_hash import icws_sketch_batch
    B, K, T = 32, 16, 512
    f32 = lambda *shape: _spec(one_chip, shape, jnp.float32)
    compiled = jax.jit(
        lambda r, c, b, w: icws_sketch_batch(r, c, b, w,
                                             interpret=False)).lower(
        f32(B, K, T), f32(B, K, T), f32(B, K, T), f32(B, T)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_icws_sketch_compiles(one_chip):
    from repro.kernels.icws_hash import icws_sketch
    K, T = 16, 512
    f32 = lambda *shape: _spec(one_chip, shape, jnp.float32)
    compiled = jax.jit(
        lambda r, c, b, w: icws_sketch(r, c, b, w, interpret=False)).lower(
        f32(K, T), f32(K, T), f32(K, T), f32(T)).compile()
    assert "tpu_custom_call" in compiled.as_text()
