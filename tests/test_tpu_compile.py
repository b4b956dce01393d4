"""Compile the main path's Pallas kernels for a described TPU v5e, at real
widths, with no chip attached.

The TPU compiler is installed with jaxlib; `get_topology_desc` describes a
v5e 2x2 host so that Mosaic lowers and compiles each kernel exactly as it
would on the chip.  Nothing runs: these tests catch what the interpreter
cannot (unsupported shape casts, unlowerable primitives, VMEM overflow).
The topology is described inside a fixture, so importing this file loads
no TPU library, and every test skips where it cannot be described.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.rmw_engine import PALLAS_FETCHED_MAX_SLOTS
from repro.kernels.rmw import kernel as rmw_kernel
from repro.kernels.ssd import kernel as ssd_kernel

#: mamba2_780m widths: 48 heads of 64 over d_inner 3072, d_state 128,
#: chunk 256; batch 4 at sequence 2048
SSD_BH, SSD_S, SSD_P, SSD_N, SSD_CHUNK = 4 * 48, 2048, 64, 128, 256
#: a 2^20-op batch against the largest table the engine gives the per-tile
#: fetched kernel (32 programs of 512 slots)
RMW_SLOTS, RMW_OPS = PALLAS_FETCHED_MAX_SLOTS, 1 << 20


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("op", ["faa", "max", "swp"])
def test_rmw_table_compiles(one_chip, op):
    tab = _spec((1 << 14,), jnp.float32, one_chip)
    idx = _spec((RMW_OPS,), jnp.int32, one_chip)
    val = _spec((RMW_OPS,), jnp.float32, one_chip)
    _assert_mosaic(lambda t, i, v: rmw_kernel.rmw_table(
        t, i, v, op, interpret=False), tab, idx, val)


@pytest.mark.parametrize("op", ["faa", "cas"])
def test_rmw_table_fetched_compiles(one_chip, op):
    tab = _spec((RMW_SLOTS,), jnp.float32, one_chip)
    idx = _spec((RMW_OPS,), jnp.int32, one_chip)
    val = _spec((RMW_OPS,), jnp.float32, one_chip)
    exp = _spec((), jnp.float32, one_chip)
    _assert_mosaic(lambda t, i, v, e: rmw_kernel.rmw_table_fetched(
        t, i, v, op, expected=e, interpret=False), tab, idx, val, exp)


def test_slot_occupancy_compiles(one_chip):
    idx = _spec((RMW_OPS,), jnp.int32, one_chip)
    _assert_mosaic(lambda i: rmw_kernel.slot_counts(
        i, 1 << 14, interpret=False), idx)


def test_ssd_chunk_compiles_at_mamba2_780m_widths(one_chip):
    xdt = _spec((SSD_BH, SSD_S, SSD_P), jnp.float32, one_chip)
    adt = _spec((SSD_BH, SSD_S), jnp.float32, one_chip)
    bc = _spec((SSD_BH, SSD_S, SSD_N), jnp.float32, one_chip)
    _assert_mosaic(lambda *a: ssd_kernel.ssd_chunk(
        *a, chunk=SSD_CHUNK, interpret=False), xdt, adt, bc, bc)
