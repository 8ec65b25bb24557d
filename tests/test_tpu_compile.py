"""Ahead-of-time TPU compiles of the blob kernels at real widths.

Interpret mode (``tests/test_kernels.py``) accepts what the TPU compiler
refuses: unaligned slices, gathers Mosaic cannot lower, blocks that do
not fit VMEM. These tests compile each kernel for a v5e chip that is
described, not attached, at the two geometries ``chip_smoke.py`` runs,
and check that the compiled program holds the Pallas kernel. Nothing
runs, so they say nothing about results or times.

The topology is described inside a fixture: only one process at a time
may load the TPU library, so describing it while modules are imported
would break collection under several test workers.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.blob_codec.kernel import (compress_pack_fused_pallas,
                                             unpack_decompress_fused_pallas)
from repro.kernels.blob_pack.kernel import blob_pack_pallas
from repro.kernels.blob_unpack.kernel import blob_unpack_pallas

#: (rows, width, dtype, bins, capacity): the paper's evaluation blob
#: (16384 x 1 KiB records, 216 partitions) and the MoE dispatch of
#: deepseek-v2-lite (8192 tokens x d_model 2048, 64 experts)
GEOMETRIES = [
    pytest.param(16384, 256, jnp.float32, 216, 128, id="paper-blob"),
    pytest.param(8192, 2048, jnp.bfloat16, 64, 1024, id="moe-dispatch"),
]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # whatever the cause, there is no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(lambda *a: fn(*a, interpret=False)).lower(
        *args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows,width,dtype,bins,cap", GEOMETRIES)
def test_blob_pack_compiles(one_chip, rows, width, dtype, bins, cap):
    _compile(lambda x, o, s, c, **kw: blob_pack_pallas(
        x, o, s, c, capacity=cap, **kw),
        ((rows, width), dtype), ((rows,), jnp.int32), ((bins,), jnp.int32),
        ((bins,), jnp.int32), sharding=one_chip)


@pytest.mark.parametrize("rows,width,dtype,bins,cap", GEOMETRIES)
def test_blob_unpack_compiles(one_chip, rows, width, dtype, bins, cap):
    _compile(blob_unpack_pallas, ((bins, cap, width), dtype),
             ((rows,), jnp.int32), ((rows,), jnp.bool_), sharding=one_chip)


@pytest.mark.parametrize("rows,width,dtype,bins,cap", GEOMETRIES)
def test_compress_pack_compiles(one_chip, rows, width, dtype, bins, cap):
    _compile(lambda x, o, s, c, **kw: compress_pack_fused_pallas(
        x, o, s, c, capacity=cap, **kw),
        ((rows, width), dtype), ((rows,), jnp.int32), ((bins,), jnp.int32),
        ((bins,), jnp.int32), sharding=one_chip)


@pytest.mark.parametrize("rows,width,dtype,bins,cap", GEOMETRIES)
def test_unpack_decompress_compiles(one_chip, rows, width, dtype, bins,
                                    cap):
    _compile(unpack_decompress_fused_pallas, ((bins, cap, width), jnp.int8),
             ((bins, cap), jnp.float32), ((rows,), jnp.int32),
             ((rows,), jnp.bool_), sharding=one_chip)
