"""Pallas TPU kernel for blob_unpack (the Debatcher's extract).

Grid (U / row_tile,). Program ``t`` copies the rows of units
``t * row_tile ...`` straight from the blob in HBM to the output in HBM,
one DMA per valid unit (``repro.kernels.row_dma``). Each unit's source
row, or -1 for an invalid (capacity-dropped) unit, is prefetched into
SMEM. The output is aliased to a zero buffer, and rows of invalid units
are never written, so they stay zero.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.row_dma import (as_slabs, from_slabs, start_row_copy,
                                   wait_row_copies)

ROW_TILE = 128


def _make_kernel(row_tile: int):
    def kernel(src_ref, buf_hbm, zeros_hbm, out_hbm, sem):
        del zeros_hbm                      # aliased with out_hbm
        base = pl.program_id(0) * row_tile

        def start(i, n):
            s = src_ref[base + i]
            keep = s >= 0

            @pl.when(keep)
            def _():
                start_row_copy(buf_hbm, s, out_hbm, base + i, sem)

            return n + keep.astype(jnp.int32)

        n = jax.lax.fori_loop(0, row_tile, start, jnp.int32(0))
        wait_row_copies(buf_hbm, out_hbm, sem, n)
    return kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def blob_unpack_pallas(buf, slot, valid, *, interpret: bool = True):
    """(bins, cap, d) blob + (U,) slots and valid mask -> (U, d),
    bit-exact with ``blob_unpack_ref``."""
    bins, cap, d = buf.shape
    U = slot.shape[0]
    slabs = as_slabs(buf.reshape(bins * cap, d))
    row_tile = min(ROW_TILE, U)
    pad = -U % row_tile
    src = jnp.where(valid, jnp.clip(slot, 0, bins * cap - 1), -1)
    src = jnp.pad(src.astype(jnp.int32), (0, pad), constant_values=-1)
    out_shape = (U,) + slabs.shape[1:]
    out = pl.pallas_call(
        _make_kernel(row_tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=((U + pad) // row_tile,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct(out_shape, buf.dtype),
        input_output_aliases={2: 0},
        interpret=interpret,
    )(src, slabs, jnp.zeros(out_shape, buf.dtype))
    return from_slabs(out, d)
