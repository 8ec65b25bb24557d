"""Pallas TPU kernel for blob_pack (the Batcher's gather into blob layout).

Grid (bins, capacity / row_tile). Program (b, t) copies rows
``t * row_tile ...`` of bin ``b`` straight from the token rows in HBM to
the blob in HBM, one DMA per row (``repro.kernels.row_dma``): nothing is
staged in VMEM, so the token array may be any size. ``order``,
``starts`` and ``counts`` are prefetched into SMEM, where the kernel
reads them as scalars. The output is aliased to a zero buffer, and rows
past a bin's count are never written, so they stay zero.

``row_tile`` sets how many row DMAs one program issues before it waits
for them; ``benchmarks/micro.py`` sweeps it on the device. It changes no
output bit.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.row_dma import (as_slabs, from_slabs, start_row_copy,
                                   wait_row_copies)

ROW_TILE = 128

#: row-tile candidates the device benchmark lane sweeps (clamped to
#: capacity at call time)
SWEEP_ROW_TILES = (8, 32, 64, 128, 256)


def bin_rows(counts_ref, b, t, *, capacity: int, row_tile: int):
    """Rows of tile ``t`` of bin ``b`` that hold a record (a prefix)."""
    filled = jnp.minimum(counts_ref[b], capacity)
    return jnp.clip(filled - t * row_tile, 0, row_tile)


def _make_pack_kernel(capacity: int, row_tile: int):
    def kernel(order_ref, starts_ref, counts_ref, x_hbm, zeros_hbm,
               out_hbm, sem):
        del zeros_hbm                      # aliased with out_hbm
        b = pl.program_id(0)
        t = pl.program_id(1)
        n = bin_rows(counts_ref, b, t, capacity=capacity, row_tile=row_tile)
        first = starts_ref[b] + t * row_tile
        out_bin = out_hbm.at[b]

        def start(i, carry):
            start_row_copy(x_hbm, order_ref[first + i], out_bin,
                           t * row_tile + i, sem)
            return carry

        jax.lax.fori_loop(0, n, start, 0)
        wait_row_copies(x_hbm, out_bin, sem, n)
    return kernel


@functools.partial(jax.jit,
                   static_argnames=("capacity", "interpret", "row_tile"))
def blob_pack_pallas(x, order, starts, counts, *, capacity: int,
                     interpret: bool = True,
                     row_tile: Optional[int] = None):
    """(T, d) tokens + sorted-order description -> (bins, capacity, d),
    bit-exact with ``blob_pack_ref``."""
    bins = starts.shape[0]
    d = x.shape[-1]
    slabs = as_slabs(x)
    row_tile = min(row_tile or ROW_TILE, capacity)
    out_shape = (bins, capacity) + slabs.shape[1:]
    out = pl.pallas_call(
        _make_pack_kernel(capacity, row_tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(bins, pl.cdiv(capacity, row_tile)),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct(out_shape, x.dtype),
        input_output_aliases={4: 0},
        interpret=interpret,
    )(order.astype(jnp.int32), starts.astype(jnp.int32),
      counts.astype(jnp.int32), slabs, jnp.zeros(out_shape, x.dtype))
    return from_slabs(out, d)
