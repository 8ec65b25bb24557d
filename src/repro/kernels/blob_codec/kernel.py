"""Pallas TPU kernels for the blob compress+pack codec.

``compress_pack_fused_pallas`` gathers each tile of ``row_tile`` blob
rows into VMEM, one DMA per row (``repro.kernels.row_dma``), then
computes the per-row absmax scale and the int8 codes before anything is
stored: the uncompressed blob layout never reaches HBM. Rows are
gathered as float32, the dtype the quantizer works in. Two outputs per
tile: the int8 codes and a lane-dense (1, row_tile) block of scales.

``unpack_decompress_fused_pallas`` is the inverse on the Debatcher
side. Its gather is the ``blob_unpack`` kernel run on the int8 codes.
The dequantize then runs as one elementwise pass in the same jitted
program: int8 rows travel as packed (4, d / 4) slabs
(``repro.kernels.row_dma``), and spreading a slab back out to one code
per lane inside the kernel would need a relayout the kernel cannot
express.

Both are bit-exact with the composed oracles in ``ref.py``: the
quantizer is the *same function* (``ref.quantize_rows``) applied to the
gathered tile, so kernel and oracle cannot drift.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.blob_codec.ref import quantize_rows
from repro.kernels.blob_pack.kernel import bin_rows
from repro.kernels.blob_unpack.kernel import blob_unpack_pallas
from repro.kernels.row_dma import (LANES, as_slabs, start_row_copy,
                                   wait_row_copies)

FUSED_ROW_TILE = 128


def _make_compress_pack_kernel(capacity: int, row_tile: int):
    def kernel(order_ref, starts_ref, counts_ref, x_hbm, q_ref, scale_ref,
               rows_buf, sem):
        b = pl.program_id(0)
        t = pl.program_id(1)
        n = bin_rows(counts_ref, b, t, capacity=capacity, row_tile=row_tile)
        first = starts_ref[b] + t * row_tile

        def start(i, carry):
            start_row_copy(x_hbm, order_ref[first + i], rows_buf, i, sem)
            return carry

        jax.lax.fori_loop(0, n, start, 0)
        wait_row_copies(x_hbm, rows_buf, sem, n)
        keep = jax.lax.broadcasted_iota(jnp.int32, (row_tile, 1), 0) < n
        rows = jnp.where(keep, rows_buf[:, 0, :], 0.0)
        # symmetric per-row int8 quantize; padding -> (0, 1.0)
        q, scale = quantize_rows(rows)
        q_ref[0] = q[:, :q_ref.shape[-1]]        # drop the lane padding
        scale_ref[0] = scale.reshape(1, row_tile)
    return kernel


@functools.partial(jax.jit,
                   static_argnames=("capacity", "interpret", "row_tile"))
def compress_pack_fused_pallas(x, order, starts, counts, *, capacity: int,
                               interpret: bool = True,
                               row_tile: Optional[int] = None):
    """Single-pass gather+quantize pack (bit-exact with
    ``compress_pack_ref``): (T, d) tokens -> (q int8 (bins, capacity, d),
    scales f32 (bins, capacity)). ``row_tile`` overrides the tile depth
    (the device benchmark lane sweeps it); it is rounded up to whole
    128-lane rows of scales, or to the whole capacity."""
    bins = starts.shape[0]
    d = x.shape[-1]
    slabs = as_slabs(x.astype(jnp.float32))
    row_tile = min(-(-(row_tile or FUSED_ROW_TILE) // LANES) * LANES,
                   capacity)
    q, scales = pl.pallas_call(
        _make_compress_pack_kernel(capacity, row_tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(bins, pl.cdiv(capacity, row_tile)),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[
                pl.BlockSpec((1, row_tile, d), lambda b, t, *_: (b, t, 0)),
                pl.BlockSpec((1, 1, row_tile), lambda b, t, *_: (b, 0, t)),
            ],
            scratch_shapes=[
                pltpu.VMEM((row_tile,) + slabs.shape[1:], jnp.float32),
                pltpu.SemaphoreType.DMA(())]),
        out_shape=[
            jax.ShapeDtypeStruct((bins, capacity, d), jnp.int8),
            jax.ShapeDtypeStruct((bins, 1, capacity), jnp.float32),
        ],
        interpret=interpret,
    )(order.astype(jnp.int32), starts.astype(jnp.int32),
      counts.astype(jnp.int32), slabs)
    return q, scales.reshape(bins, capacity)


@functools.partial(jax.jit, static_argnames=("interpret",))
def unpack_decompress_fused_pallas(q, scales, slot, valid, *,
                                   interpret: bool = True):
    """Gather+dequantize unpack (bit-exact with ``unpack_decompress_ref``):
    compressed blob layout -> (U, d) f32."""
    rows = blob_unpack_pallas(q, slot, valid, interpret=interpret)
    flat_s = scales.reshape(-1)
    scale = flat_s[jnp.clip(slot, 0, flat_s.shape[0] - 1)]
    return jnp.where(valid[:, None],
                     rows.astype(jnp.float32) * scale[:, None], 0.0)
