"""Single-row DMAs for the blob kernels (the Batcher and Debatcher gathers).

A TPU copies between HBM and VMEM in whole tiles: the last two dims of
an array are laid out in tiles of (8, 128) 32-bit words, and a 16- or
8-bit tile packs 2 or 4 rows into each word. The compiler refuses a DMA
that slices one row out of such a tile. So the blob kernels see a
(N, d) row array as (N, k, d / k) slabs, ``k = 4 // itemsize`` (1 for
32-bit types): every row is then a slab of its own along the leading
dim, a whole number of packed words, and one row is one DMA. ``d / k``
is padded to a multiple of 128 lanes. For 32-bit rows whose width is a
multiple of 128 the view is free; other rows cost one copy in XLA.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def as_slabs(x: jax.Array) -> jax.Array:
    """(N, d) rows of a dtype up to 32 bits -> (N, k, w) slabs, zero-padded
    so that ``w`` is a multiple of ``LANES``."""
    n, d = x.shape
    size = x.dtype.itemsize
    if size > 4:
        raise ValueError(f"rows of {x.dtype} are wider than 32 bits")
    k = 4 // size
    w = -(-d // (k * LANES)) * LANES
    pad = k * w - d
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    return x.reshape(n, k, w)


def from_slabs(slabs: jax.Array, d: int) -> jax.Array:
    """Inverse of ``as_slabs``: (..., k, w) slabs -> (..., d) rows."""
    *lead, k, w = slabs.shape
    return slabs.reshape(*lead, k * w)[..., :d]


def start_row_copy(src_hbm, s, dst, i, sem) -> None:
    """Start the DMA of row ``s`` of ``src_hbm`` (N, k, w) into row ``i``
    of ``dst`` (M, k, w), signalling ``sem`` when it lands."""
    pltpu.make_async_copy(src_hbm.at[s], dst.at[i], sem).start()


def wait_row_copies(src_hbm, dst, sem, n) -> None:
    """Wait for ``n`` row DMAs started by ``start_row_copy`` on ``sem``.
    Every row has the same size, so any row's descriptor waits for one."""
    def wait(_, carry):
        pltpu.make_async_copy(src_hbm.at[0], dst.at[0], sem).wait()
        return carry
    jax.lax.fori_loop(0, n, wait, 0)
