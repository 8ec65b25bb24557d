"""Public op: blob_pack — jitted wrapper choosing Pallas (TPU) vs oracle.

Also provides ``pack_from_keys`` which computes the sorted-order inputs
(argsort by destination) the way the shuffle layer does, and
``blob_pack_fused`` — the single-pass path that runs the sort/rank front
half of ``bin_pack`` and the pack kernel in one jitted program.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels.blob_pack.host import (blob_pack_fused_host,
                                          sorted_order_np)
from repro.kernels.blob_pack.kernel import blob_pack_pallas
from repro.kernels.blob_pack.ref import blob_pack_ref
from repro.shuffle.binning import sorted_order

__all__ = ["blob_pack", "pack_from_keys", "blob_pack_fused",
           "blob_pack_fused_host", "sorted_order_np"]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("capacity", "use_pallas"))
def blob_pack(x, order, starts, counts, *, capacity: int,
              use_pallas: bool = None):
    """(T,d) tokens + sorted-order description -> (bins, capacity, d)."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    if use_pallas:
        return blob_pack_pallas(x, order, starts, counts,
                                capacity=capacity,
                                interpret=not _on_tpu())
    return blob_pack_ref(x, order, starts, counts, capacity=capacity)


@functools.partial(jax.jit, static_argnames=("num_bins", "capacity",
                                             "use_pallas"))
def pack_from_keys(x, keys, *, num_bins: int, capacity: int,
                   use_pallas: bool = None):
    """Convenience: bin tokens by destination key and pack into blobs."""
    order, starts, counts = sorted_order(keys, num_bins)
    return blob_pack(x, order, starts, counts, capacity=capacity,
                     use_pallas=use_pallas), (order, starts, counts)


@functools.partial(jax.jit, static_argnames=("num_bins", "capacity",
                                             "use_pallas"))
def blob_pack_fused(x, keys, *, num_bins: int, capacity: int,
                    use_pallas: bool = None):
    """Fused single-pass pack: ``bin_pack``'s sort/rank and the gather run
    in one jitted pass.

    (tokens, destination keys) -> ((bins, capacity, d), sorted-order
    description). Bit-exact with ``pack_from_keys``."""
    order, starts, counts = sorted_order(keys, num_bins)
    if use_pallas is None:
        use_pallas = _on_tpu()
    if use_pallas:
        out = blob_pack_pallas(x, order, starts, counts, capacity=capacity,
                               interpret=not _on_tpu())
    else:
        out = blob_pack_ref(x, order, starts, counts, capacity=capacity)
    return out, (order, starts, counts)
