"""Public op: blob_unpack — jitted wrapper (Pallas on TPU, oracle on CPU).

``unpack_from_keys`` is the fused Debatcher path matching
``blob_pack.blob_pack_fused``: slot/valid derivation (``bin_pack``'s
rank) and the unpack kernel run in one jitted pass.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels.blob_unpack.kernel import blob_unpack_pallas
from repro.kernels.blob_unpack.ref import blob_unpack_ref
from repro.shuffle.binning import bin_pack


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def blob_unpack(buf, slot, valid, *, use_pallas: bool = None):
    if use_pallas is None:
        use_pallas = _on_tpu()
    if use_pallas:
        return blob_unpack_pallas(buf, slot, valid,
                                  interpret=not _on_tpu())
    return blob_unpack_ref(buf, slot, valid)


@functools.partial(jax.jit, static_argnames=("num_bins", "capacity",
                                             "use_pallas"))
def unpack_from_keys(buf, keys, *, num_bins: int, capacity: int,
                     use_pallas: bool = None):
    """Fused Debatcher extract: derive slot/valid from destination keys
    (``bin_pack``'s rank half) and gather unit rows in the same jitted
    pass — (bins, capacity, d) + keys -> (U, d)."""
    pack = bin_pack(keys, num_bins, capacity)
    return blob_unpack(buf, pack.slot, pack.valid, use_pallas=use_pallas)
