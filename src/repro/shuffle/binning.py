"""Token binning ("Batcher") primitives shared by all shuffle modes.

``bin_pack`` is the tensor-level analogue of the paper's Batcher: units
(token, expert-slot) are grouped by destination into fixed-capacity,
contiguous bins — the "blobs". ``counts`` is the compact notification
metadata (the analogue of the batch-id + byte-range references that flow
through Kafka in the paper).

The slot map is a partial permutation: each valid unit owns one slot and
each slot holds at most one unit. ``bin_pack`` gives it both ways
(``slot``: unit -> slot; ``src``: slot -> unit), so ``to_bins`` and
``from_bins`` move rows with gathers only, in the forward and in the
backward (``jax.custom_vjp``): no row scatter, no scatter-add, no dump
row.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.obs.scopes import scope


class Packing(NamedTuple):
    slot: jax.Array     # (U,) int32 — flat slot in the (bins*capacity) buffer
    valid: jax.Array    # (U,) bool — False for capacity-overflow (dropped)
    counts: jax.Array   # (bins,) int32 — notification metadata (true demand)
    src: jax.Array      # (bins, capacity) int32 — unit in each slot; U if empty


def sorted_order(keys: jax.Array, num_bins: int
                 ) -> "tuple[jax.Array, jax.Array, jax.Array]":
    """Stable argsort-by-destination description: (order, starts, counts).

    ``order`` maps sorted position -> unit index; ``starts[b]`` is bin
    b's first position within ``order``; ``counts`` is the true demand.
    This is the shared front half of ``bin_pack`` and the fused pack
    kernels (``repro.kernels.blob_pack``)."""
    order = jnp.argsort(keys, stable=True).astype(jnp.int32)
    counts = jnp.bincount(keys, length=num_bins).astype(jnp.int32)
    starts = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    return order, starts, counts


def bin_pack(keys: jax.Array, num_bins: int, capacity: int) -> Packing:
    """Assign each unit a slot = key*capacity + rank-within-key.

    Ranks are assigned in stable sorted order, so records for a given
    destination appear contiguously — matching the paper's blob layout
    ("records for a given partition appear sequentially within the batch").
    A dropped unit's ``slot`` is its bin's last one. ``src`` is the
    inverse: slot ``b*capacity + r`` holds unit ``order[starts[b] + r]``
    while ``r < counts[b]``.
    """
    U = keys.shape[0]
    order, starts, counts = sorted_order(keys, num_bins)
    sorted_keys = keys[order]
    rank_sorted = jnp.arange(U, dtype=jnp.int32) - starts[sorted_keys]
    rank = jnp.zeros(U, jnp.int32).at[order].set(rank_sorted)
    valid = rank < capacity
    slot = keys.astype(jnp.int32) * capacity + jnp.minimum(rank, capacity - 1)
    r = jnp.arange(capacity, dtype=jnp.int32)
    held = jnp.minimum(starts[:, None] + r, U - 1)
    src = jnp.where(r < counts[:, None], order[held], U)
    return Packing(slot, valid, counts, src)


def to_bins(rows: jax.Array, pack: Packing) -> jax.Array:
    """rows: (R, ...) -> (bins, capacity, ...), where the pack's
    ``U = R*k`` units are ``k`` per row (unit ``u`` carries row
    ``u // k``). Empty slots hold zeros. Any dtype: integer payloads get
    no cotangent."""
    num_bins, capacity = pack.src.shape
    k = pack.slot.shape[0] // rows.shape[0]
    flat = _to_bins(k, rows, pack.src.reshape(-1), pack.slot, pack.valid)
    return flat.reshape((num_bins, capacity) + rows.shape[1:])


def from_bins(buf: jax.Array, pack: Packing, weights=None) -> jax.Array:
    """Inverse of ``to_bins``: (bins, capacity, ...) -> rows; a dropped
    unit reads zero. Without ``weights``: (U, ...) in ``buf``'s dtype,
    one unit a row. With ``weights`` (R, k): (R, ...) float32, row ``r``
    the weighted sum of units ``r*k .. r*k + k-1``."""
    flat = buf.reshape((-1,) + buf.shape[2:])
    return _from_bins(flat, weights, pack.src.reshape(-1), pack.slot,
                      pack.valid)


def dropped_units(pack: Packing, capacity: int) -> jax.Array:
    """Overflow count derived from the notification metadata."""
    return jnp.sum(jnp.maximum(pack.counts - capacity, 0))


# ---------------------------------------------------------------------------
# The gather pair. Each direction's transpose is the other direction's
# gather, so forward, backward and recompute all read rows by index.
# ---------------------------------------------------------------------------

def _take_rows(rows, idx):
    """``rows[idx]``, where ``idx == len(rows)`` reads a zero row."""
    zero = jnp.zeros((1,) + rows.shape[1:], rows.dtype)
    return jnp.concatenate([rows, zero]).at[idx].get(
        mode="promise_in_bounds")


def _units(flat, slot, valid, k):
    """The rows of ``flat`` at ``slot``, zero where not ``valid``, as
    (k, U/k, ...): unit ``r*k + j`` at ``[j, r]``. Unit-major, a (U/k, k,
    ...) array pads ``k`` to the chip's row tile and is relaid out; with
    ``k`` leading, the gather's (U, ...) output is the same bytes."""
    idx = slot.reshape(-1, k).T
    mask = valid.reshape(-1, k).T.reshape(idx.shape + (1,) * (flat.ndim - 1))
    return jnp.where(mask, flat.at[idx].get(mode="promise_in_bounds"), 0)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
@scope("moe_dispatch")
def _to_bins(k, rows, src, slot, valid):
    return _take_rows(rows, src // k)


def _to_bins_fwd(k, rows, src, slot, valid):
    return _to_bins(k, rows, src, slot, valid), (slot, valid)


@scope("moe_dispatch")
def _to_bins_bwd(k, res, g):
    slot, valid = res
    units = _units(g, slot, valid, k).astype(jnp.float32)
    return jnp.sum(units, axis=0).astype(g.dtype), None, None, None


_to_bins.defvjp(_to_bins_fwd, _to_bins_bwd)


@jax.custom_vjp
@scope("moe_dispatch")
def _from_bins(flat, weights, src, slot, valid):
    if weights is None:
        return _units(flat, slot, valid, 1)[0]
    units = _units(flat, slot, valid, weights.shape[1]).astype(jnp.float32)
    return jnp.einsum("tk,kt...->t...", weights, units)


def _from_bins_fwd(flat, weights, src, slot, valid):
    return (_from_bins(flat, weights, src, slot, valid),
            (flat, weights, src, slot, valid))


@scope("moe_dispatch")
def _from_bins_bwd(res, g):
    flat, weights, src, slot, valid = res
    if weights is None:
        return _take_rows(g, src), None, None, None, None
    k = weights.shape[1]
    units = _units(flat, slot, valid, k).astype(jnp.float32)
    d_weights = jnp.einsum("t...,kt...->tk", g, units)
    # the cotangent of each unit, (k, R+1, ...) with a zero row R past the
    # last: slot s holds unit src[s], and an empty slot's src, U = R*k,
    # reads [0, R]. Gathered from units and not from g's rows: scaled
    # after the gather, the product fuses into the expert FFN's backward,
    # which then reads the gathered rows in float32.
    wk = jnp.pad(weights.T, ((0, 0), (0, 1)))
    wk = wk.reshape(wk.shape + (1,) * (flat.ndim - 1))
    gk = jnp.pad(g, ((0, 1),) + ((0, 0),) * (g.ndim - 1))
    d_units = (wk * gk[None]).astype(flat.dtype)
    d_flat = d_units.at[src % k, src // k].get(mode="promise_in_bounds")
    return d_flat, d_weights.astype(weights.dtype), None, None, None


_from_bins.defvjp(_from_bins_fwd, _from_bins_bwd)
