"""Per-device expert dispatch/combine — flat baseline vs blob-hierarchical.

These functions run *inside* ``shard_map`` (see ``repro.shuffle.api``) and
implement two routings of the same logical token→expert repartitioning:

``flat``  — the "native Kafka Streams shuffling" analogue: one all-to-all over
            the full EP domain. Every (source, destination-device) pair gets
            its own worst-case-sized lane, so slack capacity (and on a
            multi-pod mesh, every fine-grained message) crosses the expensive
            inter-pod link individually.

``blob``  — the BlobShuffle analogue: two-stage hierarchical exchange.
            Stage 1 bins units by destination *model-rank* and exchanges them
            intra-pod (cheap ICI) so that each device aggregates one
            contiguous **blob** per destination pod. Stage 2 moves those
            pooled blobs across the ``pod`` axis (expensive DCN) exactly once
            — the "GET once per AZ" invariant — with capacity pooled over all
            intra-pod sources (statistical multiplexing → smaller slack), and
            optionally int8-compressed (the cheap-tier/expensive-tier split
            of the paper).

Both modes pre-exchange compact **notification** metadata (per-destination
counts) so overflow/load diagnostics are exact.
"""

from __future__ import annotations

import math
from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp

from repro.shuffle.binning import bin_pack, dropped_units, from_bins, to_bins
from repro.shuffle import compression


class DispatchDiagnostics(NamedTuple):
    dropped: jax.Array          # units dropped to capacity overflow (global)
    expert_load: jax.Array      # (E,) tokens routed per expert (global)
    dcn_bytes: jax.Array        # payload bytes that crossed the pod axis


def _cap(expected: float, factor: float, align: int = 8) -> int:
    c = int(math.ceil(expected * factor))
    return max(align, -(-c // align) * align)


def pooled_capacity_factor(base: float, pool: int) -> float:
    """Slack needed shrinks ~1/sqrt(pool) when pooling independent demand —
    the statistical-multiplexing win of blob aggregation (paper §4 batching)."""
    return 1.0 + (base - 1.0) / math.sqrt(max(pool, 1))


def _a2a(x: jax.Array, axis_names) -> jax.Array:
    """Tiled all-to-all over (possibly multiple) mesh axes; x: (ep, C, ...)."""
    return jax.lax.all_to_all(x, axis_names, split_axis=0, concat_axis=0,
                              tiled=False)


# ---------------------------------------------------------------------------
# Flat (baseline) dispatch
# ---------------------------------------------------------------------------

def flat_dispatch_combine(
    x: jax.Array,                 # (T_loc, d) local tokens
    sel_idx: jax.Array,           # (T_loc, k) selected global expert ids
    sel_w: jax.Array,             # (T_loc, k) combine weights
    expert_fn: Callable,          # (E_loc, C, d) -> (E_loc, C, d_out)
    *,
    num_experts: int,
    ep_axes: Sequence[str],       # axes forming the EP domain, e.g. ("pod","model")
    capacity_factor: float,
    d_out: int,
):
    """One-stage all-to-all over the whole EP domain."""
    T_loc, d = x.shape
    k = sel_idx.shape[1]
    ep = _axes_size(ep_axes)
    E_loc = num_experts // ep
    U = T_loc * k

    # Per-(source, expert) lane capacity — fine-grained, worst-case slack.
    cap = _cap(U / num_experts, capacity_factor)
    pack = bin_pack(sel_idx.reshape(-1), num_experts, cap)

    send = to_bins(x, pack)
    send = send.reshape(ep, E_loc * cap, d)
    recv = _a2a(send, tuple(ep_axes))                       # (ep, E_loc*cap, d)
    recv = recv.reshape(ep, E_loc, cap, d).transpose(1, 0, 2, 3) \
        .reshape(E_loc, ep * cap, d)

    out = expert_fn(recv)                                   # (E_loc, ep*cap, d_out)

    back = out.reshape(E_loc, ep, cap, d_out).transpose(1, 0, 2, 3) \
        .reshape(ep, E_loc * cap, d_out)
    back = _a2a(back, tuple(ep_axes))
    back = back.reshape(num_experts, cap, d_out)
    y = from_bins(back, pack, sel_w)                        # (T_loc, d_out)

    # notifications → diagnostics
    counts_global = jax.lax.psum(pack.counts, tuple(ep_axes))
    dropped = jax.lax.psum(dropped_units(pack, cap), tuple(ep_axes))
    dcn = _flat_dcn_bytes(send, ep_axes)
    return y.astype(x.dtype), DispatchDiagnostics(dropped, counts_global, dcn)


def _flat_dcn_bytes(send: jax.Array, ep_axes: Sequence[str]) -> jax.Array:
    """Bytes of the flat a2a payload that cross the pod boundary."""
    if "pod" not in ep_axes:
        return jnp.zeros((), jnp.float32)
    npods = jax.lax.psum(1, "pod")
    frac_cross = (npods - 1) / npods
    per_dev = send.size * jnp.dtype(send.dtype).itemsize * frac_cross
    return jax.lax.psum(jnp.float32(per_dev), tuple(ep_axes))


# ---------------------------------------------------------------------------
# Blob (hierarchical) dispatch — the paper's technique
# ---------------------------------------------------------------------------

def blob_dispatch_combine(
    x: jax.Array,
    sel_idx: jax.Array,
    sel_w: jax.Array,
    expert_fn: Callable,
    *,
    num_experts: int,
    pod_axis: str,                # outer (expensive) axis
    inner_axes: Sequence[str],    # intra-pod EP axes, e.g. ("model",)
    capacity_factor: float,
    d_out: int,
    compress_dcn: bool = False,   # int8-compress the inter-pod leg
):
    """Two-stage hierarchical dispatch: intra-pod aggregation → pooled
    inter-pod blob transfer → local expert execution. See module docstring."""
    T_loc, d = x.shape
    k = sel_idx.shape[1]
    P = _axes_size([pod_axis])
    M = _axes_size(inner_axes)
    ep = P * M
    E_loc = num_experts // ep
    U = T_loc * k

    unit_expert = sel_idx.reshape(-1)

    # expert e lives at (pod p, model m, local l):
    #   p = e // (M*E_loc);  m = (e // E_loc) % M;  l = e % E_loc
    dest_m = (unit_expert // E_loc) % M

    # ---- Stage 1: intra-pod exchange over the model axis (cheap ICI).
    cap1 = _cap(U / M, capacity_factor)
    pack1 = bin_pack(dest_m, M, cap1)
    payload1 = to_bins(x, pack1)
    meta1 = to_bins(unit_expert + 1, pack1)       # 0 == empty
    recv1 = _a2a(payload1, tuple(inner_axes))     # (M, cap1, d)
    rmeta1 = _a2a(meta1, tuple(inner_axes))       # (M, cap1)

    # This device now aggregates, per destination pod, one contiguous blob
    # of everything its pod wants to send to its model-rank peers there.
    u1_expert = rmeta1.reshape(-1) - 1            # (-1 == empty slot)
    u1_valid = u1_expert >= 0
    u1_x = recv1.reshape(M * cap1, d)

    dest_p = jnp.where(u1_valid, u1_expert // (M * E_loc), P)  # P == drop bin
    # ---- Stage 2: pooled blob capacity — slack shrinks by ~1/sqrt(M)
    # because demand from M sources is multiplexed into one blob.
    # Expected arrivals at this device: M sources × U/M units = U; per pod U/P.
    cf2 = pooled_capacity_factor(capacity_factor, M)
    cap2 = _cap(U / P, cf2)
    pack2 = bin_pack(dest_p.astype(jnp.int32), P + 1, cap2)
    payload2 = to_bins(u1_x, pack2)[:P]
    meta2 = to_bins(u1_expert + 1, pack2)[:P]

    if compress_dcn:
        q, scale = compression.int8_quantize(payload2)
        q = _a2a(q, (pod_axis,))
        scale = _a2a(scale, (pod_axis,))
        recv2 = compression.int8_dequantize(q, scale, payload2.dtype)
        dcn_payload_bytes = payload2.size * 1 + scale.size * 4
    else:
        recv2 = _a2a(payload2, (pod_axis,))
        dcn_payload_bytes = payload2.size * jnp.dtype(payload2.dtype).itemsize
    rmeta2 = _a2a(meta2, (pod_axis,))

    # ---- Local expert execution ("Debatcher" + processing)
    u2_expert = rmeta2.reshape(-1) - 1
    u2_valid = u2_expert >= 0
    u2_x = recv2.reshape(P * cap2, d)
    local_e = jnp.where(u2_valid, u2_expert % E_loc, E_loc)
    # Expected per local expert: U·P·M system units / E experts = U/E_loc.
    cf3 = pooled_capacity_factor(capacity_factor, M * P)
    cap_e = _cap(U / E_loc, cf3)
    pack3 = bin_pack(local_e.astype(jnp.int32), E_loc + 1, cap_e)
    ebuf = to_bins(u2_x, pack3)[:E_loc]

    eout = expert_fn(ebuf)                        # (E_loc, cap_e, d_out)

    # ---- Reverse path (slots are symmetric; results ride the same lanes)
    eout_full = jnp.concatenate(
        [eout, jnp.zeros((1, cap_e, d_out), eout.dtype)], axis=0)
    y2 = from_bins(eout_full, pack3)              # (P*cap2, d_out)
    back2 = y2.reshape(P, cap2, d_out)
    back2 = _a2a(back2, (pod_axis,))
    y1_full = jnp.concatenate(
        [back2, jnp.zeros((1, cap2, d_out), back2.dtype)], axis=0)
    y1 = from_bins(y1_full, pack2)                # (M*cap1, d_out)
    back1 = y1.reshape(M, cap1, d_out)
    back1 = _a2a(back1, tuple(inner_axes))
    y = from_bins(back1, pack1, sel_w)            # (T_loc, d_out)

    all_axes = tuple(inner_axes) + (pod_axis,)
    counts_global = jax.lax.psum(
        jnp.bincount(unit_expert, length=num_experts).astype(jnp.int32),
        all_axes)
    dropped = jax.lax.psum(
        dropped_units(pack1, cap1)
        + jnp.sum(jnp.maximum(pack2.counts[:P] - cap2, 0))
        + jnp.sum(jnp.maximum(pack3.counts[:E_loc] - cap_e, 0)), all_axes)
    frac_cross = (P - 1) / P
    dcn = jax.lax.psum(jnp.float32(dcn_payload_bytes * frac_cross), all_axes)
    return y.astype(x.dtype), DispatchDiagnostics(dropped, counts_global, dcn)


def _axes_size(axis_names) -> int:
    size = 1
    for a in axis_names:
        size *= jax.lax.psum(1, a)
    return size
