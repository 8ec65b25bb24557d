"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.blob_codec.kernel import (compress_pack_fused_pallas,
                                             unpack_decompress_fused_pallas)
from repro.kernels.blob_codec.ops import (compress_pack_fused,
                                          unpack_decompress_fused)
from repro.kernels.blob_codec.ref import (compress_pack_ref,
                                          unpack_decompress_ref)
from repro.kernels.blob_codec.host import compress_pack_fused_host
from repro.kernels.blob_pack.host import (blob_pack_fused_host,
                                          sorted_order_np)
from repro.kernels.blob_pack.kernel import SWEEP_ROW_TILES, blob_pack_pallas
from repro.kernels.blob_pack.ops import blob_pack_fused, pack_from_keys
from repro.kernels.blob_pack.ref import blob_pack_ref
from repro.kernels.blob_unpack.kernel import blob_unpack_pallas
from repro.kernels.blob_unpack.ops import unpack_from_keys
from repro.kernels.blob_unpack.ref import blob_unpack_ref
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import flash_ref
from repro.kernels.ssd_scan.ops import ssd_scan_op
from repro.models.ssm import ssd_reference
from repro.shuffle.binning import bin_pack, sorted_order


# --- blob_pack ------------------------------------------------------------

@pytest.mark.parametrize("T,d,bins,cap,dtype", [
    (64, 32, 8, 16, jnp.float32),
    (100, 16, 4, 8, jnp.float32),       # drops (cap < demand)
    (64, 128, 8, 16, jnp.bfloat16),
    (7, 8, 3, 4, jnp.float32),          # tiny / ragged
    (128, 64, 16, 8, jnp.int32),        # integer payload (metadata)
])
def test_blob_pack_matches_ref(T, d, bins, cap, dtype):
    key = jax.random.key(0)
    if jnp.issubdtype(dtype, jnp.integer):
        x = jax.random.randint(key, (T, d), 0, 100).astype(dtype)
    else:
        x = jax.random.normal(key, (T, d)).astype(dtype)
    keys = jax.random.randint(jax.random.key(1), (T,), 0, bins)
    order = jnp.argsort(keys, stable=True).astype(jnp.int32)
    counts = jnp.bincount(keys, length=bins).astype(jnp.int32)
    starts = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    ref = blob_pack_ref(x, order, starts, counts, capacity=cap)
    out = blob_pack_pallas(x, order, starts, counts, capacity=cap,
                           interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_pack_from_keys_consistent_with_binning():
    x = jax.random.normal(jax.random.key(2), (50, 8))
    keys = jax.random.randint(jax.random.key(3), (50,), 0, 4)
    buf, (order, starts, counts) = pack_from_keys(
        x, keys, num_bins=4, capacity=32, use_pallas=True)
    pack = bin_pack(keys, 4, 32)
    from repro.shuffle.binning import scatter_to_bins
    expect = scatter_to_bins(x, pack, 4, 32)
    np.testing.assert_allclose(np.asarray(buf), np.asarray(expect))


# --- blob_unpack ------------------------------------------------------------

@pytest.mark.parametrize("U,bins,cap,d,dtype", [
    (64, 8, 16, 32, jnp.float32),
    (33, 4, 8, 16, jnp.bfloat16),
    (8, 2, 4, 8, jnp.float32),
])
def test_blob_unpack_matches_ref(U, bins, cap, d, dtype):
    buf = jax.random.normal(jax.random.key(4), (bins, cap, d)).astype(dtype)
    slot = jax.random.randint(jax.random.key(5), (U,), 0, bins * cap)
    valid = jax.random.bernoulli(jax.random.key(6), 0.8, (U,))
    ref = blob_unpack_ref(buf, slot, valid)
    out = blob_unpack_pallas(buf, slot, valid, interpret=True)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(ref, np.float32))


def test_pack_unpack_roundtrip():
    """Kernel-level Batcher→Debatcher roundtrip (no drops)."""
    x = jax.random.normal(jax.random.key(7), (40, 16))
    keys = jax.random.randint(jax.random.key(8), (40,), 0, 4)
    pack = bin_pack(keys, 4, 64)
    order = jnp.argsort(keys, stable=True).astype(jnp.int32)
    counts = pack.counts
    starts = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    buf = blob_pack_pallas(x, order, starts, counts, capacity=64,
                           interpret=True)
    back = blob_unpack_pallas(buf, pack.slot, pack.valid, interpret=True)
    np.testing.assert_allclose(np.asarray(back), np.asarray(x), atol=1e-6)


# --- fused single-pass kernels ----------------------------------------------

@pytest.mark.parametrize("T,d,bins,cap,dtype", [
    (64, 32, 8, 16, jnp.float32),
    (100, 16, 4, 8, jnp.float32),       # drops (cap < demand)
    (64, 128, 8, 16, jnp.bfloat16),
    (7, 8, 3, 4, jnp.float32),          # tiny / ragged
    (50, 8, 4, 200, jnp.float32),       # capacity > row tile, uneven
    (128, 64, 16, 8, jnp.int32),        # integer payload (metadata)
])
def test_blob_pack_fused_matches_ref(T, d, bins, cap, dtype):
    key = jax.random.key(0)
    if jnp.issubdtype(dtype, jnp.integer):
        x = jax.random.randint(key, (T, d), 0, 100).astype(dtype)
    else:
        x = jax.random.normal(key, (T, d)).astype(dtype)
    keys = jax.random.randint(jax.random.key(1), (T,), 0, bins)
    order, starts, counts = sorted_order(keys, bins)
    ref = blob_pack_ref(x, order, starts, counts, capacity=cap)
    out = blob_pack_pallas(x, order, starts, counts, capacity=cap,
                           interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # the jit-fused front half (sort/rank + gather in one pass) agrees too
    fused, (o2, s2, c2) = blob_pack_fused(x, keys, num_bins=bins,
                                          capacity=cap, use_pallas=True)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(o2), np.asarray(order))
    np.testing.assert_array_equal(np.asarray(c2), np.asarray(counts))


@pytest.mark.parametrize("U,bins,cap,d", [
    (64, 8, 16, 32),
    (33, 4, 8, 16),       # U not a multiple of the tile
    (8, 2, 4, 8),
    (300, 4, 128, 8),     # U > row tile
])
def test_blob_unpack_fused_matches_ref(U, bins, cap, d):
    buf = jax.random.normal(jax.random.key(4), (bins, cap, d))
    slot = jax.random.randint(jax.random.key(5), (U,), 0, bins * cap)
    valid = jax.random.bernoulli(jax.random.key(6), 0.8, (U,))
    ref = blob_unpack_ref(buf, slot, valid)
    out = blob_unpack_pallas(buf, slot, valid, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_fused_pack_unpack_roundtrip():
    """Fused-kernel Batcher→Debatcher roundtrip (no drops)."""
    x = jax.random.normal(jax.random.key(7), (40, 16))
    keys = jax.random.randint(jax.random.key(8), (40,), 0, 4)
    buf, _ = blob_pack_fused(x, keys, num_bins=4, capacity=64,
                             use_pallas=True)
    back = unpack_from_keys(buf, keys, num_bins=4, capacity=64,
                            use_pallas=True)
    np.testing.assert_allclose(np.asarray(back), np.asarray(x), atol=1e-6)


# --- blob_codec (fused compress+pack) ----------------------------------------

@pytest.mark.parametrize("T,d,bins,cap", [
    (64, 32, 8, 16),
    (100, 16, 4, 8),       # drops (cap < demand)
    (7, 8, 3, 4),          # tiny / ragged
    (50, 8, 4, 200),       # capacity > row tile, uneven
])
def test_compress_pack_fused_matches_ref(T, d, bins, cap):
    x = jax.random.normal(jax.random.key(11), (T, d))
    keys = jax.random.randint(jax.random.key(12), (T,), 0, bins)
    order, starts, counts = sorted_order(keys, bins)
    q_ref, s_ref = compress_pack_ref(x, order, starts, counts, capacity=cap)
    q, s = compress_pack_fused_pallas(x, order, starts, counts,
                                      capacity=cap, interpret=True)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q_ref))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s_ref))
    # jit-fused front half (sort/rank + gather+quantize) agrees too
    (qf, sf), (o2, _, c2) = compress_pack_fused(
        x, keys, num_bins=bins, capacity=cap, use_pallas=True)
    np.testing.assert_array_equal(np.asarray(qf), np.asarray(q_ref))
    np.testing.assert_array_equal(np.asarray(sf), np.asarray(s_ref))
    np.testing.assert_array_equal(np.asarray(o2), np.asarray(order))
    np.testing.assert_array_equal(np.asarray(c2), np.asarray(counts))


@pytest.mark.parametrize("U,bins,cap,d", [
    (64, 8, 16, 32),
    (33, 4, 8, 16),        # U not a multiple of the tile
    (300, 4, 128, 8),      # U > row tile
])
def test_unpack_decompress_fused_matches_ref(U, bins, cap, d):
    q = jax.random.randint(jax.random.key(13), (bins, cap, d),
                           -127, 128).astype(jnp.int8)
    scales = jnp.abs(jax.random.normal(jax.random.key(14),
                                       (bins, cap))) + 1e-3
    slot = jax.random.randint(jax.random.key(15), (U,), 0, bins * cap)
    valid = jax.random.bernoulli(jax.random.key(16), 0.8, (U,))
    ref = unpack_decompress_ref(q, scales, slot, valid)
    out = unpack_decompress_fused_pallas(q, scales, slot, valid,
                                         interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_compress_pack_roundtrip_within_int8_error():
    """Fused Batcher→Debatcher roundtrip through the compressed layout:
    lossy, but bounded by the per-row quantization step (absmax/127)."""
    x = jax.random.normal(jax.random.key(17), (40, 16))
    keys = jax.random.randint(jax.random.key(18), (40,), 0, 4)
    (q, s), _ = compress_pack_fused(x, keys, num_bins=4, capacity=64,
                                    use_pallas=True)
    back = unpack_decompress_fused(q, s, keys, num_bins=4, capacity=64,
                                   use_pallas=True)
    step = np.abs(np.asarray(x)).max(axis=-1, keepdims=True) / 127.0
    np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                               atol=float(step.max()) * 0.51 + 1e-7)


# --- tile-geometry edge cases -----------------------------------------------

#: geometries that stress the grid/tile math: capacity below the tile,
#: capacity not a multiple of the tile, single-lane features (d == 1),
#: and bins the keys never hit (empty bins must stay zero / padding)
EDGE_GEOMS = [
    pytest.param(64, 16, 4, 3, 128, id="capacity-lt-row-tile"),
    pytest.param(64, 16, 4, 37, 8, id="capacity-not-tile-multiple"),
    pytest.param(100, 1, 8, 32, 16, id="d-eq-1"),
    pytest.param(50, 8, 16, 8, 8, id="empty-bins"),
    pytest.param(3, 1, 5, 7, 256, id="tiny-everything"),
]


def _edge_inputs(T, bins, seed=21):
    # draw keys from the lower half of the bin range so the upper half
    # is guaranteed empty (covers the empty-bins contract everywhere)
    hi = max(1, bins // 2)
    return jax.random.randint(jax.random.key(seed), (T,), 0, hi)


@pytest.mark.parametrize("T,d,bins,cap,row_tile", EDGE_GEOMS)
def test_pack_tile_geometry_edges(T, d, bins, cap, row_tile):
    x = jax.random.normal(jax.random.key(20), (T, d))
    keys = _edge_inputs(T, bins)
    order, starts, counts = sorted_order(keys, bins)
    ref = blob_pack_ref(x, order, starts, counts, capacity=cap)
    for rt in (None, row_tile):
        out = blob_pack_pallas(x, order, starts, counts, capacity=cap,
                               interpret=True, row_tile=rt)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # bins beyond the key range really are empty
    assert not np.asarray(ref)[bins // 2 + 1:].any()


@pytest.mark.parametrize("T,d,bins,cap,row_tile", EDGE_GEOMS)
def test_codec_tile_geometry_edges(T, d, bins, cap, row_tile):
    x = jax.random.normal(jax.random.key(22), (T, d))
    keys = _edge_inputs(T, bins)
    order, starts, counts = sorted_order(keys, bins)
    q_ref, s_ref = compress_pack_ref(x, order, starts, counts, capacity=cap)
    for rt in (None, row_tile):
        q, s = compress_pack_fused_pallas(x, order, starts, counts,
                                          capacity=cap, interpret=True,
                                          row_tile=rt)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(q_ref))
        np.testing.assert_array_equal(np.asarray(s), np.asarray(s_ref))
    # empty bins carry the quantizer's padding identity (q=0, scale=1)
    assert not np.asarray(q_ref)[bins // 2 + 1:].any()
    np.testing.assert_array_equal(np.asarray(s_ref)[bins // 2 + 1:], 1.0)


def test_row_tile_sweep_parity():
    """Every candidate in the device benchmark's row-tile sweep produces
    bit-identical output — tile geometry is a pure perf knob."""
    T, d, bins, cap = 200, 24, 8, 48
    x = jax.random.normal(jax.random.key(23), (T, d))
    keys = jax.random.randint(jax.random.key(24), (T,), 0, bins)
    order, starts, counts = sorted_order(keys, bins)
    ref = blob_pack_ref(x, order, starts, counts, capacity=cap)
    for rt in SWEEP_ROW_TILES:
        out = blob_pack_pallas(x, order, starts, counts, capacity=cap,
                               interpret=True, row_tile=rt)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# --- host fast paths ---------------------------------------------------------

@pytest.mark.parametrize("dtype", [np.float32, np.int32, "bfloat16"])
def test_blob_pack_host_bit_parity(dtype):
    """Host numpy pack is bit-exact with the oracle, both into a fresh
    output and into a dirty reused arena (padding must be re-zeroed)."""
    if dtype == "bfloat16":
        dtype = np.asarray(jnp.zeros(0, jnp.bfloat16)).dtype
    rng = np.random.default_rng(5)
    T, d, bins, cap = 150, 12, 8, 24
    x = rng.standard_normal((T, d)).astype(np.float32).astype(dtype)
    keys = rng.integers(0, bins, T).astype(np.int32)
    order, starts, counts = sorted_order(jnp.asarray(keys), bins)
    ref = np.asarray(blob_pack_ref(jnp.asarray(x), order, starts, counts,
                                   capacity=cap))
    out, (o, s, c) = blob_pack_fused_host(x, keys, num_bins=bins,
                                          capacity=cap)
    np.testing.assert_array_equal(out.view(np.uint8), ref.view(np.uint8))
    np.testing.assert_array_equal(o, np.asarray(order))
    np.testing.assert_array_equal(s, np.asarray(starts))
    np.testing.assert_array_equal(c, np.asarray(counts))
    arena = np.ones((bins, cap, d), dtype)       # dirty arena
    out2, _ = blob_pack_fused_host(x, keys, num_bins=bins, capacity=cap,
                                   out=arena)
    assert out2 is arena
    np.testing.assert_array_equal(out2.view(np.uint8), ref.view(np.uint8))


def test_compress_pack_host_bit_parity():
    rng = np.random.default_rng(6)
    T, d, bins, cap = 150, 12, 8, 24
    x = rng.standard_normal((T, d)).astype(np.float32)
    keys = rng.integers(0, bins, T).astype(np.int32)
    order, starts, counts = sorted_order(jnp.asarray(keys), bins)
    q_ref, s_ref = compress_pack_ref(jnp.asarray(x), order, starts, counts,
                                     capacity=cap)
    (q, s), _ = compress_pack_fused_host(x, keys, num_bins=bins,
                                         capacity=cap)
    np.testing.assert_array_equal(q, np.asarray(q_ref))
    np.testing.assert_array_equal(s, np.asarray(s_ref))
    arenas = (np.full((bins, cap, d), 3, np.int8),
              np.full((bins, cap), 9.0, np.float32))
    (q2, s2), _ = compress_pack_fused_host(x, keys, num_bins=bins,
                                           capacity=cap, out=arenas)
    assert q2 is arenas[0] and s2 is arenas[1]
    np.testing.assert_array_equal(q2, np.asarray(q_ref))
    np.testing.assert_array_equal(s2, np.asarray(s_ref))


def test_sorted_order_np_matches_jnp():
    rng = np.random.default_rng(7)
    keys = rng.integers(0, 11, 500).astype(np.int32)
    o, s, c = sorted_order_np(keys, 16)          # some bins empty
    oj, sj, cj = sorted_order(jnp.asarray(keys), 16)
    np.testing.assert_array_equal(o, np.asarray(oj))
    np.testing.assert_array_equal(s, np.asarray(sj))
    np.testing.assert_array_equal(c, np.asarray(cj))


# --- flash attention ---------------------------------------------------------

@pytest.mark.parametrize("B,S,H,KVH,D,causal,dtype", [
    (2, 256, 4, 4, 64, True, jnp.float32),
    (1, 256, 4, 2, 64, True, jnp.float32),    # GQA
    (1, 128, 2, 1, 32, True, jnp.float32),    # MQA
    (2, 256, 4, 4, 64, False, jnp.float32),   # encoder
    (1, 200, 2, 2, 64, True, jnp.float32),    # ragged seq (padding)
    (1, 256, 2, 2, 64, True, jnp.bfloat16),
])
def test_flash_kernel_matches_dense(B, S, H, KVH, D, causal, dtype):
    ks = jax.random.split(jax.random.key(9), 3)
    q = jax.random.normal(ks[0], (B, S, H, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, KVH, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, KVH, D)).astype(dtype)
    ref = flash_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                    v.astype(jnp.float32), causal=causal)
    out = flash_attention_pallas(q, k, v, causal=causal, q_tile=64,
                                 kv_tile=64, interpret=True)
    atol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


# --- ssd_scan ----------------------------------------------------------------

@pytest.mark.parametrize("b,S,H,P,G,N,chunk", [
    (1, 64, 2, 8, 1, 16, 16),
    (2, 60, 4, 8, 2, 16, 16),    # ragged + groups
    (1, 128, 4, 16, 1, 32, 64),
])
def test_ssd_kernel_matches_reference(b, S, H, P, G, N, chunk):
    ks = jax.random.split(jax.random.key(10), 5)
    x = jax.random.normal(ks[0], (b, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, H)) - 1.0)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    B = jax.random.normal(ks[3], (b, S, G, N))
    C = jax.random.normal(ks[4], (b, S, G, N))
    y_ref, st_ref = ssd_reference(x, dt, A, B, C)
    y, st = ssd_scan_op(x, dt, A, B, C, chunk=chunk, use_pallas=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                               atol=1e-4, rtol=1e-4)
