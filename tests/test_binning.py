"""bin_pack / to_bins / from_bins properties (the Batcher-analogue core)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.shuffle.binning import bin_pack, dropped_units, from_bins, to_bins


@settings(deadline=None, max_examples=30)
@given(st.lists(st.integers(0, 7), min_size=1, max_size=64),
       st.integers(1, 12))
def test_pack_scatter_gather_roundtrip(keys, capacity):
    keys = jnp.asarray(keys, jnp.int32)
    U = keys.shape[0]
    vals = jnp.arange(U, dtype=jnp.float32)[:, None] + 1.0
    pack = bin_pack(keys, 8, capacity)
    buf = to_bins(vals, pack)
    back = from_bins(buf, pack)
    # valid units roundtrip exactly; dropped units read zero
    np.testing.assert_array_equal(
        np.asarray(back[pack.valid]), np.asarray(vals[pack.valid]))
    assert np.all(np.asarray(back[~pack.valid]) == 0)
    # counts == true demand
    np.testing.assert_array_equal(
        np.asarray(pack.counts), np.bincount(np.asarray(keys), minlength=8))
    # drops = sum of overflow
    assert int(dropped_units(pack, capacity)) == int(
        np.maximum(np.asarray(pack.counts) - capacity, 0).sum())


@settings(deadline=None, max_examples=20)
@given(st.lists(st.integers(0, 3), min_size=1, max_size=40))
def test_bins_are_contiguous_and_ordered(keys):
    """Valid slots for bin k lie in [k·cap, k·cap + count_k) — the blob
    layout invariant (records per partition are contiguous)."""
    keys = jnp.asarray(keys, jnp.int32)
    cap = 64  # no drops
    pack = bin_pack(keys, 4, cap)
    assert bool(jnp.all(pack.valid))
    slots = np.asarray(pack.slot)
    counts = np.asarray(pack.counts)
    for k in range(4):
        sel = np.asarray(keys) == k
        got = np.sort(slots[sel])
        expect = np.arange(k * cap, k * cap + counts[k])
        np.testing.assert_array_equal(got, expect)


def test_no_collisions_among_valid():
    keys = jnp.asarray([0, 0, 0, 1, 1, 2] * 10, jnp.int32)
    pack = bin_pack(keys, 3, 8)
    slots = np.asarray(pack.slot)[np.asarray(pack.valid)]
    assert len(np.unique(slots)) == len(slots)


def test_scatter_gather_multidim_payload():
    keys = jnp.asarray([2, 0, 1, 2, 0], jnp.int32)
    vals = jnp.arange(5 * 3, dtype=jnp.bfloat16).reshape(5, 3)
    pack = bin_pack(keys, 3, 4)
    buf = to_bins(vals, pack)
    assert buf.shape == (3, 4, 3)
    back = from_bins(buf, pack)
    np.testing.assert_array_equal(np.asarray(back, np.float32),
                                  np.asarray(vals, np.float32))


# --- the gather pair against the scatter formulation ------------------------

def _oracle_to_bins(rows, pack, num_bins, capacity, k):
    """Each unit's row set into its slot; dropped units go to a dump row."""
    total = num_bins * capacity
    slot = jnp.where(pack.valid, pack.slot, total)
    buf = jnp.zeros((total + 1,) + rows.shape[1:], rows.dtype)
    buf = buf.at[slot].set(jnp.repeat(rows, k, axis=0))
    return buf[:total].reshape((num_bins, capacity) + rows.shape[1:])


def _oracle_units(buf, pack):
    vals = buf.reshape((-1,) + buf.shape[2:])[pack.slot]
    return jnp.where(pack.valid[:, None], vals, 0)


def _oracle_combine(buf, pack, weights):
    units = _oracle_units(buf, pack).reshape(weights.shape + buf.shape[2:])
    return jnp.einsum("tk,tkd->td", weights, units)


def _assert_close(got, want):
    """Equal to 1e-6 of the largest reference value."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want), initial=0) <= \
        1e-6 * np.max(np.abs(want), initial=0)


@pytest.mark.parametrize("top_k", [1, 2, 6])
@pytest.mark.parametrize("layout", ["spread", "one_bin"])
@settings(deadline=None, max_examples=15)
@given(data=st.data())
def test_gather_pair_matches_scatter_oracle(layout, top_k, data):
    """to_bins / from_bins equal the scatter formulation differentiated by
    JAX: the same values in the same slots, and f32 cotangents for rows,
    bins and combine weights, with drops and empty bins."""
    # few shapes, so that examples share compiled programs; the keys vary
    rows_n, num_bins = 5, 4
    capacity = data.draw(st.sampled_from([1, 4, 32]), label="capacity")
    U = rows_n * top_k
    if layout == "one_bin":
        keys = [data.draw(st.integers(0, num_bins - 1), label="bin")] * U
    else:
        keys = data.draw(st.lists(st.integers(0, num_bins - 1), min_size=U,
                                  max_size=U), label="keys")
    seed = data.draw(st.integers(0, 2**31 - 1), label="seed")
    r = jax.random.split(jax.random.key(seed), 5)
    d = 3
    rows = jax.random.normal(r[0], (rows_n, d))
    buf = jax.random.normal(r[1], (num_bins, capacity, d))
    w = jax.random.uniform(r[2], (rows_n, top_k))
    ct_bins = jax.random.normal(r[3], (num_bins, capacity, d))
    ct_rows = jax.random.normal(r[4], (rows_n, d))
    pack = bin_pack(jnp.asarray(keys, jnp.int32), num_bins, capacity)

    bins, vjp = jax.vjp(lambda x: to_bins(x, pack), rows)
    want, vjp_want = jax.vjp(
        lambda x: _oracle_to_bins(x, pack, num_bins, capacity, top_k), rows)
    np.testing.assert_array_equal(np.asarray(bins), np.asarray(want))
    _assert_close(vjp(ct_bins)[0], vjp_want(ct_bins)[0])

    y, vjp = jax.vjp(lambda b, w: from_bins(b, pack, w), buf, w)
    want, vjp_want = jax.vjp(lambda b, w: _oracle_combine(b, pack, w), buf, w)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want))
    for got, ref in zip(vjp(ct_rows), vjp_want(ct_rows)):
        _assert_close(got, ref)

    ct_units = jnp.repeat(ct_rows, top_k, axis=0)
    units, vjp = jax.vjp(lambda b: from_bins(b, pack), buf)
    want, vjp_want = jax.vjp(lambda b: _oracle_units(b, pack), buf)
    np.testing.assert_array_equal(np.asarray(units), np.asarray(want))
    _assert_close(vjp(ct_units)[0], vjp_want(ct_units)[0])


def test_moe_grad_moves_rows_by_gather_only():
    """The compiled gradient of ``dense_moe_ffn`` scatters no row of the
    model width (only (U,) index scalars), and its row gathers, the
    backward's among them, fall in the ``moe_dispatch`` layer."""
    import re

    from repro.obs.scopes import layer_of_ops
    from repro.shuffle.api import dense_moe_ffn

    T, d, E, de, k = 16, 40, 4, 24, 2      # d unlike every other size
    r = jax.random.split(jax.random.key(0), 5)
    args = (jax.random.normal(r[0], (T, d)), jax.random.normal(r[1], (d, E)),
            jax.random.normal(r[2], (E, d, de)),
            jax.random.normal(r[3], (E, d, de)),
            jax.random.normal(r[4], (E, de, d)))

    def loss(*a):
        y, aux, _, _ = dense_moe_ffn(*a, top_k=k, capacity_factor=1.5)
        return jnp.sum(y.astype(jnp.float32) ** 2) + aux

    hlo = jax.jit(jax.grad(loss, argnums=range(5))).lower(
        *args).compile().as_text()
    layer = layer_of_ops(hlo)
    ops = [m.groups() for m in re.finditer(
        r"%?([\w.\-]+) = \w+\[([\d,]*)\]\S* (gather|scatter)\(.*"
        r'op_name="([^"]*)"', hlo)]
    assert any(op == "scatter" for _, _, op, _ in ops)   # the index ones
    for name, dims, op, _ in ops:
        if op == "scatter":
            assert str(d) not in dims.split(","), name
    row_gathers = [(name, path) for name, dims, op, path in ops
                   if op == "gather" and str(d) in dims.split(",")]
    assert sum("transpose(" in path for _, path in row_gathers) >= 2
    for name, _ in row_gathers:
        assert layer[name] == "moe_dispatch", name
