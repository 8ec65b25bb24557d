"""HLO analyzer: trip-count-aware flops/bytes/collectives (the roofline
backbone) validated on programs with known costs."""


import jax
import jax.numpy as jnp
import pytest

from repro.launch import hlo_analysis as H

pytestmark = pytest.mark.slow   # XLA compile sweeps: deselected in CI


def _compile(f, *abstract):
    return jax.jit(f).lower(*abstract).compile()


def test_scan_flops_multiplied_by_trip_count():
    L, M, K, N = 12, 64, 128, 96

    def f(w, x):
        def body(h, wl):
            return jnp.tanh(h @ wl), None
        h, _ = jax.lax.scan(body, x, w)
        return h

    comp = _compile(f, jax.ShapeDtypeStruct((L, K, K), jnp.float32),
                    jax.ShapeDtypeStruct((M, K), jnp.float32))
    st = H.analyze(comp.as_text())
    expect = 2 * M * K * K * L
    assert st.flops == pytest.approx(expect, rel=0.01)
    # XLA's own analysis counts the loop body once — ours must be larger
    xla = comp.cost_analysis().get("flops", 0)
    assert st.flops > xla * (L / 2)


def test_nested_scans_multiply():
    Lo, Li, M, K = 3, 5, 16, 32

    def f(w, x):
        def outer(h, wo):
            def inner(h2, _):
                return jnp.tanh(h2 @ wo), None
            h2, _ = jax.lax.scan(inner, h, None, length=Li)
            return h2, None
        h, _ = jax.lax.scan(outer, x, w)
        return h

    comp = _compile(f, jax.ShapeDtypeStruct((Lo, K, K), jnp.float32),
                    jax.ShapeDtypeStruct((M, K), jnp.float32))
    st = H.analyze(comp.as_text())
    expect = 2 * M * K * K * Lo * Li
    assert st.flops == pytest.approx(expect, rel=0.02)


def test_grad_flops_about_3x_forward():
    M, K = 64, 128

    def fwd(w, x):
        return jnp.sum(jnp.tanh(x @ w))

    c_f = _compile(fwd, jax.ShapeDtypeStruct((K, K), jnp.float32),
                   jax.ShapeDtypeStruct((M, K), jnp.float32))
    c_g = _compile(jax.grad(fwd, argnums=(0, 1)),
                   jax.ShapeDtypeStruct((K, K), jnp.float32),
                   jax.ShapeDtypeStruct((M, K), jnp.float32))
    f = H.analyze(c_f.as_text()).flops
    g = H.analyze(c_g.as_text()).flops
    assert 2.5 <= g / f <= 3.5


def test_bytes_scale_with_trip_count():
    def make(n):
        def f(x):
            def body(h, _):
                return jnp.sin(h) * 1.0001, None
            h, _ = jax.lax.scan(body, x, None, length=n)
            return h
        return _compile(f, jax.ShapeDtypeStruct((1024, 256), jnp.float32))

    b2 = H.analyze(make(2).as_text()).bytes_accessed
    b20 = H.analyze(make(20).as_text()).bytes_accessed
    assert 6 <= b20 / b2 <= 14  # ~10x (loop-invariant overhead dilutes)


def test_replica_group_parsers():
    explicit = "all-gather(%x), replica_groups={{0,2},{1,3}}, dims"
    g = H.parse_replica_groups(explicit, 4)
    assert g == [[0, 2], [1, 3]]
    iota = "all-reduce(%x), replica_groups=[4,2]<=[8], more"
    g = H.parse_replica_groups(iota, 8)
    assert g == [[0, 1], [2, 3], [4, 5], [6, 7]]
    iota_t = "all-gather(%x), replica_groups=[4,2]<=[2,4]T(1,0), dims"
    g = H.parse_replica_groups(iota_t, 8)
    # arange(8).reshape(2,4).T.flatten() = [0,4,1,5,2,6,3,7]
    assert g == [[0, 4], [1, 5], [2, 6], [3, 7]]


def test_pod_crossing_classification():
    assert H._crosses_pod([[0, 255], [256, 511]], 256) is False
    assert H._crosses_pod([[0, 256]], 256) is True
    assert H._crosses_pod([[5, 6, 7]], 256) is False
