"""GPipe over the pod axis == sequential stack (8 host devices)."""

import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.slow   # subprocess multi-device: deselected in CI


def test_gpipe_matches_sequential():
    code = textwrap.dedent("""
    import os
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
    import jax, jax.numpy as jnp, numpy as np
    from repro.distributed.pipeline_parallel import gpipe_apply
    from repro.launch.mesh import make_test_mesh

    mesh = make_test_mesh(devices=8)      # pod=2 -> 2 pipeline stages
    n_stages, d, B = 2, 32, 16
    ks = jax.random.split(jax.random.key(0), 2)
    params = {"w": jax.random.normal(ks[0], (n_stages, d, d)) / jnp.sqrt(d),
              "b": jax.random.normal(ks[1], (n_stages, d)) * 0.1}
    x = jax.random.normal(jax.random.key(1), (B, d))

    def stage_fn(p, xm):
        return jnp.tanh(xm @ p["w"] + p["b"])

    # sequential reference
    ref = x
    for s in range(n_stages):
        ref = stage_fn(jax.tree.map(lambda a: a[s], params), ref)

    out = jax.jit(lambda p, x: gpipe_apply(
        stage_fn, p, x, mesh=mesh, n_micro=4))(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    print("GPIPE-OK")
    """)
    r = subprocess.run([sys.executable, "-c", code],
                       env=dict(os.environ,
                                PYTHONPATH=os.path.join(ROOT, "src")),
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "GPIPE-OK" in r.stdout
