"""Multi-device (8 fake CPU devices) tests, run in subprocesses so the
device count can be set before jax initializes.

Covers: MoE dispatch equivalence (dense oracle vs flat vs blob-hierarchical,
values AND gradients), token conservation, DCN-bytes accounting, the
blob-bucketed hierarchical grad sync (exact + int8 + error feedback), and
the partial-auto shard_map train step.
"""

import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.slow   # subprocess multi-device: deselected in CI


def run_py(body: str, devices: int = 8) -> str:
    code = ("import os\n"
            f"os.environ['XLA_FLAGS'] = "
            f"'--xla_force_host_platform_device_count={devices}'\n"
            + textwrap.dedent(body))
    env = dict(os.environ,
               PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_moe_dispatch_modes_agree():
    run_py("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_test_mesh
    from repro.shuffle.api import ShuffleConfig, dense_moe_ffn, ep_moe_ffn

    mesh = make_test_mesh(devices=8)   # (pod=2, data=2, model=2)
    E, k, d, de, T = 8, 2, 16, 32, 64
    ks = jax.random.split(jax.random.key(0), 5)
    x = jax.random.normal(ks[0], (T, d), jnp.float32)
    wr = jax.random.normal(ks[1], (d, E)) * 0.5
    wg = jax.random.normal(ks[2], (E, d, de)) / jnp.sqrt(d)
    wu = jax.random.normal(ks[3], (E, d, de)) / jnp.sqrt(d)
    wd = jax.random.normal(ks[4], (E, de, d)) / jnp.sqrt(de)

    # capacity high enough that nothing drops -> all modes exact-equal
    y_ref, aux_ref, _, _ = dense_moe_ffn(x, wr, wg, wu, wd, top_k=k,
                                      capacity_factor=16.0,
                                      compute_dtype=jnp.float32)
    outs = {}
    for mode in ("direct", "blob"):
        cfg = ShuffleConfig(mode=mode, token_axes=("pod","data","model"),
                            expert_axes=("pod","model"),
                            capacity_factor=16.0)
        y, aux, diag = jax.jit(lambda x: ep_moe_ffn(
            x, wr, wg, wu, wd, top_k=k, cfg=cfg, mesh=mesh,
            compute_dtype=jnp.float32))(x)
        outs[mode] = (y, aux, diag)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-4)
        assert int(diag.dropped) == 0
        # token conservation: selections == T*k
        assert int(jnp.sum(diag.expert_load)) == T * k
    # blob mode crossed the pod boundary; direct reports its payload too
    assert float(outs["blob"][2].dcn_bytes) > 0
    print("MODES-AGREE-OK")
    """)


def test_moe_dispatch_gradients_agree():
    run_py("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_test_mesh
    from repro.shuffle.api import ShuffleConfig, dense_moe_ffn, ep_moe_ffn

    mesh = make_test_mesh(devices=8)
    E, k, d, de, T = 8, 2, 12, 16, 32
    ks = jax.random.split(jax.random.key(1), 5)
    x = jax.random.normal(ks[0], (T, d), jnp.float32)
    wr = jax.random.normal(ks[1], (d, E)) * 0.5
    wg = jax.random.normal(ks[2], (E, d, de)) / jnp.sqrt(d)
    wu = jax.random.normal(ks[3], (E, d, de)) / jnp.sqrt(d)
    wd = jax.random.normal(ks[4], (E, de, d)) / jnp.sqrt(de)

    def loss_dense(x, wr, wg, wu, wd):
        y, aux, _, _ = dense_moe_ffn(x, wr, wg, wu, wd, top_k=k,
                                  capacity_factor=16.0,
                                  compute_dtype=jnp.float32)
        return jnp.sum(jnp.tanh(y)) + aux

    def make_loss(mode):
        cfg = ShuffleConfig(mode=mode, token_axes=("pod","data","model"),
                            expert_axes=("pod","model"),
                            capacity_factor=16.0)
        def loss(x, wr, wg, wu, wd):
            y, aux, _ = ep_moe_ffn(x, wr, wg, wu, wd, top_k=k, cfg=cfg,
                                   mesh=mesh, compute_dtype=jnp.float32)
            return jnp.sum(jnp.tanh(y)) + aux
        return loss

    g_ref = jax.grad(loss_dense, argnums=(0,1,2,3,4))(x, wr, wg, wu, wd)
    for mode in ("direct", "blob"):
        g = jax.jit(jax.grad(make_loss(mode), argnums=(0,1,2,3,4)))(
            x, wr, wg, wu, wd)
        for a, b in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=2e-4)
    print("GRADS-AGREE-OK")
    """)


def test_blob_pools_capacity_smaller_dcn():
    """The hierarchical mode's pooled stage-2 capacity sends fewer bytes
    across the pod axis than flat per-(src,expert) lanes."""
    run_py("""
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_test_mesh
    from repro.shuffle.api import ShuffleConfig, ep_moe_ffn

    mesh = make_test_mesh(devices=8)
    E, k, d, de, T = 16, 2, 8, 8, 256
    ks = jax.random.split(jax.random.key(2), 5)
    x = jax.random.normal(ks[0], (T, d), jnp.float32)
    wr = jax.random.normal(ks[1], (d, E)) * 0.1
    wg = jax.random.normal(ks[2], (E, d, de))
    wu = jax.random.normal(ks[3], (E, d, de))
    wd = jax.random.normal(ks[4], (E, de, d))
    dcn = {}
    for mode in ("direct", "blob"):
        cfg = ShuffleConfig(mode=mode, token_axes=("pod","data","model"),
                            expert_axes=("pod","model"),
                            capacity_factor=1.5)
        _, _, diag = jax.jit(lambda x: ep_moe_ffn(
            x, wr, wg, wu, wd, top_k=k, cfg=cfg, mesh=mesh,
            compute_dtype=jnp.float32))(x)
        dcn[mode] = float(diag.dcn_bytes)
    assert dcn["blob"] < dcn["direct"], dcn
    print("DCN", dcn)
    """)


def test_grad_sync_exact_and_compressed():
    run_py("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_test_mesh
    from repro.shuffle import grad_sync as GS

    mesh = make_test_mesh(devices=8)
    grads = {"a": jnp.arange(1000, dtype=jnp.float32).reshape(10, 100),
             "b": jnp.ones((37,), jnp.float32)}

    def pod_fn(g):
        g = jax.tree.map(lambda x: x * (1 + jax.lax.axis_index("pod")), g)
        out, _ = GS.blob_allreduce_grads(g, blob_bytes=512, average=True)
        return out

    out = jax.jit(jax.shard_map(pod_fn, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(), grads),),
        out_specs=jax.tree.map(lambda _: P(), grads),
        check_vma=False,
        axis_names={"pod"}))(grads)
    # mean over pods of (1x, 2x) = 1.5x
    np.testing.assert_allclose(np.asarray(out["a"]),
                               np.asarray(grads["a"]) * 1.5, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out["b"]), 1.5, rtol=1e-6)

    # int8-compressed path: small relative error
    def pod_fn_c(g):
        out, _ = GS.blob_allreduce_grads(g, blob_bytes=512, average=True,
                                         compress=True)
        return out
    outc = jax.jit(jax.shard_map(pod_fn_c, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(), grads),),
        out_specs=jax.tree.map(lambda _: P(), grads),
        check_vma=False,
        axis_names={"pod"}))(grads)
    err = np.abs(np.asarray(outc["a"]) - np.asarray(grads["a"]))
    rel = err.max() / np.abs(np.asarray(grads["a"])).max()
    assert rel < 0.02, rel
    print("GRAD-SYNC-OK", rel)
    """)


def test_error_feedback_reduces_bias():
    run_py("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.shuffle import compression as C

    # repeated compression of the same gradient: EF makes the *running sum*
    # of transmitted payloads converge to the true sum (unbiased).
    g = jnp.asarray(np.random.default_rng(0).normal(size=4096) * 1e-3,
                    jnp.float32)
    resid = jnp.zeros_like(g)
    acc = jnp.zeros_like(g)
    for _ in range(50):
        payload, resid = C.with_error_feedback(g, resid)
        acc = acc + payload
    err_ef = float(jnp.max(jnp.abs(acc / 50 - g)))
    naive = C.compress_decompress(g)
    err_naive = float(jnp.max(jnp.abs(naive - g)))
    assert err_ef < err_naive * 0.2, (err_ef, err_naive)
    print("EF-OK", err_ef, err_naive)
    """)


def test_train_step_blob_grad_sync_matches_auto():
    run_py("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.launch.mesh import make_test_mesh
    from repro.models import lm
    from repro.models.common import init_params
    from repro.training import OptConfig, TrainConfig, adamw_init, \\
        make_train_step

    mesh = make_test_mesh(devices=8)
    cfg = get_config("granite-3-2b", smoke=True)
    params = init_params(lm.param_defs(cfg), jax.random.key(0))
    opt = adamw_init(params)
    B, S = 8, 16
    batch = {"tokens": jax.random.randint(jax.random.key(1), (B, S), 0,
                                          cfg.vocab_size),
             "labels": jax.random.randint(jax.random.key(2), (B, S), 0,
                                          cfg.vocab_size)}
    outs = {}
    for sync in ("auto", "blob", "blob_int8"):
        tcfg = TrainConfig(opt=OptConfig(learning_rate=1e-3),
                           grad_sync=sync, grad_sync_blob_bytes=4096)
        step = make_train_step(cfg, tcfg, mesh=mesh)
        p2, o2, m = jax.jit(step)(params, opt, batch)
        outs[sync] = (m["loss"], m["grad_norm"], p2)
    # loss equal up to bf16 reduction-order noise (pod-local vs global mean)
    np.testing.assert_allclose(float(outs["blob"][0]),
                               float(outs["auto"][0]), rtol=1e-4)
    np.testing.assert_allclose(float(outs["blob"][1]),
                               float(outs["auto"][1]), rtol=1e-3)
    # updated params match between auto and exact blob sync
    for a, b in zip(jax.tree.leaves(outs["auto"][2]),
                    jax.tree.leaves(outs["blob"][2])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-5, rtol=5e-4)
    # int8 path close but not exact
    np.testing.assert_allclose(float(outs["blob_int8"][1]),
                               float(outs["auto"][1]), rtol=0.05)
    print("TRAIN-SYNC-OK")
    """)
