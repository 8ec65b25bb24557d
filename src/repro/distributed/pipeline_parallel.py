"""GPipe-style pipeline parallelism over the "pod" axis (optional config).

Alternative use of the expensive inter-pod link: instead of a pod-DP
all-reduce domain, map pipeline STAGES onto pods — the DCN then carries
only microbatch boundary activations, point-to-point (collective_permute),
which is the cheapest possible inter-pod pattern (paper analogy: ship one
blob per hop instead of an all-to-all).

``gpipe_apply`` runs the classic fill/drain schedule inside a shard_map
that is manual over the stage axis:

    step t: stage s computes microbatch (t - s) if 0 <= t-s < n_micro,
            then passes its activation to stage s+1.

Equivalence to the sequential stack is tested on 8 host devices
(tests/test_pipeline_parallel.py).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def gpipe_apply(stage_fn: Callable, params, x, *, mesh, n_micro: int,
                stage_axis: str = "pod"):
    """Run a pipelined stack of ``n_stages = mesh.shape[stage_axis]``.

    stage_fn(stage_params, x_mb) -> y_mb  (same shape as x_mb)
    params: pytree with a leading stage dim on every leaf.
    x: (batch, ...) global input; batch % n_micro == 0.

    Returns y with the same shape as x, equal to applying the stages
    sequentially (stage 0 first).
    """
    n_stages = mesh.shape[stage_axis]
    B = x.shape[0]
    assert B % n_micro == 0, "batch must divide into microbatches"
    mb = B // n_micro
    x_micro = x.reshape((n_micro, mb) + x.shape[1:])

    def local(params_s, xm):
        # params_s: this stage's params (leading stage dim stripped to 1)
        params_s = jax.tree.map(lambda a: a[0], params_s)
        x_micro = xm.reshape((n_micro, mb) + xm.shape[1:])
        sidx = jax.lax.axis_index(stage_axis)
        n_steps = n_micro + n_stages - 1
        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def step(carry, t):
            buf, out = carry  # buf: (mb, ...) activation entering this stage
            my_mb = t - sidx  # microbatch index this stage works on now
            active = (my_mb >= 0) & (my_mb < n_micro)
            # stage 0 ingests fresh microbatches; others use the received buf
            xin = jnp.where(sidx == 0,
                            x_micro[jnp.clip(t, 0, n_micro - 1)], buf)
            y = stage_fn(params_s, xin)
            y = jnp.where(active, y, buf)
            # last stage records its finished microbatch
            out = jax.lax.cond(
                active & (sidx == n_stages - 1),
                lambda o: o.at[jnp.clip(my_mb, 0, n_micro - 1)].set(y),
                lambda o: o, out)
            # ship activations one hop downstream (wraps around harmlessly)
            buf_next = jax.lax.ppermute(y, stage_axis, fwd_perm)
            return (buf_next, out), None

        buf0 = jnp.zeros_like(x_micro[0])
        out0 = jnp.zeros_like(x_micro)
        (_, out), _ = jax.lax.scan(step, (buf0, out0),
                                   jnp.arange(n_steps))
        # result lives on the last stage; share it with every stage
        out = jax.lax.psum(
            jnp.where(sidx == n_stages - 1, out, jnp.zeros_like(out)),
            stage_axis)
        return out

    spec_p = jax.tree.map(lambda _: P(stage_axis), params)
    out = jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec_p, P()),
        out_specs=P(),
        check_vma=False,
        axis_names={stage_axis},
    )(params, x)
    return out.reshape(x.shape)
