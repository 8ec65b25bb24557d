"""Train-step builder: loss, microbatch grad accumulation, remat policy,
grad-sync modes (auto GSPMD vs blob-hierarchical cross-pod).

grad_sync modes:
  * ``auto``      — XLA/GSPMD inserts all reductions (incl. cross-pod) —
                    the "native" baseline analogue.
  * ``blob``      — the whole step runs inside a shard_map that is *manual*
                    over the "pod" axis (auto over data/model); the cross-pod
                    gradient reduction is the blob-bucketed hierarchical
                    all-reduce from ``repro.shuffle.grad_sync``.
  * ``blob_int8`` — same, with int8 compression on the DCN leg.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import lm
from repro.models.common import ModelConfig
from repro.shuffle.api import ShuffleConfig
from repro.shuffle import grad_sync as GS
from repro.training.optimizer import OptConfig, adamw_update

IGNORE = -100  # label value ignored by the loss (e.g. image-patch positions)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    opt: OptConfig = OptConfig()
    microbatches: int = 1
    remat: str = "full"              # none | dots | full
    shuffle: ShuffleConfig = ShuffleConfig(mode="dense")
    grad_sync: str = "auto"          # auto | blob | blob_int8
    grad_sync_blob_bytes: int = 16 * 1024 * 1024
    z_loss: float = 0.0


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  z_loss: float = 0.0) -> jax.Array:
    """Mean CE over labels != IGNORE. logits (B,S,V) any dtype; fp32 math."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    idx = jnp.clip(labels, 0, logits.shape[-1] - 1)
    picked = jnp.take_along_axis(logits, idx[..., None], axis=-1)[..., 0]
    ce = lse - picked
    if z_loss:
        ce = ce + z_loss * jnp.square(lse)
    mask = (labels != IGNORE).astype(jnp.float32)
    return jnp.sum(ce * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def cast_compute_params(cfg: ModelConfig, params):
    """Mixed precision: cast master (param_dtype) weights to compute dtype
    at the top of the step, so FSDP all-gathers move bf16, not fp32.
    Leaves declared f32 in the defs (norm scales, A_log, dt_bias) stay f32.
    """
    defs = lm.param_defs(cfg)
    pd = jnp.dtype(cfg.param_dtype)
    cd = jnp.dtype(cfg.compute_dtype)
    if pd == cd or not jnp.issubdtype(pd, jnp.floating):
        return params

    from repro.models.common import is_spec

    def cast(spec, x):
        if jnp.dtype(spec.dtype) == pd:
            return x.astype(cd)
        return x
    return jax.tree.map(cast, defs, params, is_leaf=is_spec)


def make_loss_fn(cfg: ModelConfig, tcfg: TrainConfig, mesh=None,
                 hints=None) -> Callable:
    from repro.models.flash import NO_HINTS
    hints = hints or NO_HINTS

    def loss_fn(params, batch):
        params = cast_compute_params(cfg, params)
        logits, aux = lm.forward(cfg, params, batch, mesh=mesh,
                                 shuffle=tcfg.shuffle, remat=tcfg.remat,
                                 hints=hints)
        ce = cross_entropy(logits, batch["labels"], tcfg.z_loss)
        return ce + aux, {"loss": ce, "aux_loss": aux}
    return loss_fn


def _split_micro(batch: Dict[str, jax.Array], k: int):
    def r(x):
        b = x.shape[0]
        return x.reshape((k, b // k) + x.shape[1:])
    return {key: (r(v) if v.ndim >= 1 and v.shape[0] % k == 0 else v)
            for key, v in batch.items()}


def _grads(loss_fn, params, batch, microbatches: int):
    """(mean) gradients, with optional scan-based microbatch accumulation."""
    if microbatches <= 1:
        (_, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        return grads, metrics
    micro = _split_micro(batch, microbatches)

    def body(carry, mb):
        g_acc, m_acc = carry
        (_, metrics), g = jax.value_and_grad(
            loss_fn, has_aux=True)(params, mb)
        g_acc = jax.tree.map(jnp.add, g_acc, g)
        m_acc = jax.tree.map(jnp.add, m_acc, metrics)
        return (g_acc, m_acc), None

    g0 = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
    m0 = {"loss": jnp.zeros((), jnp.float32),
          "aux_loss": jnp.zeros((), jnp.float32)}
    (grads, metrics), _ = jax.lax.scan(body, (g0, m0), micro)
    inv = 1.0 / microbatches
    return (jax.tree.map(lambda x: x * inv, grads),
            jax.tree.map(lambda x: x * inv, metrics))


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig, mesh=None,
                    hints=None):
    """Returns train_step(params, opt_state, batch) -> (params, opt, metrics).

    With grad_sync != auto and a multi-pod mesh, the step is wrapped in a
    shard_map manual over "pod": the loss is the pod-local mean and the
    cross-pod reduction is the explicit blob-hierarchical all-reduce.
    """
    loss_fn = make_loss_fn(cfg, tcfg, mesh=mesh, hints=hints)

    def plain_step(params, opt_state, batch):
        grads, metrics = _grads(loss_fn, params, batch, tcfg.microbatches)
        params, opt_state, om = adamw_update(tcfg.opt, grads, opt_state,
                                             params)
        metrics.update(om)
        return params, opt_state, metrics

    use_blob = (tcfg.grad_sync in ("blob", "blob_int8") and mesh is not None
                and "pod" in mesh.axis_names and mesh.shape["pod"] > 1)
    if not use_blob:
        return plain_step

    compress = tcfg.grad_sync == "blob_int8"
    # inside the pod-manual region the EP domain is intra-pod (experts are
    # part of the pod-DP replica) and shard_maps use the context mesh
    tcfg_pod = dataclasses.replace(tcfg, shuffle=tcfg.shuffle.pod_local())
    pod_loss_fn = make_loss_fn(cfg, tcfg_pod, mesh=None, hints=hints)

    def pod_local_step(params, opt_state, batch):
        grads, metrics = _grads(pod_loss_fn, params, batch,
                                tcfg.microbatches)
        grads, _ = GS.blob_allreduce_grads(
            grads, pod_axis="pod", blob_bytes=tcfg.grad_sync_blob_bytes,
            compress=compress, average=True)
        metrics = jax.tree.map(
            lambda x: jax.lax.pmean(x, "pod"), metrics)
        params, opt_state, om = adamw_update(tcfg.opt, grads, opt_state,
                                             params)
        metrics.update(om)
        return params, opt_state, metrics

    # manual over "pod" only; data/model stay automatic (GSPMD).
    def spec_tree(tree, batch_dim0=False):
        return jax.tree.map(
            lambda _: P("pod") if batch_dim0 else P(), tree)

    def step(params, opt_state, batch):
        return jax.shard_map(
            pod_local_step, mesh=mesh,
            in_specs=(spec_tree(params), spec_tree(opt_state),
                      spec_tree(batch, batch_dim0=True)),
            out_specs=(spec_tree(params), spec_tree(opt_state),
                       jax.tree.map(lambda _: P(), {"loss": 0, "aux_loss": 0,
                                                    "grad_norm": 0,
                                                    "lr": 0})),
            check_vma=False,
            axis_names={"pod"},
        )(params, opt_state, batch)

    return step
