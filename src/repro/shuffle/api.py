"""Public entry point: expert-parallel MoE FFN with selectable shuffle mode.

Modes (``ShuffleConfig.mode``):
  * ``dense``  — single-device capacity-based einsum dispatch (oracle; used
                 by smoke tests and as the correctness reference).
  * ``direct`` — flat all-to-all over the full EP domain (the "native Kafka
                 shuffling" baseline analogue).
  * ``blob``   — BlobShuffle: hierarchical two-stage exchange with pooled
                 per-pod blob capacity and optional int8 DCN compression.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.shuffle import dispatch as D

# Public kernel surface, resolved lazily (PEP 562): the kernel packages
# import repro.shuffle.* for their host-side front halves, so importing
# them eagerly here would cycle when a kernel module is imported first.
_KERNEL_EXPORTS = {
    "compress_pack_fused": "repro.kernels.blob_codec.ops",
    "unpack_decompress_fused": "repro.kernels.blob_codec.ops",
    "blob_pack_fused": "repro.kernels.blob_pack.ops",
    "unpack_from_keys": "repro.kernels.blob_unpack.ops",
}


def __getattr__(name):
    mod = _KERNEL_EXPORTS.get(name)
    if mod is None:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}")
    import importlib
    return getattr(importlib.import_module(mod), name)


@dataclasses.dataclass(frozen=True)
class ShuffleConfig:
    mode: str = "dense"                  # dense | direct | blob
    token_axes: tuple = ("pod", "data", "model")
    expert_axes: tuple = ("pod", "model")  # EP domain, major → minor
    pod_axis: str = "pod"
    capacity_factor: float = 1.25
    compress_dcn: bool = False
    norm_topk: bool = True
    # set by make_train_step when the step runs inside a shard_map that is
    # already manual over "pod" (blob grad sync): the EP domain is then
    # intra-pod and the inner shard_map uses the ambient (context) mesh.
    use_context_mesh: bool = False

    def resolve(self, mesh) -> "ShuffleConfig":
        """Drop axes that are absent from (or trivial in) the mesh."""
        names = set(_mesh_axis_names(mesh))
        tok = tuple(a for a in self.token_axes if a in names)
        exp = tuple(a for a in self.expert_axes if a in names)
        return dataclasses.replace(self, token_axes=tok, expert_axes=exp)

    def pod_local(self) -> "ShuffleConfig":
        """EP restricted to intra-pod axes (for pod-manual DP regions)."""
        return dataclasses.replace(
            self,
            token_axes=tuple(a for a in self.token_axes if a != self.pod_axis),
            expert_axes=tuple(a for a in self.expert_axes
                              if a != self.pod_axis),
            use_context_mesh=True)


def context_mesh():
    """Ambient mesh of the enclosing shard_map region, else ``None``."""
    mesh = jax.sharding.get_abstract_mesh()
    # outside any region this is an empty AbstractMesh
    return mesh if mesh.axis_names else None


def manual_axis_names(mesh) -> set:
    """Names of the mesh axes that are Manual (shard_map'd) in ``mesh``."""
    if mesh is None:
        return set()
    return {n for n, t in zip(mesh.axis_names, mesh.axis_types)
            if t == jax.sharding.AxisType.Manual}


def _mesh_axis_names(mesh):
    if mesh is not None:
        return mesh.axis_names
    ctx = context_mesh()
    return ctx.axis_names if ctx is not None else ()


def mesh_axis_size(mesh, name) -> int:
    if mesh is not None:
        return mesh.shape[name]
    return dict(context_mesh().shape)[name]


def _expert_ffn(we_gate, we_up, we_down, compute_dtype):
    """Batched SwiGLU over (E_loc, C, d) token buffers."""
    def fn(t):
        t = t.astype(compute_dtype)
        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", t,
                                   we_gate.astype(compute_dtype)))
        u = jnp.einsum("ecd,edf->ecf", t, we_up.astype(compute_dtype))
        return jnp.einsum("ecf,efd->ecd", g * u,
                          we_down.astype(compute_dtype))
    return fn


def _route(x, w_router, top_k, norm_topk, num_real: Optional[int] = None):
    """Router in fp32. Returns (sel_w (T,k) f32, sel_idx (T,k) i32, probs).

    ``num_real``: if the expert set was padded up to the EP-domain size
    (e.g. qwen2-moe's 60 experts on a 32-way domain -> 64), mask the pad
    columns so they are never selected.
    """
    logits = x.astype(jnp.float32) @ w_router.astype(jnp.float32)
    if num_real is not None and num_real < logits.shape[-1]:
        mask = jnp.arange(logits.shape[-1]) < num_real
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    sel_w, sel_idx = jax.lax.top_k(probs, top_k)
    if norm_topk:
        sel_w = sel_w / jnp.maximum(
            jnp.sum(sel_w, axis=-1, keepdims=True), 1e-9)
    return sel_w, sel_idx.astype(jnp.int32), probs


def dense_moe_ffn(x, w_router, we_gate, we_up, we_down, *, top_k: int,
                  capacity_factor: float, norm_topk: bool = True,
                  compute_dtype=jnp.bfloat16):
    """Single-device capacity-based dispatch (correctness oracle).

    x: (T, d). Returns (y (T, d), aux_loss scalar, expert_load (E,)).
    """
    T, d = x.shape
    E = w_router.shape[1]
    sel_w, sel_idx, probs = _route(x, w_router, top_k, norm_topk)
    U = T * top_k
    cap = D._cap(U / E, capacity_factor)
    from repro.shuffle.binning import bin_pack, scatter_to_bins, \
        gather_from_bins
    unit_expert = sel_idx.reshape(-1)
    unit_tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), top_k)
    pack = bin_pack(unit_expert, E, cap)
    ebuf = scatter_to_bins(x[unit_tok], pack, E, cap)      # (E, cap, d)
    eout = _expert_ffn(we_gate, we_up, we_down, compute_dtype)(ebuf)
    y_units = gather_from_bins(eout, pack)                  # (U, d)
    y = jnp.einsum("tk,tkd->td", sel_w,
                   y_units.reshape(T, top_k, d).astype(jnp.float32))
    load = pack.counts
    aux = _aux_loss(probs, load, U, E)
    return y.astype(x.dtype), aux, load


def _aux_loss(probs, load, total_units, E):
    """Switch-style load-balance loss: E * Σ_e f_e · p̄_e."""
    f = load.astype(jnp.float32) / jnp.maximum(total_units, 1)
    pbar = jnp.mean(probs, axis=0)
    return E * jnp.sum(f * pbar)


def _pad_experts(w_router, we_gate, we_up, we_down, ep: int):
    """Pad the expert dimension up to a multiple of the EP-domain size."""
    E = we_gate.shape[0]
    E_pad = -(-E // ep) * ep
    if E_pad == E:
        return w_router, we_gate, we_up, we_down, E
    padE = ((0, E_pad - E),)
    return (jnp.pad(w_router, ((0, 0), padE[0])),
            jnp.pad(we_gate, padE + ((0, 0), (0, 0))),
            jnp.pad(we_up, padE + ((0, 0), (0, 0))),
            jnp.pad(we_down, padE + ((0, 0), (0, 0))),
            E)


def ep_moe_ffn(x, w_router, we_gate, we_up, we_down, *, top_k: int,
               cfg: ShuffleConfig, mesh, compute_dtype=jnp.bfloat16,
               token_mask: Optional[jax.Array] = None):
    """Expert-parallel MoE FFN under shard_map.

    x: (T, d) global flat token array; T must divide the token-axes product
    (callers pad; ``token_mask`` zeroes the combine weights of pad tokens).
    Expert weights: (E, d, d_e) / (E, d_e, d), sharded over ``expert_axes``.

    Returns (y (T, d), aux_loss, diagnostics) with diagnostics psum'd over
    the whole mesh (fully replicated scalars / (E,) loads).
    """
    cfg = cfg.resolve(mesh if not cfg.use_context_mesh else None)
    if cfg.use_context_mesh:
        mesh = None
    if cfg.mode == "dense" or not cfg.expert_axes:
        y, aux, load = dense_moe_ffn(
            x, w_router, we_gate, we_up, we_down, top_k=top_k,
            capacity_factor=cfg.capacity_factor, norm_topk=cfg.norm_topk,
            compute_dtype=compute_dtype)
        zero = jnp.zeros((), jnp.float32)
        return y, aux, D.DispatchDiagnostics(
            jnp.zeros((), jnp.int32), load, zero)

    ep_size = 1
    for a in cfg.expert_axes:
        ep_size *= mesh_axis_size(mesh, a)
    w_router, we_gate, we_up, we_down, E_real = _pad_experts(
        w_router, we_gate, we_up, we_down, ep_size)
    E = w_router.shape[1]
    all_axes = tuple(_mesh_axis_names(mesh))
    # diagnostics are psum'd over the EP axes inside dispatch; fold the
    # remaining mesh axes here so out_specs=P() (fully replicated) is sound.
    spectators = tuple(a for a in all_axes if a not in cfg.expert_axes)
    has_pod = cfg.pod_axis in cfg.expert_axes and \
        mesh_axis_size(mesh, cfg.pod_axis) > 1
    mode = cfg.mode if (cfg.mode != "blob" or has_pod) else "direct"
    inner_axes = tuple(a for a in cfg.expert_axes if a != cfg.pod_axis)

    if token_mask is None:
        token_mask = jnp.ones((x.shape[0],), jnp.float32)

    def local_fn(x_loc, mask_loc, wr, wg, wu, wd):
        sel_w, sel_idx, probs = _route(x_loc, wr, top_k, cfg.norm_topk,
                                       num_real=E_real)
        sel_w = sel_w * mask_loc[:, None]
        expert_fn = _expert_ffn(wg, wu, wd, compute_dtype)
        common = dict(num_experts=E, capacity_factor=cfg.capacity_factor,
                      d_out=x_loc.shape[1])
        if mode == "blob":
            y, diag = D.blob_dispatch_combine(
                x_loc, sel_idx, sel_w, expert_fn, pod_axis=cfg.pod_axis,
                inner_axes=inner_axes, compress_dcn=cfg.compress_dcn,
                **common)
        else:
            y, diag = D.flat_dispatch_combine(
                x_loc, sel_idx, sel_w, expert_fn, ep_axes=cfg.expert_axes,
                **common)
        # Fold spectator axes into the global diagnostics + aux loss.
        n_tok = jax.lax.psum(jnp.sum(mask_loc), all_axes)
        load = diag.expert_load
        psum_probs = jax.lax.psum(
            jnp.sum(probs * mask_loc[:, None], axis=0), all_axes)
        if spectators:
            load = jax.lax.psum(load, spectators)
            dropped = jax.lax.psum(diag.dropped, spectators)
            dcn = jax.lax.psum(diag.dcn_bytes, spectators)
        else:
            dropped, dcn = diag.dropped, diag.dcn_bytes
        f = load.astype(jnp.float32) / jnp.maximum(n_tok * top_k, 1)
        pbar = psum_probs / jnp.maximum(n_tok, 1)
        aux = E_real * jnp.sum(f[:E_real] * pbar[:E_real])
        return y, aux, dropped, load[:E_real], dcn

    tok_spec = P(cfg.token_axes if cfg.token_axes else None)
    kwargs = {}
    if cfg.use_context_mesh:
        # nested inside a pod-manual region: use the ambient mesh and make
        # manual only the axes that are not already manual in the context.
        ctx = context_mesh()
        kwargs["axis_names"] = set(ctx.axis_names) - manual_axis_names(ctx)
    y, aux, dropped, load, dcn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(cfg.token_axes, None), tok_spec, P(None, None),
                  P(cfg.expert_axes, None, None),
                  P(cfg.expert_axes, None, None),
                  P(cfg.expert_axes, None, None)),
        out_specs=(P(cfg.token_axes, None), P(), P(), P(), P()),
        **kwargs,
    )(x, token_mask, w_router, we_gate, we_up, we_down)
    return y, aux, D.DispatchDiagnostics(dropped, load, dcn)
