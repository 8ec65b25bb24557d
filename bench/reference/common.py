"""Plain float32 reference of a shuffle-fed MoE train step, in jax.numpy.

It imports nothing of the program under test. A model type's module
(``deepseek_v2.py``, ``qwen2_moe.py``) gives the parameter layout, the
attention of one block and its MoE rule (``MoESpec``): the router, the
router state kept between steps and how it follows the experts' loads,
and which of the routed experts are held here. This module holds the
rest: the initialisation from the seed, RMSNorm, rotary embeddings,
causal attention, top-k selection with the capacity rule, the held
experts and the shared ones, cross entropy with the load-balance loss,
and AdamW.

Every matmul runs at float32 with ``precision="highest"``. The control of
``correct`` is the same reference with each matmul operand rounded to
float8 (e4m3, one absmax scale per operand): ``Numerics(fp8=True)``.

Memory: the batch is processed in blocks of rows and the gradients summed,
so that a step fits one chip beside nothing else. Which routed units a
capacity drops depends on the whole batch, so the routing of every MoE
layer is decided first, over all rows (``route``), and the gradient
blocks take it as data.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import (Any, Callable, Dict, FrozenSet, List, NamedTuple,
                    Optional, Tuple)

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30
#: rows of the batch in one gradient block: one sequence at a time, so that
#: a step of the largest cell fits one chip
BLOCK_ROWS = 1


class Leaf(NamedTuple):
    """One parameter: shape, initialisation and the fan-in that scales it.

    ``init``: ``zeros``, ``small`` (normal x 0.02), ``normal`` (normal /
    sqrt(fan_in)) or ``state``: zeros, kept between steps but not trained
    (no gradient, no AdamW), the router state of ``ROUTER_STATE``. Leaves
    are drawn in the sorted order of their dict keys, one key each from
    ``jax.random.split(key(seed), n_leaves)``."""
    shape: tuple
    init: str
    fan_in: int = 1


def is_leaf(x) -> bool:
    return isinstance(x, Leaf)


def trained_mask(layout):
    """The layout's tree with True on each trained leaf, False on state."""
    return jax.tree.map(lambda leaf: leaf.init != "state", layout,
                        is_leaf=is_leaf)


def init_params_fn(layout):
    """key -> float32 parameters."""
    leaves, treedef = jax.tree.flatten(layout, is_leaf=is_leaf)

    def make(key):
        keys = jax.random.split(key, len(leaves))
        vals = []
        for leaf, k in zip(leaves, keys):
            if leaf.init in ("zeros", "state"):
                vals.append(jnp.zeros(leaf.shape, jnp.float32))
                continue
            scale = 0.02 if leaf.init == "small" else \
                1.0 / math.sqrt(max(leaf.fan_in, 1))
            vals.append(jax.random.normal(k, leaf.shape, jnp.float32) * scale)
        return jax.tree.unflatten(treedef, vals)
    return make


def init_params(layout, seed: int):
    """Float32 parameters from the seed, in one jitted call."""
    return jax.jit(init_params_fn(layout))(jax.random.key(seed))


# --- numerics ---------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Numerics:
    """``fp8``: the control. Every matmul takes its operands rounded to
    float8 e4m3 in the forward pass and its output cotangent rounded to
    float8 e5m2 in the backward pass, each under one absmax scale, and
    accumulates in float32: the usual recipe of float8 training."""
    fp8: bool = False


def _round8(x, dtype):
    """x rounded to a float8 type under one absmax scale, as float32."""
    top = float(jnp.finfo(dtype).max)
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = top / amax
    return (x * s).astype(dtype).astype(jnp.float32) / s


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision="highest",
                      preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum8(spec, a, b):
    return _einsum(spec, _round8(a, jnp.float8_e4m3fn),
                   _round8(b, jnp.float8_e4m3fn))


def _einsum8_fwd(spec, a, b):
    a8 = _round8(a, jnp.float8_e4m3fn)
    b8 = _round8(b, jnp.float8_e4m3fn)
    return _einsum(spec, a8, b8), (a8, b8)


def _einsum8_bwd(spec, res, g):
    _, vjp = jax.vjp(lambda a, b: _einsum(spec, a, b), *res)
    return vjp(_round8(g, jnp.float8_e5m2))


_einsum8.defvjp(_einsum8_fwd, _einsum8_bwd)


def make_einsum(num: Numerics) -> Callable:
    if num.fp8:
        return lambda spec, a, b: _einsum8(spec, a, b)
    return _einsum


# --- layers -------------------------------------------------------------------

def rms_norm(x, w, eps):
    """x / rms(x) * (1 + w): the weight is stored as an offset from 1."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def rope_freqs(d: int, theta: float):
    """Inverse frequencies theta^(-2i/d) of a rotary part of width d."""
    return 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))


def rope(x, theta: float, inv=None):
    """Rotary embedding, rotate-half pairs (x[:d/2], x[d/2:]), positions
    0..S-1, at the inverse frequencies ``inv`` (by default
    ``rope_freqs(d, theta)``). x: (B, S, H, d)."""
    S, d = x.shape[1], x.shape[-1]
    inv = rope_freqs(d, theta) if inv is None else inv
    ang = np.arange(S, dtype=np.float32)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(es, q, k, v):
    """softmax(q k^T / sqrt(d_qk)) v with a causal mask, one row of the
    batch at a time. q, k: (B, S, H, dq); v: (B, S, H, dv)."""
    S, dq = q.shape[1], q.shape[-1]
    mask = np.tril(np.ones((S, S), bool))

    def one(qkv):
        qb, kb, vb = qkv
        s = es("qhd,khd->hqk", qb, kb) / math.sqrt(dq)
        s = jnp.where(mask[None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return es("hqk,khd->qhd", p, vb)
    return jax.lax.map(one, (q, k, v))


def swiglu(es, x, w_gate, w_up, w_down):
    return es("...f,fd->...d",
              jax.nn.silu(es("...d,df->...f", x, w_gate))
              * es("...d,df->...f", x, w_up), w_down)


# --- mixture of experts -----------------------------------------------------

#: key, under a MoE block's ``ffn``, of the router state (``Leaf`` init
#: ``state``), one row per MoE layer
ROUTER_STATE = "router_state"


@dataclasses.dataclass(frozen=True)
class MoESpec:
    """A model type's MoE rule, with the run's capacity.

    ``router(xp, logits, state)`` takes one layer's float32 router logits
    (T, num_experts), with ``xp`` numpy on the host (``route``) and
    jax.numpy in the differentiated step (``moe_ffn``), and the layer's
    router state (None without one). It returns ``(scores, combine,
    probs)``: the scores by which the ``top_k`` experts are chosen (a
    selection bias from the state is added to these only); ``combine(sel)``,
    the differentiable (T, top_k) weights of the chosen experts ``sel``
    after the type's normalisation and scaling; and the probabilities the
    load-balance loss takes, or None for no such loss.

    ``update(state, load)``, numpy, gives a layer's router state after a
    step from that step's expert loads (``route``); None keeps it.

    ``held``: the contiguous range [start, stop) of the routed experts
    whose weights are in the layout and whose kept units add to the
    output (None: all). Routing, capacity and the load-balance loss cover
    every routed expert; the absent experts' part is left out."""
    num_experts: int           # routed over: the router's width
    top_k: int
    router: Callable
    capacity_factor: float
    capacity_groups: int       # token shards that each get their own capacity
    aux_coef: float
    update: Optional[Callable] = None
    held: Optional[Tuple[int, int]] = None


def softmax_router(norm_topk: bool) -> Callable:
    """Softmax scoring and greedy top-k, with no router state and no
    scaling: experts are chosen by probability; the combine weights are
    the chosen probabilities, over their sum where ``norm_topk``; the
    load-balance loss takes the probabilities."""
    def router(xp, logits, state):
        if xp is np:
            z = logits - logits.max(-1, keepdims=True)
            probs = np.exp(z) / np.exp(z).sum(-1, keepdims=True)
        else:
            probs = jax.nn.softmax(logits, axis=-1)

        def combine(sel):
            w = xp.take_along_axis(probs, sel, axis=-1)
            if norm_topk:
                w = w / xp.maximum(xp.sum(w, -1, keepdims=True), 1e-9)
            return w
        return probs, combine, probs
    return router


def capacity(units: int, experts: int, factor: float, align: int = 8) -> int:
    """Slots per expert: units/experts x factor, rounded up to 8."""
    c = int(math.ceil(units / experts * factor))
    return max(align, -(-c // align) * align)


def route(moe: MoESpec, logits: np.ndarray, state=None):
    """Top-k routing of the whole batch on the host, from float32 router
    logits (T, E) and the layer's router state, by the type's selection
    scores. Returns (sel_idx (T, k), keep (T, k), load (E,)).

    Units are (token, choice) in token-major order. The tokens fall into
    ``capacity_groups`` equal contiguous shards; within a shard, a unit is
    kept while fewer than ``capacity`` earlier units chose its expert.
    ``load`` counts every choice, kept or not."""
    T, E = logits.shape
    k = moe.top_k
    scores, _, _ = moe.router(np, logits, state)
    # highest score first, lower expert index first on ties
    sel = np.argsort(-scores, axis=-1, kind="stable")[:, :k].astype(np.int32)
    G = moe.capacity_groups
    per = T // G
    cap = capacity(per * k, E, moe.capacity_factor)
    keep = np.zeros((T, k), bool)
    for g in range(G):
        units = sel[g * per:(g + 1) * per].reshape(-1)
        onehot = np.eye(E, dtype=np.int32)[units]
        rank = (np.cumsum(onehot, axis=0) - onehot)[np.arange(units.size),
                                                     units]
        keep[g * per:(g + 1) * per] = (rank < cap).reshape(per, k)
    load = np.bincount(sel.reshape(-1), minlength=E).astype(np.float32)
    return sel, keep, load


def moe_ffn(es, moe: MoESpec, p, x, sel, keep):
    """Routed experts of one block of tokens. x: (T, d) float32; sel, keep:
    (T, k) from ``route``. Each kept unit on a held expert adds its
    combine weight x SwiGLU_e(x); the held experts run eight at a time
    over all tokens, weighted by the unit's combine weight where the
    token chose that expert and by 0 elsewhere. Returns the output and
    the router's probabilities for the load-balance loss (or None)."""
    E = moe.num_experts
    logits = es("td,de->te", x, p["router"])
    state = p.get(ROUTER_STATE)
    _, combine, probs = moe.router(
        jnp, logits, None if state is None else jax.lax.stop_gradient(state))
    w = combine(sel) * keep
    # (T, E) combine weights, then the held experts' columns
    cw = jnp.zeros((x.shape[0], E), jnp.float32).at[
        jnp.arange(x.shape[0])[:, None], sel].add(w)
    if moe.held is not None:
        cw = cw[:, moe.held[0]:moe.held[1]]
    chunk = math.gcd(cw.shape[1], 8)
    n = cw.shape[1] // chunk

    def body(y, xs):
        wg, wu, wd, c = xs
        h = jax.nn.silu(es("td,edf->etf", x, wg)) * es("td,edf->etf", x, wu)
        out = es("etf,efd->etd", h, wd)
        return y + jnp.einsum("te,etd->td", c, out,
                              precision="highest"), None

    def split(a):
        return a.reshape((n, chunk) + a.shape[1:])
    y, _ = jax.lax.scan(jax.checkpoint(body), jnp.zeros_like(x),
                        (split(p["we_gate"]), split(p["we_up"]),
                         split(p["we_down"]), split(cw.T).swapaxes(1, 2)))
    return y, probs


def aux_loss(moe: MoESpec, probs_sum, load, n_tokens):
    """Switch load-balance loss, E x sum_e f_e x mean_prob_e, times its
    coefficient; ``probs_sum`` is this block's sum over tokens."""
    f = load / (n_tokens * moe.top_k)
    return moe.aux_coef * moe.num_experts * jnp.sum(f * probs_sum / n_tokens)


# --- the model ------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Model:
    """A decoder: ``first_dense`` blocks with a dense SwiGLU, then MoE
    blocks. ``attn(es, p, x)`` is the model type's attention."""
    layout: Any
    attn: Callable
    moe: MoESpec
    first_dense: int
    n_moe: int
    eps: float


def _block_params(params, kind: str, i: int):
    return jax.tree.map(lambda a: a[i], params[kind])


def _dense_block(m: Model, es, p, x):
    x = x + m.attn(es, p["attn"], rms_norm(x, p["ln1"], m.eps))
    z = rms_norm(x, p["ln2"], m.eps)
    f = p["ffn"]
    return x + swiglu(es, z, f["w_gate"], f["w_up"], f["w_down"])


def _moe_block(m: Model, es, p, x, sel, keep):
    B, S, d = x.shape
    x = x + m.attn(es, p["attn"], rms_norm(x, p["ln1"], m.eps))
    z = rms_norm(x, p["ln2"], m.eps).reshape(B * S, d)
    f = p["ffn"]
    y, probs = moe_ffn(es, m.moe, f, z, sel, keep)
    if "shared" in f:
        s = f["shared"]
        y = y + swiglu(es, z, s["w_gate"], s["w_up"], s["w_down"])
    return x + y.reshape(B, S, d), \
        None if probs is None else jnp.sum(probs, axis=0)


def _embed(params, tokens):
    return jnp.take(params["embed"]["tok"], tokens, axis=0)


def router_inputs(m: Model, num: Numerics, params, tokens, routes):
    """Float32 router logits of MoE layer ``len(routes)`` for a block of
    rows, given the routing of the MoE layers before it."""
    es = make_einsum(num)
    x = _embed(params, tokens)
    B, S, _ = x.shape
    for i in range(m.first_dense):
        x = _dense_block(m, es, _block_params(params, "dense_blocks", i), x)
    for i, (sel, keep) in enumerate(routes):
        x, _ = _moe_block(m, es, _block_params(params, "blocks", i), x,
                          sel, keep)
    p = _block_params(params, "blocks", len(routes))
    x = x + m.attn(es, p["attn"], rms_norm(x, p["ln1"], m.eps))
    z = rms_norm(x, p["ln2"], m.eps).reshape(B * S, -1)
    return es("td,de->te", z, p["ffn"]["router"])


def block_loss(m: Model, num: Numerics, params, tokens, labels, routes,
               loads, rows_total):
    """(sum of CE over the block's tokens / all tokens + the block's share
    of the load-balance loss, sum of CE / all tokens)."""
    es = make_einsum(num)
    x = _embed(params, tokens)
    B, S, _ = x.shape
    n_tok = rows_total * S
    for i in range(m.first_dense):
        x = jax.checkpoint(lambda p, x: _dense_block(m, es, p, x))(
            _block_params(params, "dense_blocks", i), x)
    aux = 0.0
    for i, (sel, keep) in enumerate(routes):
        x, psum = jax.checkpoint(
            lambda p, x, s, k: _moe_block(m, es, p, x, s, k))(
            _block_params(params, "blocks", i), x, sel, keep)
        if psum is not None:
            aux = aux + aux_loss(m.moe, psum, loads[i], n_tok)
    x = rms_norm(x, params["final_norm"], m.eps)
    logits = es("bsd,dv->bsv", x, params["embed"]["unembed"])
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    ce = jnp.sum(lse - picked) / n_tok
    return ce + aux, ce


# --- AdamW ------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Opt:
    learning_rate: float
    beta1: float
    beta2: float
    eps: float
    weight_decay: float
    grad_clip: float
    warmup_steps: int
    total_steps: int
    min_lr_frac: float

    def lr(self, count: int) -> float:
        """Linear warm-up, then cosine decay to ``min_lr_frac``."""
        warm = min(count / max(self.warmup_steps, 1), 1.0)
        prog = min(max((count - self.warmup_steps)
                       / max(self.total_steps - self.warmup_steps, 1), 0.0),
                   1.0)
        cos = 0.5 * (1.0 + math.cos(math.pi * prog))
        return self.learning_rate * warm * (
            self.min_lr_frac + (1 - self.min_lr_frac) * cos)


def _adamw(opt: Opt, count, lr, scale, p, g, m, v):
    g = g * scale
    m = opt.beta1 * m + (1 - opt.beta1) * g
    v = opt.beta2 * v + (1 - opt.beta2) * jnp.square(g)
    c1 = 1.0 - opt.beta1 ** count
    c2 = 1.0 - opt.beta2 ** count
    step = (m / c1) / (jnp.sqrt(v / c2) + opt.eps) + opt.weight_decay * p
    return p - lr * step, m, v


# --- the first steps ----------------------------------------------------------

@dataclasses.dataclass
class Steps:
    """What the comparison reads from the reference's first steps. With
    ``keep``, also the trees themselves on the host, by leaf name, so that
    another run can be compared with them element by element."""
    losses: List[float]
    grad_norms: np.ndarray      # per leaf, first gradient after clipping
    change_norms: np.ndarray    # per leaf, |p_last - p_0|
    leaf_names: List[str]
    grads: Optional[Dict[str, np.ndarray]] = None   # trained leaves only
    change: Optional[Dict[str, np.ndarray]] = None
    #: leaves kept but not trained: no gradient (norm 0, not in ``grads``)
    state_leaves: FrozenSet[str] = frozenset()
    #: per step, per MoE layer, the expert loads of ``route``
    loads: Optional[List[List[np.ndarray]]] = None


def leaf_names(layout) -> List[str]:
    paths, _ = jax.tree_util.tree_flatten_with_path(layout, is_leaf=is_leaf)
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in paths]


def train_steps(m: Model, opt: Opt, seed: int, batches, *,
                num: Numerics = Numerics(), drop_rows: int = 0,
                trees: bool = False, device=None) -> Steps:
    """One AdamW step from the seed's parameters per batch of ``batches``
    (dicts of (B, S) int32 ``tokens``/``labels``). The moments live on the
    host between steps, so that the device holds parameters, summed
    gradients and one block's activations. ``drop_rows`` leaves the last
    rows of each batch out and takes the mean over the rest (a planted
    fault). ``trees`` copies the first gradient and the change to the
    host. Router state (``Leaf`` init ``state``) takes no gradient and no
    AdamW step; after each step it becomes ``m.moe.update`` of that step's
    expert loads."""
    device = device or jax.devices()[0]
    names = leaf_names(m.layout)
    trained = trained_mask(m.layout)
    flags = np.array(jax.tree.leaves(trained))
    state = frozenset(n for n, f in zip(names, flags) if not f)
    kept = {}
    step_loads = []
    with jax.default_device(device):
        params = init_params(m.layout, seed)
        mom = vel = None
        route_fn = jax.jit(
            lambda p, t, r: router_inputs(m, num, p, t, r))

        def acc_grad(p, acc, t, l, r, ld, rt):
            (_, ce), g = jax.value_and_grad(
                lambda p: block_loss(m, num, p, t, l, r, ld, rt),
                has_aux=True)(p)
            return jax.tree.map(jnp.add, acc, g), ce
        grad_fn = jax.jit(acc_grad, static_argnums=(6,), donate_argnums=(1,))
        zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
        sq_norms = jax.jit(lambda g: [
            jnp.sum(jnp.square(x))
            for x, f in zip(jax.tree.leaves(g), flags) if f])
        upd = jax.jit(lambda p, g, mm, vv, c, lr, s: jax.tree.map(
            lambda f, *a: _adamw(opt, c, lr, s, *a) if f else a[:1] + a[2:],
            trained, p, g, mm, vv), donate_argnums=(0, 1, 2, 3))
        losses, first_grads = [], None
        for count, batch in enumerate(batches, start=1):
            tokens = np.asarray(batch["tokens"])
            labels = np.asarray(batch["labels"])
            if drop_rows:
                tokens, labels = tokens[:-drop_rows], labels[:-drop_rows]
            B, S = tokens.shape
            blocks = [(r, min(r + BLOCK_ROWS, B))
                      for r in range(0, B, BLOCK_ROWS)]
            routes, loads = [], []
            for i in range(m.n_moe):
                logits = np.concatenate([np.asarray(route_fn(
                    params, tokens[a:b], [_rows(r, a, b, S) for r in routes]))
                    for a, b in blocks])
                sel, keep, load = route(m.moe, logits,
                                        _router_state(params, i))
                routes.append((sel, keep))
                loads.append(load)
            step_loads.append(loads)
            grads, ce = zeros(params), 0.0
            for a, b in blocks:
                grads, ce_b = grad_fn(params, grads, tokens[a:b],
                                      labels[a:b],
                                      [_rows(r, a, b, S) for r in routes],
                                      loads, B)
                ce += float(ce_b)
            losses.append(ce)
            sq = np.array([float(x) for x in sq_norms(grads)])
            gnorm = float(np.sqrt(sq.sum()))
            scale = min(1.0, opt.grad_clip / max(gnorm, 1e-9)) \
                if opt.grad_clip > 0 else 1.0
            if first_grads is None:
                first_grads = np.zeros(len(names))
                first_grads[flags] = np.sqrt(sq) * scale
                if trees:
                    kept["grads"] = {n: np.asarray(x) * np.float32(scale)
                                     for n, x in zip(names, jax.device_get(
                                         jax.tree.leaves(grads)))
                                     if n not in state}
            # the moments wait on the host while the next gradients are made
            mom = zeros(params) if mom is None else jax.device_put(mom)
            vel = zeros(params) if vel is None else jax.device_put(vel)
            out = upd(params, grads, mom, vel, np.float32(count),
                      np.float32(opt.lr(count)), np.float32(scale))
            params = jax.tree.map(lambda o: o[0], out, is_leaf=_is_triple)
            if m.moe.update is not None:
                params = _next_state(m, params, loads)
            mom = vel = None
            if count < len(batches):
                mom = jax.device_get(jax.tree.map(lambda o: o[1], out,
                                                  is_leaf=_is_triple))
                vel = jax.device_get(jax.tree.map(lambda o: o[2], out,
                                                  is_leaf=_is_triple))
            del out, grads
        # the change, against the initial parameters made again
        delta = jax.jit(lambda p, k: [
            a - b for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(
                init_params_fn(m.layout)(k)))])(params, jax.random.key(seed))
        del params
        change = np.array([float(x) for x in jax.jit(
            lambda d: [jnp.sqrt(jnp.sum(jnp.square(x))) for x in d])(delta)])
        if trees:
            kept["change"] = dict(zip(names, jax.device_get(delta)))
        del delta
    return Steps(losses, first_grads, change, names, **kept,
                 state_leaves=state, loads=step_loads)


def _is_triple(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and not isinstance(x, Leaf)


def _router_state(params, i: int):
    """MoE layer ``i``'s router state on the host, or None."""
    f = params["blocks"]["ffn"]
    return np.asarray(f[ROUTER_STATE][i]) if ROUTER_STATE in f else None


def _next_state(m: Model, params, loads):
    """``params`` with each MoE layer's router state updated from its
    loads of the step just taken."""
    f = params["blocks"]["ffn"]
    now = np.asarray(f[ROUTER_STATE])
    new = np.stack([m.moe.update(now[i], loads[i]) for i in range(m.n_moe)])
    return {**params, "blocks": {**params["blocks"], "ffn": {
        **f, ROUTER_STATE: jnp.asarray(new, jnp.float32)}}}


def _rows(route_, a: int, b: int, S: int):
    """The routing of rows a..b of the batch."""
    sel, keep = route_
    return sel[a * S:b * S], keep[a * S:b * S]


def model_from(module, hf: Dict[str, Any], capacity_factor: float,
               capacity_groups: int) -> Model:
    """A model type's reference with the run's capacity rule."""
    spec = MoESpec(capacity_factor=capacity_factor,
                   capacity_groups=capacity_groups,
                   **module.moe_settings(hf))
    return module.model(hf, spec)
