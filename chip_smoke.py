"""Bring-up check of the device path on a TPU: ``python chip_smoke.py``.

With no arguments it needs one chip and runs, in this one process:

1. ``device``: JAX must find a TPU. There is no CPU fallback.
2. ``kernel``: the four blob kernels (pack, unpack, compress-pack,
   unpack-decompress) through their ``ops.py`` entry points, at the
   paper's evaluation blob and at the MoE dispatch of the train phase's
   model. Each compiled program must hold the Pallas kernel
   (``tpu_custom_call``), and each output must equal its ``ref.py``
   oracle bit for bit.
3. ``train``: ``train_shuffle_fed`` for 8 steps of deepseek-v2-lite at
   its published widths, cut to 2 layers and a 25600-token vocabulary.
   Every batch the shuffle engine delivers must equal
   ``tokens.reference_batch``; every loss must be finite; the step-0
   loss must be near ln(vocab) and agree with a float32 forward pass.

``python chip_smoke.py --chips 4`` runs only the expert-parallel step
on a (data=2, model=2) mesh of four chips, in the ``direct`` and
``dense`` MoE modes from the same parameters and batch, and checks that
the two agree.

Each phase prints one JSON line. The last line is
``{"ok": true, "device": {"platform", "kind", "count"}}``. Any failure
raises, so the script exits non-zero without that line.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: the model of the train and expert-parallel phases: deepseek-v2-lite at
#: its published widths, all 64 routed experts in the one MoE layer
ARCH = "deepseek-v2-lite-16b"
CUTS = {"num_layers": 2, "vocab_size": 25600}
TRAIN_STEPS = 8

#: |step-0 loss - float32 forward| allowed. The step computes in bfloat16,
#: whose 8 significant bits round each matmul input by up to 2**-9
#: relative; on a loss of about ln(25600) = 10.15 that is 2e-2 if every
#: rounding pushed the same way. Averaged over 8192 tokens the errors
#: mostly cancel, so a larger gap means a wrong path, not rounding.
F32_LOSS_TOL = 2e-2

#: direct vs dense expert dispatch on four chips. Both compute in
#: bfloat16 from the same parameters and batch; they differ in where the
#: expert rows sit in the capacity buffers and in the reduction order of
#: the all-to-alls. At capacity factor 2.0 neither drops a token.
EP_LOSS_RTOL = 1e-3
EP_GRAD_NORM_RTOL = 1e-2


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def require(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


class CacheEvents:
    """Counts JAX's persistent compilation cache hits and misses."""

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def bit_equal(a, b) -> bool:
    """Same shapes, dtypes and bits, leaf by leaf (compared on device)."""
    import jax
    import jax.numpy as jnp

    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    if len(la) != len(lb):
        return False
    for x, y in zip(la, lb):
        if x.shape != y.shape or x.dtype != y.dtype:
            return False
        bits = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
        if not bool(jnp.array_equal(jax.lax.bitcast_convert_type(x, bits),
                                    jax.lax.bitcast_convert_type(y, bits))):
            return False
    return True


# --- phase 2: blob kernels ---------------------------------------------------

#: (name, rows, row width, dtype, bins, capacity, key skew)
KERNEL_GEOMETRIES = (
    # the paper's evaluation blob (``core/simulator.py`` SimConfig): a
    # 16 MiB blob of 1 KiB records over 216 partitions, uniform keys
    ("paper_blob", 16384, 256, "float32", 216, 128, 0.0),
    # the train phase's MoE dispatch: 8192 tokens of d_model 2048 over its
    # 64 experts, Zipf(1) keys so that the hottest bins overflow
    ("moe_dispatch", 8192, 2048, "bfloat16", 64, 1024, 1.0),
)


def phase_kernels(device) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels.blob_codec.ops import (compress_pack_fused,
                                              unpack_decompress_fused)
    from repro.kernels.blob_codec.ref import (compress_pack_ref,
                                              unpack_decompress_ref)
    from repro.kernels.blob_pack.ops import blob_pack_fused
    from repro.kernels.blob_pack.ref import blob_pack_ref
    from repro.kernels.blob_unpack.ops import unpack_from_keys
    from repro.kernels.blob_unpack.ref import blob_unpack_ref
    from repro.shuffle.binning import bin_pack, sorted_order

    for name, rows, width, dtype, bins, cap, skew in KERNEL_GEOMETRIES:
        kx, kk = jax.random.split(jax.random.key(rows))
        x = jax.random.normal(kx, (rows, width), jnp.float32).astype(dtype)
        logits = -skew * jnp.log1p(jnp.arange(bins, dtype=jnp.float32))
        keys = jax.random.categorical(kk, logits, shape=(rows,)).astype(
            jnp.int32)
        order, starts, counts = sorted_order(keys, bins)
        pack = bin_pack(keys, bins, cap)
        blob = blob_pack_ref(x, order, starts, counts, capacity=cap)
        q, scales = compress_pack_ref(x, order, starts, counts, capacity=cap)
        static = dict(num_bins=bins, capacity=cap)
        cases = (
            ("pack", lambda x, k: blob_pack_fused(x, k, **static)[0],
             (x, keys), blob),
            ("unpack", lambda b, k: unpack_from_keys(b, k, **static),
             (blob, keys), blob_unpack_ref(blob, pack.slot, pack.valid)),
            ("compress_pack",
             lambda x, k: compress_pack_fused(x, k, **static)[0],
             (x, keys), (q, scales)),
            ("unpack_decompress",
             lambda q, s, k: unpack_decompress_fused(q, s, k, **static),
             (q, scales, keys),
             unpack_decompress_ref(q, scales, pack.slot, pack.valid)),
        )
        for kernel, fn, args, want in cases:
            t0 = time.perf_counter()
            compiled = jax.jit(fn).lower(*args).compile()
            compile_s = time.perf_counter() - t0
            require("tpu_custom_call" in compiled.as_text(),
                    f"{kernel} at {name}: no Pallas kernel in the program")
            got = jax.block_until_ready(compiled(*args))
            t0 = time.perf_counter()
            jax.block_until_ready(compiled(*args))
            call_s = time.perf_counter() - t0
            require(bit_equal(got, want),
                    f"{kernel} at {name}: output differs from ref.py")
            emit("kernel", geometry=name, kernel=kernel,
                 shape=[rows, width], dtype=dtype, bins=bins, capacity=cap,
                 dropped=int(jnp.sum(jnp.maximum(counts - cap, 0))),
                 compile_s=compile_s, call_s=call_s, bit_exact=True)
    emit("kernels_done", peak_bytes_in_use=peak_bytes(device))


# --- phase 3: shuffle-fed training -------------------------------------------

def model_config():
    from repro.configs import get_config

    full = get_config(ARCH)
    cfg = dataclasses.replace(full, **CUTS)
    cuts = {k: [getattr(full, k), v] for k, v in CUTS.items()}
    return cfg, cuts


def make_engine(stream):
    """The exactly-once engine of ``benchmarks/train_input.py`` without
    fault injection or an outage, flushing a blob per step's records."""
    from repro.cluster import ElasticCluster
    from repro.core import AsyncShuffleEngine, BlobShuffleConfig, \
        EngineConfig
    from repro.core.stores import ExpressOneZoneStore

    bcfg = BlobShuffleConfig(
        batch_bytes=stream.batch * stream.record_value_bytes,
        max_interval_s=0.02, num_partitions=9, num_az=3)
    eng = AsyncShuffleEngine(bcfg, EngineConfig(commit_interval_s=0.15),
                             n_instances=3,
                             store=ExpressOneZoneStore(seed=7, num_az=3),
                             seed=5, exactly_once=True)
    ElasticCluster(eng, mode="cooperative")
    return eng


class CheckedStep:
    """The donated train step, called by ``train_shuffle_fed``. It first
    checks the delivered batch against ``reference_batch``, compiles on
    the first call, and times each step to ``block_until_ready``."""

    def __init__(self, step, stream):
        self.step, self.stream = step, stream
        self.n = 0
        self.compiled = None
        self.compile_s = 0.0
        self.step_s = []

    def __call__(self, params, opt, batch):
        import jax
        import numpy as np

        from repro.train_input import reference_batch

        want = reference_batch(self.stream, self.n)
        require(sorted(batch) == sorted(want),
                f"step {self.n}: batch keys {sorted(batch)}")
        for k, v in want.items():
            require(np.array_equal(np.asarray(batch[k]), v),
                    f"step {self.n}: delivered {k} differs from "
                    "reference_batch")
        batch = jax.device_put(batch)
        if self.compiled is None:
            t0 = time.perf_counter()
            self.compiled = self.step.lower(params, opt, batch).compile()
            self.compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = jax.block_until_ready(self.compiled(params, opt, batch))
        self.step_s.append(time.perf_counter() - t0)
        self.n += 1
        return out


def f32_reference_loss(cfg, tcfg, stream) -> float:
    """Step-0 loss of the same initial parameters and batch, computed in
    float32 with full-precision matmuls."""
    import jax
    import jax.numpy as jnp

    from repro.models import init_params, lm
    from repro.train_input import reference_batch
    from repro.training.train_step import make_loss_fn

    params = init_params(lm.param_defs(cfg), jax.random.key(0))
    ref_cfg = dataclasses.replace(cfg, compute_dtype=jnp.float32)
    loss_fn = make_loss_fn(ref_cfg, tcfg)
    with jax.default_matmul_precision("highest"):
        loss = jax.jit(lambda p, b: loss_fn(p, b)[1]["loss"])(
            params, reference_batch(stream, 0))
        return float(loss)


def phase_train(device) -> None:
    import jax
    import numpy as np

    from repro.shuffle import ShuffleConfig
    from repro.train_input import TokenStreamConfig, train_shuffle_fed
    from repro.training import OptConfig, TrainConfig, make_train_step

    cfg, cuts = model_config()
    stream = TokenStreamConfig(vocab_size=cfg.vocab_size, batch=4,
                               seq_len=2048, seed=0)
    tcfg = TrainConfig(opt=OptConfig(learning_rate=3e-4, warmup_steps=2,
                                     total_steps=TRAIN_STEPS),
                       shuffle=ShuffleConfig(mode="dense"))
    emit("train_config", arch=ARCH, cuts=cuts, d_model=cfg.d_model,
         experts=cfg.moe.num_experts, top_k=cfg.moe.top_k,
         batch=[stream.batch, stream.seq_len], steps=TRAIN_STEPS)

    t0 = time.perf_counter()
    ref_loss = f32_reference_loss(cfg, tcfg, stream)
    ref_s = time.perf_counter() - t0

    step = CheckedStep(jax.jit(make_train_step(cfg, tcfg),
                               donate_argnums=(0, 1)), stream)
    t0 = time.perf_counter()
    res = train_shuffle_fed(cfg, tcfg, None, stream, steps=TRAIN_STEPS,
                            engine_factory=lambda: make_engine(stream),
                            step_fn=step,
                            pipeline_kwargs={"step_interval_s": 0.05,
                                             "prefetch_steps": 2})
    wall_s = time.perf_counter() - t0
    losses = res.losses
    require(res.steps == list(range(TRAIN_STEPS)),
            f"trained steps {res.steps}")
    require(step.n == TRAIN_STEPS, f"{step.n} batches checked")
    require(all(math.isfinite(v) for v in losses), f"losses {losses}")
    ln_v = math.log(cfg.vocab_size)
    require(abs(losses[0] - ln_v) <= 2.0,
            f"step-0 loss {losses[0]} is not within 2 of ln V = {ln_v}")
    require(abs(losses[0] - ref_loss) <= F32_LOSS_TOL,
            f"step-0 loss {losses[0]} vs float32 forward {ref_loss}")
    st = res.input_stats
    emit("train", losses=losses, loss0_f32=ref_loss,
         loss0_gap=losses[0] - ref_loss, loss0_tol=F32_LOSS_TOL,
         ln_vocab=ln_v, batches_match_reference=step.n,
         compile_s=step.compile_s, step_s=step.step_s,
         step_s_median=float(np.median(step.step_s[1:])),
         f32_forward_s=ref_s, wall_s=wall_s,
         records_delivered=st["records_delivered"],
         bytes_delivered=st["bytes_delivered"],
         peak_bytes_in_use=peak_bytes(device))


# --- --chips 4: the expert-parallel step -------------------------------------

def phase_expert_parallel(devices) -> None:
    import jax

    from repro.distributed.sharding import DEFAULT_RULES, batch_specs
    from repro.launch import make_test_mesh
    from repro.launch.specs import input_specs
    from repro.models.common import ShapeConfig
    from repro.shuffle import ShuffleConfig
    from repro.train_input import (TokenStreamConfig, init_train_state,
                                   reference_batch)
    from repro.training import OptConfig, TrainConfig, make_train_step

    require(len(devices) >= 4, f"--chips 4 needs 4 chips, found "
            f"{len(devices)}")
    cfg, cuts = model_config()
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=2.0))
    mesh = make_test_mesh(devices=4)
    stream = TokenStreamConfig(vocab_size=cfg.vocab_size, batch=8,
                               seq_len=2048, seed=0)
    emit("ep_config", arch=ARCH, cuts=cuts, mesh=dict(mesh.shape),
         batch=[stream.batch, stream.seq_len], capacity_factor=2.0)

    params, opt = init_train_state(cfg, 0, mesh)
    shape = ShapeConfig("ep", stream.seq_len, stream.batch, "train")
    shardings = batch_specs(input_specs(cfg, shape), DEFAULT_RULES, mesh)
    batch = {k: jax.device_put(v, shardings[k])
             for k, v in reference_batch(stream, 0).items()}
    metrics = {}
    for mode in ("direct", "dense"):
        shuf = ShuffleConfig(mode=mode, expert_axes=("model",),
                             capacity_factor=2.0)
        tcfg = TrainConfig(opt=OptConfig(), shuffle=shuf)
        # not donated: both modes start from the same parameters
        step = jax.jit(make_train_step(cfg, tcfg, mesh=mesh))
        t0 = time.perf_counter()
        compiled = step.lower(params, opt, batch).compile()
        compile_s = time.perf_counter() - t0
        all_to_alls = compiled.as_text().count(" all-to-all(")
        step_s = []
        for _ in range(3):
            t0 = time.perf_counter()
            _, _, m = jax.block_until_ready(compiled(params, opt, batch))
            step_s.append(time.perf_counter() - t0)
        metrics[mode] = {k: float(m[k]) for k in ("loss", "grad_norm",
                                                   "aux_loss")}
        require(all(math.isfinite(v) for v in metrics[mode].values()),
                f"{mode}: {metrics[mode]}")
        emit("ep_step", mode=mode, **metrics[mode], compile_s=compile_s,
             step_s=step_s, all_to_alls=all_to_alls,
             peak_bytes_in_use=[peak_bytes(d) for d in devices[:4]])
    d, g = metrics["direct"], metrics["dense"]
    loss_rel = abs(d["loss"] - g["loss"]) / abs(g["loss"])
    gn_rel = abs(d["grad_norm"] - g["grad_norm"]) / abs(g["grad_norm"])
    require(loss_rel <= EP_LOSS_RTOL,
            f"direct loss {d['loss']} vs dense {g['loss']}")
    require(gn_rel <= EP_GRAD_NORM_RTOL,
            f"direct grad norm {d['grad_norm']} vs dense {g['grad_norm']}")
    emit("ep_agree", loss_rel=loss_rel, loss_rtol=EP_LOSS_RTOL,
         grad_norm_rel=gn_rel, grad_norm_rtol=EP_GRAD_NORM_RTOL)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the expert-parallel step on 4 chips")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    require(dev.platform == "tpu",
            f"JAX found no TPU (platform {dev.platform!r})")
    cache_dir = enable_compile_cache()
    cache = CacheEvents()
    emit("device", platform=dev.platform, kind=dev.device_kind,
         count=len(devices), compile_cache=cache_dir)
    if args.chips == 4:
        phase_expert_parallel(devices)
    else:
        phase_kernels(dev)
        phase_train(dev)
    emit("compile_cache", dir=cache_dir, hits=cache.hits,
         misses=cache.misses)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
