import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

# Multi-pod dry-run: lower + compile every (arch × shape) cell on the
# production meshes and extract roofline terms from the compiled artifact.
# A CPU tool by design: its 512 devices are forced host devices. The lines
# above MUST run before any jax import (device count locks on first init);
# tests/benches never import this module.

import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import subprocess    # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import all_cells, all_skips, get_config, get_shape  # noqa: E402
from repro.distributed.sharding import (DEFAULT_RULES, named_shardings,  # noqa: E402
                                        partition_spec)
from repro.launch import hlo_analysis  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.specs import input_specs  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.models.common import (ArraySpec, ModelConfig, ShapeConfig,  # noqa: E402
                                 abstract_params, is_spec)
from repro.serving.engine import ServeConfig, make_decode_step, \
    make_prefill_step  # noqa: E402
from repro.shuffle.api import (ShuffleConfig, context_mesh,  # noqa: E402
                               manual_axis_names)
from repro.training.train_step import TrainConfig, make_train_step  # noqa: E402
from repro.training.optimizer import OptConfig  # noqa: E402

# --- hardware model (TPU v5e-class, per assignment) -------------------------
PEAK_FLOPS = 197e12        # bf16 FLOP/s per chip
HBM_BW = 819e9             # bytes/s per chip
ICI_BW = 50e9              # bytes/s per chip (intra-pod link)
DCN_BW = 6.25e9            # bytes/s per chip (inter-pod; assumed ICI/8)
HBM_PER_CHIP = 16 * 1024**3

# microbatch counts for train cells (activation-memory control)
MICROBATCH = {
    "qwen2-72b": 8, "llava-next-34b": 8,
    "zamba2-2.7b": 8, "mamba2-130m": 8,
    # §Perf 2.1: one big microbatch amortizes FSDP/SP gathers (3.4× step)
    "deepseek-v2-lite-16b": 1, "qwen2-moe-a2.7b": 1,
    "starcoder2-3b": 2, "granite-3-2b": 2, "gemma-2b": 2,
    "hubert-xlarge": 2,
}


def _float_to(dtype):
    def f(s: ArraySpec) -> ArraySpec:
        if jnp.issubdtype(jnp.dtype(s.dtype), jnp.floating):
            return dataclasses.replace(s, dtype=dtype)
        return s
    return f


def serving_param_defs(cfg: ModelConfig):
    """Serving keeps weights in compute dtype (bf16)."""
    return jax.tree.map(_float_to(cfg.compute_dtype), lm.param_defs(cfg),
                        is_leaf=is_spec)


def cell_rules(cfg: ModelConfig, shape: ShapeConfig, mesh):
    """Per-cell sharding rules (see DESIGN.md / sharding.py).

    Decode: pure tensor parallelism — NO FSDP ("embed"->data) on serving
    weights. FSDP'd weights make QKV projections partial-sum over "data",
    and GSPMD pushes that psum through the cache dynamic-update-slice,
    all-reducing the entire stacked KV cache every step (observed 14.6 GB
    all-reduce on deepseek decode_32k — see EXPERIMENTS.md §Perf).
    MLA latent caches (no head dim) and GQA caches whose kv-head count
    does not divide the model axis are sequence-sharded instead.
    """
    rules = DEFAULT_RULES
    if shape.is_decode:
        model_size = mesh.shape.get("model", 1)
        if cfg.mla is not None:
            # MLA: pure TP + seq-sharded latent cache. FSDP'd serving
            # weights make the latent projection a partial sum which GSPMD
            # pushes through the cache update, all-reducing the whole
            # stacked cache (§Perf D1) — measured 64 GiB -> 10 GiB.
            rules = rules.override(embed=(), kv_embed=(),
                                   kv_heads=(), kv_seq=("model",))
        elif cfg.num_kv_heads % model_size != 0:
            # GQA with kv heads not divisible by TP: sequence-shard caches
            rules = rules.override(kv_heads=(), kv_seq=("model",))
    return rules


def _strip_ambient_manual(pspec):
    """Drop mesh axes that are Manual in the ambient mesh (inside a
    pod-manual shard_map the constraint must not mention "pod")."""
    manual = manual_axis_names(context_mesh())
    if not manual:
        return pspec

    def strip(part):
        if part is None:
            return None
        if isinstance(part, tuple):
            kept = tuple(a for a in part if a not in manual)
            return kept if len(kept) > 1 else (kept[0] if kept else None)
        return None if part in manual else part
    from jax.sharding import PartitionSpec as P
    return P(*(strip(p) for p in pspec))


def make_hints(cfg: ModelConfig, mesh, rules):
    """Sharding-constraint hooks: sequence-parallel residuals + either
    head-sharded or context-parallel (q-block-sharded) flash attention."""
    from repro.models.flash import ShardHints
    act_rules = rules.override(seq=("model",))

    def residual(x):
        spec = ArraySpec(x.shape, x.dtype, ("batch", "seq", None))
        ps = _strip_ambient_manual(partition_spec(spec, act_rules, mesh))
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, ps))

    model_size = mesh.shape.get("model", 1)
    heads_ok = cfg.num_heads % model_size == 0

    def qblocks(x):  # (B, nq, qc, H, D)
        axes = (("batch", None, None, "heads", None) if heads_ok
                else ("batch", "seq", None, None, None))
        spec = ArraySpec(x.shape, x.dtype, axes)
        ps = _strip_ambient_manual(partition_spec(spec, act_rules, mesh))
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, ps))

    return ShardHints(residual=residual, qblocks=qblocks)


def pick_q_chunk(cfg: ModelConfig, shape: ShapeConfig, mesh) -> int:
    """q-chunk so that the q-block count divides the model axis when the
    arch needs context-parallel attention (heads % model != 0)."""
    model_size = mesh.shape.get("model", 1)
    if cfg.num_heads % model_size == 0:
        return cfg.flash_q_chunk
    qc = cfg.flash_q_chunk
    while qc > 128 and (shape.seq_len // qc) % model_size != 0:
        qc //= 2
    return qc


def shuffle_for(cfg: ModelConfig, mesh, moe_mode: str) -> ShuffleConfig:
    return ShuffleConfig(
        mode=moe_mode if cfg.moe is not None else "dense",
        token_axes=("pod", "data", "model"),
        expert_axes=("pod", "model"),
    )


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """Analytic MODEL_FLOPS: 6·N·D (train) / 2·N·D (inference tokens)."""
    n_active = cfg.active_param_count()
    embed = cfg.vocab_size * cfg.d_model
    n = max(n_active - embed, 1)
    if shape.step == "train":
        toks = shape.global_batch * shape.seq_len
        return 6.0 * n * toks
    if shape.step == "prefill":
        toks = shape.global_batch * shape.seq_len
        return 2.0 * n * toks
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, *,
               moe_mode: str, grad_sync: str, microbatches: int,
               remat: str = "full", mla_absorb: bool = False,
               compress_dcn: bool = False):
    rules = cell_rules(cfg, shape, mesh)
    shuf = shuffle_for(cfg, mesh, moe_mode)
    batch_defs = input_specs(cfg, shape)
    batch_abs = {k: s.abstract() for k, s in batch_defs.items()}
    batch_sh = {k: NamedSharding(mesh, partition_spec(s, rules, mesh))
                for k, s in batch_defs.items()}

    if shape.step in ("train", "prefill"):
        cfg = dataclasses.replace(
            cfg, flash_q_chunk=pick_q_chunk(cfg, shape, mesh))
    hints = make_hints(cfg, mesh, rules)

    if shape.step == "train":
        defs = lm.param_defs(cfg)
        params_abs = abstract_params(defs)
        params_sh = named_shardings(defs, rules, mesh)
        opt_defs = {"m": jax.tree.map(_float_to(jnp.float32), defs,
                                      is_leaf=is_spec),
                    "v": jax.tree.map(_float_to(jnp.float32), defs,
                                      is_leaf=is_spec),
                    "count": ArraySpec((), jnp.int32, ())}
        opt_abs = abstract_params(opt_defs)
        opt_sh = named_shardings(opt_defs, rules, mesh)
        if compress_dcn:
            shuf = dataclasses.replace(shuf, compress_dcn=True)
        tcfg = TrainConfig(opt=OptConfig(), microbatches=microbatches,
                           remat=remat, shuffle=shuf, grad_sync=grad_sync)
        step = make_train_step(cfg, tcfg, mesh=mesh, hints=hints)
        fn = jax.jit(step,
                     in_shardings=(params_sh, opt_sh, batch_sh),
                     out_shardings=(params_sh, opt_sh, None),
                     donate_argnums=(0, 1))
        return fn, (params_abs, opt_abs, batch_abs)

    if shape.step == "prefill":
        defs = serving_param_defs(cfg)
        params_abs = abstract_params(defs)
        params_sh = named_shardings(defs, rules, mesh)
        scfg = ServeConfig(shuffle=shuf)
        prefill = make_prefill_step(cfg, scfg, mesh=mesh, hints=hints)
        fn = jax.jit(prefill, in_shardings=(params_sh, batch_sh))
        return fn, (params_abs, batch_abs)

    # decode
    defs = serving_param_defs(cfg)
    params_abs = abstract_params(defs)
    params_sh = named_shardings(defs, rules, mesh)
    cdefs = lm.cache_defs(cfg, shape.global_batch, shape.seq_len)
    cache_abs = abstract_params(cdefs)
    cache_sh = named_shardings(cdefs, rules, mesh)
    scfg = ServeConfig(shuffle=shuf)
    decode = make_decode_step(cfg, scfg, mesh=mesh)
    fn = jax.jit(decode, in_shardings=(params_sh, cache_sh, batch_sh),
                 out_shardings=(cache_sh, None, None),
                 donate_argnums=(1,))
    return fn, (params_abs, cache_abs, batch_abs)


def run_cell(arch: str, shape_name: str, mesh_kind: str, *,
             moe_mode: str = "blob", grad_sync: str = "auto",
             microbatches: int = 0, remat: str = "full",
             capacity_factor: float = 0.0, ssd_chunk: int = 0,
             ssd_bf16: bool = False, mla_absorb: bool = False,
             compress_dcn: bool = False) -> dict:
    cfg = get_config(arch)
    overrides = {}
    if ssd_chunk and cfg.ssm is not None:
        overrides["ssm"] = dataclasses.replace(cfg.ssm, chunk=ssd_chunk)
    if ssd_bf16 and cfg.ssm is not None:
        base = overrides.get("ssm", cfg.ssm)
        overrides["ssm"] = dataclasses.replace(base, intra_bf16=True)
    if capacity_factor and cfg.moe is not None:
        overrides["moe"] = dataclasses.replace(
            cfg.moe, capacity_factor=capacity_factor)
    if mla_absorb and cfg.mla is not None:
        overrides["mla"] = dataclasses.replace(cfg.mla)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = get_shape(shape_name)
    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    n_dev = 1
    for a in mesh.axis_names:
        n_dev *= mesh.shape[a]
    dpp = n_dev // mesh.shape.get("pod", 1)
    mb = microbatches or (MICROBATCH.get(arch, 1)
                          if shape.step == "train" else 1)

    if mla_absorb:
        import repro.models.lm as _lm
        import repro.models.mla as _mla
        _orig = _mla.mla_decode

        def _mla_decode_abs(c, p, x, cache, pos):
            return _orig(c, p, x, cache, pos, absorb=True)
        _lm_attn = _lm._attn_decode

        def _patched(c, p, x, cache, pos):
            if c.mla is not None:
                return _mla_decode_abs(c, p, x, cache, pos)
            return _lm_attn(c, p, x, cache, pos)
        _lm._attn_decode = _patched

    t0 = time.time()
    fn, args = build_cell(cfg, shape, mesh, moe_mode=moe_mode,
                          grad_sync=grad_sync, microbatches=mb,
                          remat=remat, compress_dcn=compress_dcn)
    lowered = fn.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    stats = hlo_analysis.analyze(compiled.as_text(), num_devices=n_dev,
                                 devices_per_pod=dpp)

    compute_s = stats.flops / PEAK_FLOPS
    memory_s = stats.bytes_accessed / HBM_BW
    ici_bytes = stats.collective_bytes - stats.dcn_collective_bytes
    collective_s = ici_bytes / ICI_BW + stats.dcn_collective_bytes / DCN_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)
    step_s = max(terms.values())
    mf = model_flops(cfg, shape) / n_dev
    bound_s = mf / PEAK_FLOPS
    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "step": shape.step, "devices": n_dev,
        "moe_mode": moe_mode if cfg.moe else None,
        "grad_sync": grad_sync if shape.step == "train" else None,
        "microbatches": mb, "remat": remat,
        "capacity_factor": capacity_factor or None,
        "ssd_chunk": ssd_chunk or None, "ssd_bf16": ssd_bf16,
        "mla_absorb": mla_absorb,
        "compress_dcn": compress_dcn,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_est_bytes": ma.argument_size_in_bytes
            + ma.output_size_in_bytes + ma.temp_size_in_bytes
            - ma.alias_size_in_bytes,
            "hbm_per_chip": HBM_PER_CHIP,
        },
        "xla_cost_analysis": {
            "flops_no_trips": ca.get("flops"),
            "bytes_no_trips": ca.get("bytes accessed"),
        },
        "hlo": {
            "flops_per_dev": stats.flops,
            "bytes_per_dev": stats.bytes_accessed,
            "collective_bytes_per_dev": stats.collective_bytes,
            "dcn_collective_bytes_per_dev": stats.dcn_collective_bytes,
            "collective_by_op": stats.collective_by_op,
            "collective_count": stats.collective_count,
        },
        "roofline": {
            **terms,
            "dominant": dominant,
            "step_time_s": step_s,
            "model_flops_per_dev": mf,
            "useful_flops_ratio": (mf / stats.flops) if stats.flops else 0.0,
            "roofline_fraction": (bound_s / step_s) if step_s else 0.0,
        },
    }
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--moe-mode", default="blob",
                    choices=["blob", "direct", "dense"])
    ap.add_argument("--grad-sync", default="auto",
                    choices=["auto", "blob", "blob_int8"])
    ap.add_argument("--microbatches", type=int, default=0)
    ap.add_argument("--remat", default="full",
                    choices=["full", "dots", "none"])
    ap.add_argument("--cf", type=float, default=0.0,
                    help="MoE capacity factor override")
    ap.add_argument("--ssd-chunk", type=int, default=0)
    ap.add_argument("--ssd-bf16", action="store_true")
    ap.add_argument("--mla-absorb", action="store_true")
    ap.add_argument("--compress-dcn", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--tag", default="")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    if args.list:
        for arch, shp in all_cells():
            print(f"{arch:24s} {shp}")
        for arch, shp, why in all_skips():
            print(f"{arch:24s} {shp:12s} SKIP: {why}")
        return

    if args.all:
        # one subprocess per cell: isolation + incremental (skip existing)
        failures = []
        for arch, shp in all_cells():
            out = _cell_path(args.out, args.mesh, arch, shp, args.tag)
            if os.path.exists(out) and not args.force:
                print(f"skip (exists): {out}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shp, "--mesh", args.mesh,
                   "--moe-mode", args.moe_mode, "--grad-sync",
                   args.grad_sync, "--out", args.out]
            if args.tag:
                cmd += ["--tag", args.tag]
            print(">>", " ".join(cmd), flush=True)
            rc = subprocess.run(cmd).returncode
            if rc != 0:
                failures.append((arch, shp))
        if failures:
            print("FAILED CELLS:", failures)
            sys.exit(1)
        print("all cells OK")
        return

    res = run_cell(args.arch, args.shape, args.mesh, moe_mode=args.moe_mode,
                   grad_sync=args.grad_sync, microbatches=args.microbatches,
                   remat=args.remat, capacity_factor=args.cf,
                   ssd_chunk=args.ssd_chunk, ssd_bf16=args.ssd_bf16,
                   mla_absorb=args.mla_absorb,
                   compress_dcn=args.compress_dcn)
    out = _cell_path(args.out, args.mesh, args.arch, args.shape, args.tag)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(res, f, indent=2)
    r = res["roofline"]
    print(f"{args.arch} {args.shape} [{args.mesh}] compile="
          f"{res['compile_s']}s dominant={r['dominant']} "
          f"step={r['step_time_s']:.4f}s frac={r['roofline_fraction']:.3f} "
          f"peak_mem={res['memory']['peak_est_bytes']/2**30:.2f}GiB")


def _cell_path(out, mesh, arch, shape, tag=""):
    suffix = f"__{tag}" if tag else ""
    return os.path.join(out, mesh, f"{arch}__{shape}{suffix}.json")


if __name__ == "__main__":
    main()
