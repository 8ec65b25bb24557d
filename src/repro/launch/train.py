"""Training launcher: ``python -m repro.launch.train --arch <id> ...``

On real hardware this runs the full production configuration; on CPU use
``--smoke`` for the reduced config (same code path, small shapes). The
multi-pod distribution config itself is proven by ``repro.launch.dryrun``.
"""

from __future__ import annotations

import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--moe-mode", default="dense",
                    choices=["dense", "direct", "blob"])
    ap.add_argument("--grad-sync", default="auto",
                    choices=["auto", "blob", "blob_int8"])
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()

    import jax
    from repro.checkpoint import FileStore
    from repro.launch.compile_cache import enable_compile_cache
    from repro.configs import get_config
    from repro.data import lm_batch_stream
    from repro.models import lm
    from repro.models.common import init_params
    from repro.runtime import FaultTolerantTrainer
    from repro.shuffle.api import ShuffleConfig
    from repro.training import (OptConfig, TrainConfig, adamw_init,
                                make_train_step)
    from repro.utils import tree_num_params

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    params = init_params(lm.param_defs(cfg), jax.random.key(0))
    opt = adamw_init(params)
    n_dev = jax.device_count()
    mesh = None
    if n_dev > 1:
        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh(devices=n_dev)
    shuf = ShuffleConfig(mode=args.moe_mode) if cfg.moe else \
        ShuffleConfig(mode="dense")
    tcfg = TrainConfig(opt=OptConfig(learning_rate=args.lr,
                                     total_steps=args.steps),
                       microbatches=args.microbatches, shuffle=shuf,
                       grad_sync=args.grad_sync)
    # donated: checkpoints copy the state to the host before save returns
    step = jax.jit(make_train_step(cfg, tcfg, mesh=mesh),
                   donate_argnums=(0, 1))
    batch_fn = lm_batch_stream(cfg.vocab_size, args.batch, args.seq,
                               multimodal=cfg.multimodal,
                               d_model=cfg.d_model)
    print(f"arch={cfg.name} params={tree_num_params(params):,} "
          f"devices={n_dev}")

    ckpt_dir = args.ckpt_dir or f"/tmp/repro_ckpt_{args.arch}"
    trainer = FaultTolerantTrainer(FileStore(ckpt_dir), step, batch_fn,
                                   ckpt_every=args.ckpt_every)
    t0 = time.time()
    params, opt, losses = trainer.run(params, opt, steps=args.steps)
    print(f"done: {args.steps} steps in {time.time() - t0:.1f}s; "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f}; ckpt={ckpt_dir}")


if __name__ == "__main__":
    main()
