"""Serving launcher: ``python -m repro.launch.serve --arch <id> ...``

Batched prefill + autoregressive decode with the KV/state cache; reduced
config on CPU (``--smoke``, default); production shapes are exercised by
the dry-run (decode_32k / long_500k cells).
"""

from __future__ import annotations

import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=48)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import lm
    from repro.models.common import init_params
    from repro.serving import ServeConfig, make_decode_step

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    if not cfg.has_decode:
        raise SystemExit(f"{args.arch} is encoder-only (no decode step)")
    params = init_params(lm.param_defs(cfg), jax.random.key(0))
    B = args.batch
    max_seq = args.prompt_len + args.tokens
    cache = jax.tree.map(
        jnp.zeros_like,
        init_params(lm.cache_defs(cfg, B, max_seq), jax.random.key(1)))
    serve_step = jax.jit(make_decode_step(cfg, ServeConfig()))
    prompts = jax.random.randint(jax.random.key(2), (B, args.prompt_len),
                                 0, cfg.vocab_size)
    nxt = prompts[:, 0]
    t0 = time.time()
    for t in range(max_seq - 1):
        tok = prompts[:, t:t + 1] if t < args.prompt_len else nxt[:, None]
        cache, nxt, _ = serve_step(params, cache,
                                   {"tokens": tok, "pos": jnp.int32(t)})
    jax.block_until_ready(nxt)
    dt = time.time() - t0
    print(f"arch={cfg.name} batch={B} {max_seq - 1} steps in {dt:.2f}s "
          f"({(max_seq - 1) * B / dt:.1f} tok/s)")


if __name__ == "__main__":
    main()
