"""Serving steps: prefill (full-sequence forward) and one-token decode.

``serve_step`` semantics per the assignment: decode shapes lower ONE new
token against a KV cache of ``seq_len`` (the cache is the dominant state).
The batch scheduler in ``repro.serving.scheduler`` drives these steps for
the runnable serving example.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models import lm
from repro.models.common import ModelConfig
from repro.shuffle.api import ShuffleConfig


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    shuffle: ShuffleConfig = ShuffleConfig(mode="dense")
    temperature: float = 0.0  # 0 = greedy


def greedy_sample(logits: jax.Array) -> jax.Array:
    return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)


def make_prefill_step(cfg: ModelConfig, scfg: ServeConfig, mesh=None,
                      hints=None):
    """prefill(params, batch) -> logits (B, S, V). Inference forward."""
    from repro.models.flash import NO_HINTS
    hints = hints or NO_HINTS

    def prefill(params, batch):
        logits, _, _ = lm.forward(cfg, params, batch, mesh=mesh,
                                  shuffle=scfg.shuffle, remat="none",
                                  hints=hints)
        return logits
    return prefill


def make_decode_step(cfg: ModelConfig, scfg: ServeConfig, mesh=None):
    """serve_step(params, cache, batch{tokens,pos}) -> (cache, next, logits)."""
    def serve_step(params, cache, batch):
        logits, new_cache = lm.decode_step(cfg, params, cache, batch,
                                           mesh=mesh, shuffle=scfg.shuffle)
        nxt = greedy_sample(logits)
        return new_cache, nxt, logits
    return serve_step
