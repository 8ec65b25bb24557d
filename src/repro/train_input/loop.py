"""Shuffle-fed training loop with blob checkpointing and crash/resume.

``train_shuffle_fed`` is the driver that makes the two halves of the
repo one system: an ``AsyncShuffleEngine`` (built fresh and
deterministically by ``engine_factory``) feeds sharded device batches
through ``ShuffleFedInput`` into a real jitted ``make_train_step``;
every ``ckpt_every`` steps the model/optimizer state is checkpointed
through ``BlobCheckpointer`` with the pipeline's committed per-partition
offsets riding in the manifest's ``extra`` — model state and input
progress commit atomically.

Crash/resume contract (the resume-after-AZ-outage scenario in
``benchmarks/train_input.py``):

* ``crash_at_step=s`` raises ``SimulatedCrash`` after step ``s``'s batch
  was fetched but before the step runs — a crash mid-step, with
  uncommitted work in flight;
* a ``resume=True`` run restores the latest manifest, rebuilds the
  engine from the same factory (the virtual-clock replay is
  bit-deterministic), fast-forwards the pipeline past the committed
  prefix, and cross-checks the replayed per-partition offsets against
  the manifest — so the resumed run re-trains exactly the uncommitted
  steps and nothing else;
* records are step-keyed (``train_input.tokens``) and parameters are
  stored as raw bytes, so the resumed loss trajectory is bit-identical
  to an uninterrupted run's.

For a deterministic crash window use a synchronous checkpointer
(``async_upload=False``): with async uploads, a manifest scheduled just
before the crash may or may not become visible — exactly the real-world
ambiguity, but not a reproducible gate.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import latest_step
from repro.distributed.sharding import DEFAULT_RULES, named_shardings
from repro.models import init_params, lm
from repro.train_input.pipeline import ShuffleFedInput
from repro.train_input.tokens import TokenStreamConfig
from repro.training import adamw_init, make_train_step


class SimulatedCrash(RuntimeError):
    """Injected process death mid-step (benchmarks/tests)."""


@dataclasses.dataclass
class ShuffleTrainResult:
    start_step: int              # first step this run trained
    steps: List[int]             # steps actually trained, in order
    losses: List[float]          # float32-exact loss per trained step
    crashed: bool
    offsets_checked: bool        # resume verified offsets vs manifest
    input_stats: Dict[str, float]
    pipeline: ShuffleFedInput
    engine: object


def init_train_state(model_cfg, seed: int = 0, mesh=None):
    """Initial (params, adamw state). With a mesh, both are created in
    place with the shardings ``DEFAULT_RULES`` gives each parameter; the
    optimizer moments follow their parameter."""
    defs = lm.param_defs(model_cfg)
    key = jax.random.key(seed)
    if mesh is None:
        params = init_params(defs, key)
        return params, adamw_init(params)
    p_sh = named_shardings(defs, DEFAULT_RULES, mesh)
    o_sh = {"m": p_sh, "v": p_sh, "count": NamedSharding(mesh, P())}
    params = jax.jit(lambda k: init_params(defs, k), out_shardings=p_sh)(key)
    return params, jax.jit(adamw_init, out_shardings=o_sh)(params)


def train_shuffle_fed(model_cfg, tcfg, mesh, stream: TokenStreamConfig, *,
                      steps: int, engine_factory, ckpt=None,
                      ckpt_every: int = 4, resume: bool = False,
                      crash_at_step: Optional[int] = None,
                      step_fn=None, init_seed: int = 0,
                      pipeline_kwargs: Optional[dict] = None
                      ) -> ShuffleTrainResult:
    """Run (or resume) a shuffle-fed training session. See module doc."""
    engine = engine_factory()
    pipeline = ShuffleFedInput(engine, stream, steps=steps, mesh=mesh,
                               model_cfg=model_cfg,
                               **(pipeline_kwargs or {}))
    pipeline.submit()

    params, opt = init_train_state(model_cfg, init_seed, mesh)
    if step_fn is None:
        # the step's outputs reuse its inputs' buffers; a checkpoint copies
        # the state to the host before save() returns, so nothing reads a
        # donated array
        step_fn = jax.jit(make_train_step(model_cfg, tcfg, mesh=mesh),
                          donate_argnums=(0, 1))

    start, offsets_checked = 0, False
    if resume:
        if ckpt is None:
            raise ValueError("resume=True requires a checkpointer")
        last = latest_step(ckpt.store)
        if last is None:
            raise RuntimeError("resume requested but no committed manifest")
        m = ckpt.manifest(last)
        like = {"params": params, "opt": opt}
        state = ckpt.restore(last, like, shardings=jax.tree.map(
            lambda a: a.sharding, like))
        params, opt = state["params"], state["opt"]
        start = int(m["extra"]["next_step"])
        pipeline.fast_forward(start, m["extra"]["offsets"])
        offsets_checked = True
    elif ckpt is not None:
        # step-0 manifest: a crash before the first periodic checkpoint
        # still restores to a well-defined state
        ckpt.save(0, {"params": params, "opt": opt},
                  extra={"next_step": 0, "offsets": {}})
        ckpt.wait()

    losses: List[float] = []
    trained: List[int] = []
    step_time_s = 0.0
    crashed = False
    try:
        for s in range(start, steps):
            got, batch, _hit = pipeline.next_batch()
            assert got == s, f"pipeline served {got}, trainer at {s}"
            if crash_at_step is not None and s == crash_at_step:
                raise SimulatedCrash(f"injected crash mid-step {s}")
            t0 = time.perf_counter()
            params, opt, metrics = step_fn(params, opt, batch)
            loss = float(metrics["loss"])       # blocks on the step
            step_time_s += time.perf_counter() - t0
            losses.append(loss)
            trained.append(s)
            if ckpt is not None and (s + 1) % ckpt_every == 0:
                pipeline.commit(s + 1)
                ckpt.save(s + 1, {"params": params, "opt": opt},
                          extra={"next_step": s + 1,
                                 "offsets": pipeline.offsets()})
    except SimulatedCrash:
        crashed = True     # process "dies": no final commit, no drain

    if not crashed:
        if ckpt is not None:
            pipeline.commit(steps)
            ckpt.save(steps, {"params": params, "opt": opt},
                      extra={"next_step": steps,
                             "offsets": pipeline.offsets()})
            ckpt.wait()
        pipeline.finish()

    m = engine.metrics
    stats = {
        "records_delivered": m.records_delivered,
        "bytes_delivered": m.bytes_delivered,
        "records_replayed": m.records_replayed,
        "engine_duplicates": m.duplicates_delivered,
        "duplicate_rows_filtered": pipeline.duplicate_rows,
        "skipped_rows": pipeline.skipped_rows,
        "requests": pipeline.requests,
        "prefetch_hits": pipeline.prefetch_hits,
        "overlap_fraction": (pipeline.prefetch_hits / pipeline.requests
                             if pipeline.requests else 0.0),
        "host_wait_s": pipeline.host_wait_s,
        "host_prefetch_s": pipeline.host_prefetch_s,
        "step_time_s": step_time_s,
    }
    return ShuffleTrainResult(start, trained, losses, crashed,
                              offsets_checked, stats, pipeline, engine)
