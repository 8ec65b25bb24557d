"""Shuffle-fed training suite: the BlobShuffle engine as the input
pipeline for a real MoE train loop (ROADMAP item 5).

Three lanes, one scenario (run via ``python -m benchmarks.run --suite
train_input [--quick]``):

* **pipeline** — an uninterrupted shuffle-fed run: step-keyed records
  flow source → Batcher → blob → ExpressOneZone store → notification
  log (ElasticCluster) → Debatcher → ``ShuffleFedInput`` → sharded
  device batches → jitted ``make_train_step``; reports the overlap
  fraction of the double buffer and the loss trajectory (gate:
  decreasing).
* **resume** — the same engine factory with an **AZ outage** on the
  virtual clock (every worker in AZ 1 fail-stops; partitions reassign
  cross-AZ and uncommitted notifications replay) and a ``SimulatedCrash``
  mid-step after it; the resumed run restores the manifest from the
  tiered checkpoint store (``BlobCheckpointer`` over a
  ``FaultyStore``-wrapped ``SimulatedS3``), fast-forwards the replayed
  engine past the committed offsets, and must reproduce the
  uninterrupted run's loss trajectory **bit-identically** with zero
  skipped and zero re-trained batches (gates).
* **dryrun** — ``train_input.specs_check``: the sharded input specs of
  the shuffle-fed batch validate against ``launch.specs`` +
  ``distributed.sharding`` and lower through the real train step.

Writes ``BENCH_train_input.json`` (fields documented under ``_doc``).
"""

from __future__ import annotations

import os

# 8 fake host devices for the (pod=2, data=2, model=2) mesh; must be set
# before the first jax import (run.py imports this suite before any
# other so the flag wins even under --suite all)
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import json                                                      # noqa: E402
from typing import List, Tuple                                   # noqa: E402

Row = Tuple[str, float, str]

#: written into the JSON under "_doc" so CI gates and docs/benchmarks.md
#: stay in sync with the producer
FIELD_DOCS = {
    "quick": "true when the run used the --quick smoke geometry",
    "arch": "model architecture (smoke-scaled) under training",
    "devices": "host device count backing the mesh",
    "mesh": "mesh axis sizes the batch is sharded over",
    "steps": "training steps per run",
    "ckpt_every": "checkpoint cadence (steps per manifest commit)",
    "crash_at_step": "step at which the interrupted run dies mid-step",
    "resume_step": "first step the resumed run re-trains (last manifest)",
    "az_outage_at_s": "virtual time when every worker in one AZ "
                      "fail-stops (partitions reassign cross-AZ, "
                      "uncommitted notifications replay)",
    "overlap_fraction": "fraction of batches already staged when the "
                        "trainer asked — the double-buffer hit rate",
    "records_delivered": "records the engine delivered (uninterrupted "
                         "run)",
    "records_replayed": "records replayed by commit-protocol recovery "
                        "across the AZ outage (interrupted+resumed runs)",
    "duplicate_rows_filtered": "replayed/duplicate (step,row) deliveries "
                               "the consumer filtered (exactly-once "
                               "consumption)",
    "loss_first": "loss at step 0",
    "loss_last": "loss at the final step",
    "loss_decreasing": "GATE: mean of last 3 losses < mean of first 3",
    "resume_loss_bit_identical": "GATE: committed-prefix + resumed losses "
                                 "equal the uninterrupted trajectory "
                                 "bit-for-bit",
    "batches_skipped": "GATE(=0): steps trained by neither the committed "
                       "prefix nor the resumed run",
    "batches_duplicated": "GATE(=0): steps trained more than once across "
                          "the committed timeline",
    "offsets_match_manifest": "GATE: per-partition offsets recomputed by "
                              "the resume replay equal the checkpoint "
                              "manifest's",
    "ckpt_retries": "StoreError retries absorbed by the tiered "
                    "checkpoint store (fault injection was live)",
    "dryrun_input_specs_ok": "GATE: sharded input specs validate and "
                             "lower through the real train step",
    "input_specs": "per-input global shape / PartitionSpec / per-device "
                   "shard shape from the dryrun lane",
}


def run(quick: bool = False) -> List[Row]:
    import jax
    import numpy as np

    from repro.cluster import ElasticCluster
    from repro.configs import get_config
    from repro.core import AsyncShuffleEngine, BlobShuffleConfig, \
        EngineConfig
    from repro.core.stores import ExpressOneZoneStore, FaultyStore, \
        SimulatedS3
    from repro.checkpoint import BlobCheckpointer, TieredCheckpointStore
    from repro.launch import make_test_mesh
    from repro.shuffle import ShuffleConfig
    from repro.train_input import (TokenStreamConfig, train_shuffle_fed,
                                   validate_device_batch, lower_train_step,
                                   input_spec_report)
    from repro.training import OptConfig, TrainConfig, make_train_step

    n_dev = jax.device_count()
    mesh = make_test_mesh(devices=8 if n_dev >= 8 else
                          (4 if n_dev >= 4 else n_dev))
    multi_pod = "pod" in mesh.axis_names
    arch = "deepseek-v2-lite-16b"
    cfg = get_config(arch, smoke=True)
    steps = 12 if quick else 16
    ckpt_every = 4
    crash_at = steps - 6           # mid-step crash after the outage
    # outage lands between two commit ticks (0.15s cadence) so a batch of
    # notifications is genuinely uncommitted and must replay cross-AZ
    outage_t = 0.30
    stream = TokenStreamConfig(vocab_size=cfg.vocab_size, batch=8,
                               seq_len=32, seed=0)

    shuf = ShuffleConfig(mode="blob" if multi_pod else "dense",
                         token_axes=("pod", "data", "model"),
                         expert_axes=("pod", "model"),
                         capacity_factor=2.0)
    tcfg = TrainConfig(opt=OptConfig(learning_rate=3e-3, warmup_steps=5,
                                     total_steps=steps),
                       shuffle=shuf,
                       grad_sync="blob_int8" if multi_pod else "auto",
                       grad_sync_blob_bytes=1 << 16)
    step_fn = jax.jit(make_train_step(cfg, tcfg, mesh=mesh))

    def make_engine():
        """Fresh, deterministic engine: zonal express tier behind mild
        fault injection, elastic cluster with an AZ-1 outage mid-stream."""
        store = FaultyStore(ExpressOneZoneStore(seed=7, num_az=3),
                            seed=11, transient_p=0.02)
        bcfg = BlobShuffleConfig(batch_bytes=4096, max_interval_s=0.02,
                                 num_partitions=9, num_az=3)
        eng = AsyncShuffleEngine(bcfg, EngineConfig(commit_interval_s=0.15),
                                 n_instances=3, store=store, seed=5,
                                 exactly_once=True)
        cluster = ElasticCluster(eng, mode="cooperative")
        cluster.az_outage_at(outage_t, 1)
        return eng

    def make_ckpt(store):
        # sync uploads: a deterministic crash window for the resume gate
        return BlobCheckpointer(TieredCheckpointStore(store),
                                async_upload=False)

    common = dict(steps=steps, engine_factory=make_engine,
                  ckpt_every=ckpt_every, step_fn=step_fn,
                  pipeline_kwargs={"step_interval_s": 0.05,
                                   "prefetch_steps": 2})

    # -- lane 1: uninterrupted run -----------------------------------------
    base = train_shuffle_fed(cfg, tcfg, mesh, stream,
                             ckpt=make_ckpt(
                                 FaultyStore(SimulatedS3(seed=21), seed=23,
                                             transient_p=0.05)),
                             **common)
    st = base.input_stats
    losses = base.losses
    loss_decreasing = (float(np.mean(losses[-3:]))
                       < float(np.mean(losses[:3])))

    # -- lane 2: crash mid-step after the AZ outage, then resume -----------
    ckpt_store = FaultyStore(SimulatedS3(seed=31), seed=33,
                             transient_p=0.05)
    ckpt = make_ckpt(ckpt_store)
    broken = train_shuffle_fed(cfg, tcfg, mesh, stream, ckpt=ckpt,
                               crash_at_step=crash_at, **common)
    assert broken.crashed
    resumed = train_shuffle_fed(cfg, tcfg, mesh, stream, ckpt=ckpt,
                                resume=True, **common)
    resume_step = resumed.start_step
    committed = broken.steps[:resume_step]        # steps the manifest covers
    timeline = committed + resumed.steps
    spliced = broken.losses[:resume_step] + resumed.losses
    bit_identical = (timeline == list(range(steps))
                     and spliced == losses)
    skipped = len(set(range(steps)) - set(timeline))
    duplicated = sum(n - 1 for n in
                     np.unique(timeline, return_counts=True)[1] if n > 1)

    # -- lane 3: dryrun input-spec validation ------------------------------
    # validate a real device batch from a fresh pipeline (base consumed its
    # stream); one step is enough
    from repro.train_input import ShuffleFedInput
    p3 = ShuffleFedInput(make_engine(), stream, steps=1, mesh=mesh,
                         model_cfg=cfg, step_interval_s=0.05)
    p3.submit()
    _, batch, _ = p3.next_batch()
    report = validate_device_batch(batch, cfg, p3.shape, mesh)
    lower_train_step(cfg, tcfg, mesh, p3.shape)
    dryrun_ok = report == input_spec_report(cfg, p3.shape, mesh)

    data = {
        "quick": quick,
        "arch": arch,
        "devices": n_dev,
        "mesh": dict(mesh.shape),
        "steps": steps,
        "ckpt_every": ckpt_every,
        "crash_at_step": crash_at,
        "resume_step": resume_step,
        "az_outage_at_s": outage_t,
        "overlap_fraction": st["overlap_fraction"],
        "records_delivered": st["records_delivered"],
        "records_replayed": (broken.input_stats["records_replayed"]
                             + resumed.input_stats["records_replayed"]),
        "duplicate_rows_filtered": (
            st["duplicate_rows_filtered"]
            + broken.input_stats["duplicate_rows_filtered"]
            + resumed.input_stats["duplicate_rows_filtered"]),
        "loss_first": losses[0],
        "loss_last": losses[-1],
        "loss_decreasing": loss_decreasing,
        "resume_loss_bit_identical": bit_identical,
        "batches_skipped": skipped,
        "batches_duplicated": int(duplicated),
        "offsets_match_manifest": resumed.offsets_checked,
        "ckpt_retries": ckpt.store.retries,
        "dryrun_input_specs_ok": bool(dryrun_ok),
        "input_specs": report,
    }
    data["_doc"] = {k: FIELD_DOCS[k] for k in data if k in FIELD_DOCS}
    with open("BENCH_train_input.json", "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")

    rows: List[Row] = [
        ("train_input.pipeline", 0.0,
         f"overlap={st['overlap_fraction']:.2f} "
         f"loss {losses[0]:.3f}->{losses[-1]:.3f} "
         f"decreasing={loss_decreasing}"),
        ("train_input.resume", 0.0,
         f"bit_identical={bit_identical} skipped={skipped} "
         f"dup={duplicated} resume_step={resume_step} "
         f"replayed={data['records_replayed']} "
         f"offsets_ok={resumed.offsets_checked}"),
        ("train_input.dryrun", 0.0,
         f"specs_ok={dryrun_ok} "
         f"tokens={report['tokens']['partition_spec']}"
         f"->{tuple(report['tokens']['per_device_shape'])}"),
    ]
    return rows


if __name__ == "__main__":
    print("name,us_per_call,derived")
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}")
