"""Benchmark harness — one suite per paper table/figure + the TPU
adaptation and kernel microbenches. Prints ``name,us_per_call,derived``
CSV (and a dry-run roofline summary if results/dryrun exists)."""

from __future__ import annotations

import argparse
import json
import os


def _dryrun_summary(out_dir="results/dryrun"):
    rows = []
    for mesh in ("single", "multi"):
        d = os.path.join(out_dir, mesh)
        if not os.path.isdir(d):
            continue
        for name in sorted(os.listdir(d)):
            with open(os.path.join(d, name)) as f:
                r = json.load(f)
            rl = r["roofline"]
            rows.append((f"dryrun.{mesh}.{r['arch']}.{r['shape']}",
                         r["compile_s"] * 1e6,
                         f"dom={rl['dominant'][:-2]} "
                         f"step={rl['step_time_s']:.3f}s "
                         f"frac={rl['roofline_fraction']:.3f} "
                         f"mem={r['memory']['peak_est_bytes'] / 2**30:.1f}GiB"))
    return rows


SUITES = {
    "all": "every suite below",
    "paper": "paper figure/table reproductions (Figs. 5-9 + model)",
    "async": "async engine latency/cost sweeps",
    "tiers": "storage-tier sweep (S3 Standard / Express / faulty)",
    "micro": "data-plane microbenchmarks: ingest/pack/debatch/format "
             "host lanes + a device-mode Pallas kernel lane (compiled, "
             "block_until_ready; skipped off-accelerator). Writes "
             "BENCH_micro.json, appends BENCH_trajectory.jsonl",
    "elastic": "elasticity: rebalance, exactly-once handoff, autoscale "
               "(writes BENCH_elastic.json)",
    "strategies": "shuffle-strategy head-to-head on one Zipf-skewed "
                  "workload: default vs map-side combining vs push-based "
                  "AZ-local vs two-round merge (writes "
                  "BENCH_strategies.json)",
    "obs": "observability acceptance: per-strategy latency decomposition "
           "with bit-identity, conservation, reconciliation, sketch "
           "accuracy and <10% overhead gates (writes BENCH_obs.json + "
           "TRACE_obs.json)",
    "tpu": "TPU shuffle adaptation",
    "kernels": "Pallas kernel microbenchmarks",
    "train_input": "shuffle-fed MoE train loop: double-buffer overlap, "
                   "resume-after-AZ-outage bit-identity, sharded "
                   "input-spec dryrun (writes BENCH_train_input.json)",
    "dryrun": "roofline summary of results/dryrun",
}


def main() -> None:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
        epilog="suites:\n" + "\n".join(
            f"  {name:<8} {desc}" for name, desc in SUITES.items()))
    ap.add_argument("--suite", default="all", choices=sorted(SUITES),
                    metavar="SUITE",
                    help="one of: " + ", ".join(SUITES) + " (default: all)")
    ap.add_argument("--quick", action="store_true",
                    help="micro/strategies suites: shrunk record/iteration "
                         "counts for a sub-2-minute CI smoke lane (micro "
                         "GB/s figures stay within the ratchet tolerance "
                         "band; strategy gates still hold)")
    args = ap.parse_args()

    rows = []
    if args.suite in ("all", "train_input"):
        # first: its XLA_FLAGS (8 host devices for the pod/data/model
        # mesh) must be set before any other suite initializes jax
        from benchmarks import train_input
        rows += train_input.run(quick=args.quick)  # BENCH_train_input.json
    if args.suite in ("all", "micro"):
        from benchmarks import micro
        rows += micro.run(quick=args.quick)  # also writes BENCH_micro.json
    if args.suite in ("all", "async"):
        from benchmarks import async_engine
        rows += async_engine.run()
    if args.suite in ("all", "tiers"):
        from benchmarks import tier_sweep
        rows += tier_sweep.run()
    if args.suite in ("all", "elastic"):
        from benchmarks import elastic
        rows += elastic.run()  # also writes BENCH_elastic.json
    if args.suite in ("all", "strategies"):
        from benchmarks import strategies
        rows += strategies.run(quick=args.quick)  # BENCH_strategies.json
    if args.suite in ("all", "obs"):
        from benchmarks import obs_report
        rows += obs_report.run(quick=args.quick)  # BENCH_obs + TRACE_obs
    if args.suite in ("all", "paper"):
        from benchmarks import paper_figs as F
        rows += F.fig5_latency_cdf()
        rows += F.fig6_batch_size()
        rows += F.fig7_cost_latency()
        rows += F.fig8_partitions()
        rows += F.fig9_scalability()
        rows += F.model_validation()
    if args.suite in ("all", "tpu"):
        from benchmarks import tpu_shuffle
        rows += tpu_shuffle.run()
    if args.suite in ("all", "kernels"):
        from benchmarks import kernel_bench
        rows += kernel_bench.run()
    if args.suite in ("all", "dryrun"):
        rows += _dryrun_summary()

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
