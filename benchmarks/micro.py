"""Data-plane microbenchmarks: legacy per-record vs columnar hot path.

Measures, in one run (so the comparison is apples-to-apples):

  * **ingest** — records/s through ``Batcher.process`` (per-``Record``
    loop: scalar FNV-1a, per-record serialize, dict churn) vs
    ``Batcher.ingest`` (vectorized FNV-1a over the key arena, one
    argsort, one serialized chunk per destination partition), and
    asserts the finalized blob payloads are **bit-identical**;
  * **pack** — GB/s through the pack hot path. The headline lane is the
    host fast path (``blob_pack_fused_host`` / ``compress_pack_fused_
    host``: numpy sorted-order + block copies, arena-reused output) —
    the path a CPU deployment actually runs. The jitted XLA oracle lane
    is kept alongside for trajectory continuity with earlier runs;
  * **pack (device)** — real ``jax.jit`` Pallas timing with
    ``block_until_ready`` across the ``SWEEP_ROW_TILES`` tile
    geometries, **skipped gracefully off-accelerator** (interpret-mode
    Pallas timings are meaningless as throughput, so the lane only runs
    on tpu/gpu backends);
  * **debatch** — bytes/s extracting partitions from a blob payload,
    legacy ``extract`` (per-``Record``) vs columnar ``extract_batch``
    (memoryview slice + vectorized arena gather);
  * **format** — columnar-v2 encode/decode GB/s on the same Zipf blob,
    the compressed ratio, and $/logical-GiB per storage tier with and
    without compression (request charges fixed, byte charges scaled).

**Byte accounting:** every GB/s figure in BENCH_micro.json is over
**logical (pre-compression) bytes** — the serialized wire bytes for the
format lanes, rows × features × itemsize for the pack lanes — so raw
and compressed paths are directly comparable and a codec cannot "speed
up" by shrinking its own denominator.

Writes ``BENCH_micro.json`` (every field documented under its ``_doc``
key, so the CI gates are self-describing) and appends one JSON line per
run to ``BENCH_trajectory.jsonl`` so the throughput trajectory across
runs/commits is recoverable. ``quick=True`` shrinks record counts and
iteration counts for a sub-2-minute CI smoke lane; GB/s figures are
size-stable enough for the ratchet's tolerance band.

Returns ``(name, us_per_call, derived)`` rows for ``benchmarks.run``.
"""

from __future__ import annotations

import json
import time
from typing import List, Tuple

import numpy as np

from repro.core.batcher import Batcher, BlobShuffleConfig
from repro.core.blob import extract, extract_batch
from repro.core.cache import DistributedCache
from repro.core.recordbatch import default_partitioner_batch
from repro.core.records import default_partitioner
from repro.core.stores import SimulatedS3
from repro.core.workload import WorkloadConfig, generate_batch

Row = Tuple[str, float, str]

N_RECORDS = 50_000
N_RECORDS_QUICK = 10_000
RECORD_BYTES = 256
NUM_PARTITIONS = 64

#: pack-lane geometry: (rows, features, bins, capacity). Quick mode
#: keeps the full geometry on the host/jnp lanes (they are vectorized —
#: the full sweep costs single-digit seconds — and shrinking the arrays
#: shifts GB/s out of the ratchet's tolerance band); only the device
#: lane, where compile time dominates, uses the quick shape.
PACK_SHAPE = (16384, 512, 64, 512)
PACK_SHAPE_QUICK = (4096, 512, 64, 128)

#: every BENCH_micro.json field, documented where the numbers are made —
#: written into the JSON itself under "_doc" so the gates in CI (and the
#: ratchet baseline) are self-describing
FIELD_DOCS = {
    "records": "records per ingest iteration (quick mode uses fewer)",
    "quick": "true when the run used the --quick smoke geometry",
    "records_s_ingest_legacy":
        "records/s through Batcher.process (per-Record scalar loop)",
    "records_s_ingest_columnar":
        "records/s through Batcher.ingest (vectorized columnar path)",
    "ingest_speedup": "records_s_ingest_columnar / records_s_ingest_legacy",
    "payload_bit_identical":
        "legacy and columnar ingest produced byte-identical blob payloads "
        "and notifications (correctness gate, must stay true)",
    "blobs_s_pack": "blobs/s through the host pack fast path",
    "pack_gb_s":
        "GB/s of logical input bytes (rows*features*itemsize) through "
        "blob_pack_fused_host with a reused output arena — the CPU "
        "deployment pack path (RATCHETED)",
    "pack_gb_s_v2":
        "GB/s of logical input bytes through compress_pack_fused_host "
        "(quantize-before-gather + int8 gathers, reused arenas)",
    "pack_gb_s_jnp":
        "GB/s through the jitted XLA oracle pack (pre-PR-7 headline lane, "
        "kept for trajectory continuity)",
    "pack_gb_s_v2_jnp":
        "GB/s through the jitted XLA oracle compress+pack",
    "pack_v2_out_bytes_ratio":
        "compressed pack output bytes / raw pack output bytes "
        "(int8 codes + f32 scale vs bf16 rows)",
    "bytes_s_debatch_legacy": "payload bytes/s via per-Record extract",
    "bytes_s_debatch": "payload bytes/s via columnar extract_batch",
    "v2_encode_gb_s":
        "GB/s of logical wire bytes through ColumnarV2.encode_block "
        "(RATCHETED)",
    "v2_decode_gb_s":
        "GB/s of logical wire bytes recovered by ColumnarV2.decode_block",
    "v2_compressed_ratio": "encoded block bytes / logical wire bytes",
    "cost_per_gib_raw_standard":
        "$/logical-GiB shuffled, raw blobs on S3 Standard",
    "cost_per_gib_v2_standard": "same with columnar-v2 compression",
    "cost_per_gib_raw_express-one-zone":
        "$/logical-GiB shuffled, raw blobs on S3 Express One Zone",
    "cost_per_gib_v2_express-one-zone":
        "same with columnar-v2 compression",
    "device_lane":
        "why the device-mode kernel lane did not run (absent when it did)",
    "device_backend": "jax backend the device lane ran on (tpu/gpu)",
    "device_pack_row_tile_gb_s":
        "row_tile -> GB/s sweep of blob_pack_pallas, compiled "
        "(interpret=False), block_until_ready timing",
    "device_best_row_tile": "argmax of device_pack_row_tile_gb_s",
    "device_pack_gb_s": "GB/s of the best row_tile config",
    "device_pack_v2_gb_s":
        "GB/s of compress_pack_fused_pallas at the best row_tile",
}


def _make_batcher(name: str):
    """Single-AZ batcher with an infinite batch size: exactly one blob per
    flush, captured by the uploader hook (no store writes on the clock)."""
    store = SimulatedS3(seed=0)
    cache = DistributedCache(0, 1, 1 << 30, store)
    blobs = []
    b = Batcher(
        BlobShuffleConfig(batch_bytes=1 << 62, num_partitions=NUM_PARTITIONS,
                          num_az=1),
        lambda p: 0,
        lambda k: default_partitioner(k, NUM_PARTITIONS),
        cache,
        uploader=lambda blob, notes, counts, now: blobs.append((blob, notes)),
        name=name,
        partitioner_batch=lambda bt: default_partitioner_batch(
            bt, NUM_PARTITIONS))
    return b, blobs


def _best_of(f, iters: int = 3) -> float:
    """Best-of-N wall time (fresh state per iteration, first run warms
    pages/caches) — robust against transient machine load in CI."""
    return min(f() for _ in range(iters))


def bench_ingest(quick: bool = False) -> Tuple[List[Row], dict]:
    n_records = N_RECORDS_QUICK if quick else N_RECORDS
    wl = WorkloadConfig(arrival_rate=n_records, duration_s=1.0,
                        record_bytes=RECORD_BYTES, key_skew=0.5, seed=7)
    _, batch = generate_batch(wl)
    records = batch.to_records()
    n = len(records)

    def run_legacy() -> float:
        legacy, blobs = _make_batcher("m")
        t0 = time.perf_counter()
        for r in records:
            legacy.process(r, 0.0)
        dt = time.perf_counter() - t0
        legacy.flush_all(0.0)
        run_legacy.blobs = blobs
        return dt

    def run_columnar() -> float:
        columnar, blobs = _make_batcher("m")
        batch.partitions = None        # don't amortize across iterations
        t0 = time.perf_counter()
        columnar.ingest(batch, 0.0)
        dt = time.perf_counter() - t0
        columnar.flush_all(0.0)
        run_columnar.blobs = blobs
        return dt

    iters = 2 if quick else 3
    legacy_s = _best_of(run_legacy, iters)
    col_s = _best_of(run_columnar, iters)
    legacy_blobs, col_blobs = run_legacy.blobs, run_columnar.blobs

    assert len(legacy_blobs) == len(col_blobs) == 1
    bit_identical = (
        bytes(legacy_blobs[0][0].payload) == bytes(col_blobs[0][0].payload)
        and legacy_blobs[0][1] == col_blobs[0][1])
    assert bit_identical, "legacy vs columnar blob payloads diverged"

    legacy_rps = n / legacy_s
    col_rps = n / col_s
    rows = [
        ("micro.ingest_legacy", legacy_s / n * 1e6,
         f"{legacy_rps:,.0f}rec/s"),
        ("micro.ingest_columnar", col_s / n * 1e6,
         f"{col_rps:,.0f}rec/s speedup={col_rps / legacy_rps:.1f}x"),
    ]
    data = {
        "records": n,
        "records_s_ingest_legacy": legacy_rps,
        "records_s_ingest_columnar": col_rps,
        "ingest_speedup": col_rps / legacy_rps,
        "payload_bit_identical": bool(bit_identical),
    }
    return rows, data


def _pack_inputs(quick: bool):
    import jax
    T, d, bins, cap = PACK_SHAPE_QUICK if quick else PACK_SHAPE
    x = jax.random.normal(jax.random.key(2), (T, d), jax.numpy.bfloat16)
    keys = jax.random.randint(jax.random.key(3), (T,), 0, bins)
    return T, d, bins, cap, x, keys


def bench_pack(quick: bool = False) -> Tuple[List[Row], dict]:
    import jax
    from repro.kernels.blob_codec.host import compress_pack_fused_host
    from repro.kernels.blob_codec.ops import compress_pack_fused
    from repro.kernels.blob_pack.host import blob_pack_fused_host
    from repro.kernels.blob_pack.ops import blob_pack_fused

    # full geometry even in quick mode: the lanes are vectorized, so the
    # run stays fast and the GB/s stay comparable to the full baseline
    T, d, bins, cap, x, keys = _pack_inputs(quick=False)
    logical = T * d * x.dtype.itemsize
    x_np = np.asarray(x)
    keys_np = np.asarray(keys)
    iters = 3 if quick else 5

    # best-of-N per-call times, like _best_of: a throughput-capability
    # number should not be dragged down by a transient load spike on a
    # shared runner mid-loop
    def timed(fn):
        jax.block_until_ready(fn())      # compile/warm
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            best = min(best, time.perf_counter() - t0)
        return best

    def timed_host(fn):
        fn()                             # warm pages
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    # headline lane: host fast path with steady-state arena reuse
    arena = np.zeros((bins, cap, d), x_np.dtype)
    per_call = timed_host(lambda: blob_pack_fused_host(
        x_np, keys_np, num_bins=bins, capacity=cap, out=arena))
    q_arena = np.zeros((bins, cap, d), np.int8)
    s_arena = np.ones((bins, cap), np.float32)
    per_call_v2 = timed_host(lambda: compress_pack_fused_host(
        x_np, keys_np, num_bins=bins, capacity=cap,
        out=(q_arena, s_arena)))

    # jitted XLA oracle lane (the pre-PR-7 headline, kept for trajectory
    # continuity across BENCH_trajectory.jsonl)
    f_pack = jax.jit(lambda x, k: blob_pack_fused(
        x, k, num_bins=bins, capacity=cap, use_pallas=False)[0])
    f_codec = jax.jit(lambda x, k: compress_pack_fused(
        x, k, num_bins=bins, capacity=cap, use_pallas=False)[0])
    per_jnp = timed(lambda: f_pack(x, keys))
    per_jnp_v2 = timed(lambda: f_codec(x, keys))

    blobs_s = bins / per_call
    gbps = logical / per_call / 1e9
    gbps_v2 = logical / per_call_v2 / 1e9
    # int8 codes + f32 scale per row vs bf16 rows
    out_ratio = (cap * d + cap * 4) / (cap * d * 2)
    rows = [
        ("micro.blob_pack_host", per_call * 1e6,
         f"{blobs_s:,.0f}blobs/s {gbps:.2f}GB/s (host fast path)"),
        ("micro.compress_pack_host", per_call_v2 * 1e6,
         f"{bins / per_call_v2:,.0f}blobs/s {gbps_v2:.2f}GB/s "
         f"out_bytes={out_ratio:.2f}x (host fast path)"),
        ("micro.blob_pack_fused", per_jnp * 1e6,
         f"{logical / per_jnp / 1e9:.2f}GB/s (jnp path)"),
        ("micro.compress_pack_fused", per_jnp_v2 * 1e6,
         f"{logical / per_jnp_v2 / 1e9:.2f}GB/s (jnp path)"),
    ]
    return rows, {"blobs_s_pack": blobs_s, "pack_gb_s": gbps,
                  "pack_gb_s_v2": gbps_v2,
                  "pack_gb_s_jnp": logical / per_jnp / 1e9,
                  "pack_gb_s_v2_jnp": logical / per_jnp_v2 / 1e9,
                  "pack_v2_out_bytes_ratio": out_ratio}


def bench_pack_device(quick: bool = False) -> Tuple[List[Row], dict]:
    """Device-mode kernel lane: compiled (interpret=False) Pallas timing
    with ``block_until_ready`` across the row-tile sweep. Interpret-mode
    timings measure the Python emulator, not the kernel, so off
    accelerator the lane reports itself skipped instead of lying."""
    import jax

    backend = jax.default_backend()
    if backend not in ("tpu", "gpu"):
        return ([("micro.pack_device", 0.0,
                  f"skipped (backend={backend}; needs tpu/gpu)")],
                {"device_lane": f"skipped (backend={backend})"})

    from repro.kernels.blob_codec.kernel import compress_pack_fused_pallas
    from repro.kernels.blob_pack.kernel import (SWEEP_ROW_TILES,
                                                blob_pack_pallas)
    from repro.shuffle.binning import sorted_order

    T, d, bins, cap, x, keys = _pack_inputs(quick)
    logical = T * d * x.dtype.itemsize
    order, starts, counts = jax.block_until_ready(sorted_order(keys, bins))
    iters = 3 if quick else 10

    def timed(fn):
        jax.block_until_ready(fn())      # compile
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn()
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters

    sweep = {}
    for rt in SWEEP_ROW_TILES:
        per = timed(lambda: blob_pack_pallas(
            x, order, starts, counts, capacity=cap, interpret=False,
            row_tile=rt))
        sweep[str(rt)] = logical / per / 1e9
    best = max(sweep, key=sweep.get)
    per_v2 = timed(lambda: compress_pack_fused_pallas(
        x, order, starts, counts, capacity=cap, interpret=False,
        row_tile=int(best)))
    v2_gbps = logical / per_v2 / 1e9
    rows = [
        ("micro.pack_device", logical / sweep[best] / 1e9 * 1e6,
         f"{sweep[best]:.2f}GB/s best row_tile={best} on {backend} " +
         " ".join(f"rt{t}={g:.2f}" for t, g in sweep.items())),
        ("micro.pack_device_v2", per_v2 * 1e6,
         f"{v2_gbps:.2f}GB/s fused compress+pack at row_tile={best}"),
    ]
    return rows, {"device_backend": backend,
                  "device_pack_row_tile_gb_s": sweep,
                  "device_best_row_tile": int(best),
                  "device_pack_gb_s": sweep[best],
                  "device_pack_v2_gb_s": v2_gbps}


def bench_debatch(quick: bool = False) -> Tuple[List[Row], dict]:
    n_records = N_RECORDS_QUICK if quick else N_RECORDS
    wl = WorkloadConfig(arrival_rate=n_records, duration_s=1.0,
                        record_bytes=RECORD_BYTES, key_skew=0.5, seed=11)
    _, batch = generate_batch(wl)
    b, blobs = _make_batcher("d")
    b.ingest(batch, 0.0)
    b.flush_all(0.0)
    blob, notes = blobs[0]
    total = blob.size
    counted = {}

    def run_legacy() -> float:
        t0 = time.perf_counter()
        counted["legacy"] = sum(
            len(extract(blob.payload, nt.byte_range)) for nt in notes)
        return time.perf_counter() - t0

    def run_columnar() -> float:
        t0 = time.perf_counter()
        counted["columnar"] = sum(
            len(extract_batch(blob.payload, nt.byte_range)) for nt in notes)
        return time.perf_counter() - t0

    iters = 2 if quick else 3
    legacy_s = _best_of(run_legacy, iters)
    col_s = _best_of(run_columnar, iters)
    assert counted["legacy"] == counted["columnar"] == len(batch)

    rows = [
        ("micro.debatch_legacy", legacy_s * 1e6,
         f"{total / legacy_s / 1e6:,.0f}MB/s"),
        ("micro.debatch_columnar", col_s * 1e6,
         f"{total / col_s / 1e6:,.0f}MB/s speedup={legacy_s / col_s:.1f}x"),
    ]
    data = {
        "bytes_s_debatch_legacy": total / legacy_s,
        "bytes_s_debatch": total / col_s,
    }
    return rows, data


def bench_format(quick: bool = False) -> Tuple[List[Row], dict]:
    """Columnar-v2 encode/decode throughput + $/logical-GiB with and
    without compression, on the same Zipf-skewed blob the other
    microbenchmarks use. GB/s figures are over the **logical wire
    bytes** in both directions (see module docstring). Quick mode keeps
    the full blob (encode/decode are vectorized and fast; a smaller blob
    would drift the ratcheted v2_encode_gb_s out of tolerance)."""
    from repro.core.costs import TIERS, shuffle_cost_per_logical_gib
    from repro.core.formats import COLUMNAR_V2, detect_format

    wl = WorkloadConfig(arrival_rate=N_RECORDS, duration_s=1.0,
                        record_bytes=RECORD_BYTES, key_skew=0.5, seed=7)
    _, batch = generate_batch(wl)
    wire = bytes(batch.serialize_rows())
    iters = 2 if quick else 3

    def run_encode() -> float:
        t0 = time.perf_counter()
        run_encode.out = COLUMNAR_V2.encode_block([wire])
        return time.perf_counter() - t0

    enc_s = _best_of(run_encode, iters)
    block = run_encode.out[0]
    ratio = len(block) / len(wire)
    assert detect_format(block) is COLUMNAR_V2

    def run_decode() -> float:
        t0 = time.perf_counter()
        run_decode.out = COLUMNAR_V2.decode_block(block)
        return time.perf_counter() - t0

    dec_s = _best_of(run_decode, iters)
    assert run_decode.out == wire, "v2 round-trip diverged"

    data = {
        "v2_encode_gb_s": len(wire) / enc_s / 1e9,
        "v2_decode_gb_s": len(wire) / dec_s / 1e9,
        "v2_compressed_ratio": ratio,
    }
    for tier in ("standard", "express-one-zone"):
        prices = TIERS[tier]
        raw = shuffle_cost_per_logical_gib(prices)
        v2 = shuffle_cost_per_logical_gib(prices, compressed_ratio=ratio)
        data[f"cost_per_gib_raw_{tier}"] = raw
        data[f"cost_per_gib_v2_{tier}"] = v2
    rows = [
        ("micro.format_v2_encode", enc_s * 1e6,
         f"{data['v2_encode_gb_s']:.2f}GB/s ratio={ratio:.3f}"),
        ("micro.format_v2_decode", dec_s * 1e6,
         f"{data['v2_decode_gb_s']:.2f}GB/s"),
        ("micro.format_v2_cost", 0.0,
         " ".join(f"{t}=${data[f'cost_per_gib_v2_{t}']:.4f}"
                  f"(raw ${data[f'cost_per_gib_raw_{t}']:.4f})/GiB"
                  for t in ("standard", "express-one-zone"))),
    ]
    return rows, data


def _append_trajectory(data: dict, path: str) -> None:
    """One JSON line per benchmark run: wall-clock timestamp + every
    numeric field. The file is append-only and git-ignored — CI uploads
    it as an artifact, locally it accumulates the machine's history (see
    README "how to read BENCH_trajectory.jsonl")."""
    rec = {"ts": time.time(), **{k: v for k, v in data.items()
                                 if not k.startswith("_")}}
    with open(path, "a") as f:
        json.dump(rec, f, sort_keys=True)
        f.write("\n")


def run(json_path: str = "BENCH_micro.json", quick: bool = False,
        trajectory_path: str = "BENCH_trajectory.jsonl") -> List[Row]:
    rows: List[Row] = []
    data: dict = {"quick": quick}
    for bench in (bench_ingest, bench_pack, bench_pack_device,
                  bench_debatch, bench_format):
        r, d = bench(quick=quick)
        rows.extend(r)
        data.update(d)
    data["_doc"] = {k: FIELD_DOCS[k] for k in data if k in FIELD_DOCS}
    with open(json_path, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")
    _append_trajectory(data, trajectory_path)
    return rows


if __name__ == "__main__":
    print("name,us_per_call,derived")
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}")
