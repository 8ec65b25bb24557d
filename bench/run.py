"""One run of one benchmark cell on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell trains through the program's served path: the exactly-once
``AsyncShuffleEngine`` feeds ``ShuffleFedInput``, whose batches go into
the donated, jitted ``make_train_step``. This file drives that step
itself (``TimedStep``), as the program's ``train_shuffle_fed`` does, but
without its ``float(loss)`` after every step: in the window up to
``ahead_steps`` steps are dispatched beyond the one waited for, and each
loss is read that many steps late, so that the chip keeps working while
the host stands still.

Set-up (``setup_s``) runs from process start to the end of the warm-up
steps: imports, the engine and its submitted stream (as many steps as
the window could hold were every step at the chip's peak FLOP rate, so
that no speed-up of the program can end the stream early), parameters made on
the device from the seed, the step's compile (from the persistent cache
after a cell's first run) and the warm-up steps, each waited for. The
window then runs for ``--seconds``: once they have passed it sends no
more steps, waits for all it sent, and closes at the end of the last.
All of its steps give ``tokens_per_s`` (their tokens over the window's
length) and ``step_p90_s`` (90th percentile of the intervals between one
step's end and the next's, as the host sees them: the step's device
time, and whatever of the host's work, ``next_batch``, the engine and
the batch check, the queue did not cover). Anything compiled inside the
window fails the run.

``correct`` compares, once the window has closed, the state freed and the
peak memory read:

- every batch the pipeline delivered (warm-up and window) with the stream
  made again from the seed (``stream.py``): exact;
- the first ``compared_steps`` steps (two; the warm-up runs them through
  the same feed and compiled step as the window) with as many
  steps of the plain float32 reference (``reference/``) on the same
  batches: each step's loss (``loss_gap``); per leaf, the first gradient
  as AdamW received it (its first moment after one step over 1 - beta1);
  per leaf, the parameters' change over those steps, read as the next
  step is handed them. Of the gradient and the change, both the gap
  between the two norms (``grad_gap``, ``change_gap``) and the norm of
  the difference, element by element (``grad_diff``, ``change_diff``),
  are compared, each by the worst leaf and relative to the larger of that
  leaf's reference norm and the median leaf's. The reference follows two
  steps and not three so that it takes less time than the window.

With ``--trace 1`` the profiler records the window's first
``trace_steps`` steps, dispatched ahead as the window's and waited for,
and the cell's per-layer metrics are read from that trace
(``trace_reduce.py``, ``program_trace.py``, ``metrics/``) in place of the
end-to-end ones. Such a run also keeps, outside the traced window, what
the readers need of the program: the traced steps' ``dropped_units``
counter (read back once the run has ended), the compiled step's HLO text
(taken after the window, as a map of its operations to the program's
layers) and the program's own host spans in the profile.

The last line of standard output is the result's JSON; the last lines of
standard error are the numbers compared, each beside its limit. Without
a TPU, with fewer chips than the cell asks for, or with a device kind
that ``peaks.json`` does not list, the run exits non-zero and prints no
result.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Optional  # noqa: E402

import numpy as np  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
# the TPU runtime logs under TPU_LOG_DIR, by default a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(tempfile.gettempdir(), "tpu_logs"))
for _p in (str(ROOT / "src"), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from cell import BenchError, Cell, load_cell, metric_reader, peaks_of  # noqa: E402,E501
from stream import step_batch  # noqa: E402

#: the persistent compilation cache: a fixed path inside the checkout,
#: unless ``JAX_COMPILATION_CACHE_DIR`` names one
CACHE_DIR = ROOT / ".jax_cache"
#: where a traced run writes its profile; removed once it has been read
TRACE_DIR = ROOT / ".bench_trace"


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


# --- devices and compiles -----------------------------------------------------

def check_devices(chips: int, require_tpu: bool):
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform "
                         f"{devices[0].platform!r}); no result on another "
                         "platform")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return devices


def enable_compile_cache() -> None:
    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", cache)
    # every program, however small, so that a warm run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileWatch:
    """Counts compile-cache hits and misses, keeps every trace or compile
    that falls inside the measured window, and adds up the seconds the
    garbage collector ran there (a pause that stretches a step)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.in_window = False
        self.hits = self.misses = 0
        self.window_events = []
        self.gc_s = 0.0
        self._gc_t0 = None
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        gc.callbacks.append(self._gc)

    def _gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None and self.in_window:
            self.gc_s += time.perf_counter() - self._gc_t0

    def close(self) -> None:
        gc.callbacks.remove(self._gc)

    def _duration(self, event, duration, **_):
        if self.in_window and event in self.EVENTS:
            self.window_events.append(event)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


# --- host spans in the profiler's trace ----------------------------------------

class Spans:
    """``jax.profiler.TraceAnnotation`` spans opened and closed by name,
    recorded only while tracing."""

    def __init__(self):
        self.on = False
        self.open = {}

    def begin(self, name: str) -> None:
        if self.on and name not in self.open:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
            self.open[name] = ann

    def end(self, name: str) -> None:
        ann = self.open.pop(name, None)
        if ann is not None:
            ann.__exit__(None, None, None)


# --- the engine ------------------------------------------------------------------

def make_engine(eng: dict, stream):
    """The exactly-once engine that feeds training: ``instances`` engine
    instances over ``num_az`` zones, Express One Zone store, one blob
    flushed per step's records, elastic cluster in ``cluster_mode``."""
    from repro.cluster import ElasticCluster
    from repro.core import AsyncShuffleEngine, BlobShuffleConfig, EngineConfig
    from repro.core.stores import ExpressOneZoneStore

    bcfg = BlobShuffleConfig(
        batch_bytes=stream.batch * stream.record_value_bytes,
        max_interval_s=eng["max_interval_s"],
        num_partitions=eng["num_partitions"], num_az=eng["num_az"])
    engine = AsyncShuffleEngine(
        bcfg, EngineConfig(commit_interval_s=eng["commit_interval_s"]),
        n_instances=eng["instances"],
        store=ExpressOneZoneStore(seed=eng["store_seed"],
                                  num_az=eng["num_az"]),
        seed=eng["engine_seed"], exactly_once=eng["exactly_once"])
    ElasticCluster(engine, mode=eng["cluster_mode"])
    return engine


# --- the timed step ----------------------------------------------------------------

class TimedStep:
    """Drives the cell's step over the pipeline's batches. Checks each
    delivered batch, compiles on the first step, reads the first gradient
    after step one and the parameters' change before step ``compared + 1``.

    The warm-up runs one step at a time. The window then keeps up to
    ``ahead`` steps dispatched beyond the one it waits for, so that the
    chip has seconds of work queued while the host stands still; a step's
    loss is read once that many later steps are in flight. When
    ``seconds`` have passed it sends no more, waits for every step sent,
    and closes at the end of the last. A traced run traces the window's
    first ``trace_steps`` steps the same way, waits for them, and goes on
    untraced until the window closes."""

    def __init__(self, step, pipeline, cell: Cell, seed: int,
                 seconds: float, trace: bool, watch: CompileWatch,
                 spans: Spans):
        t = cell.traffic
        self.step, self.pipeline = step, pipeline
        self.seed, self.seconds = seed, seconds
        self.vocab = cell.config["vocab_size"]
        self.batch, self.seq = t["batch"], t["seq_len"]
        self.warm, self.compared = t["warm_steps"], t["compared_steps"]
        if self.warm <= self.compared:
            raise BenchError("warm_steps has to exceed compared_steps")
        self.ahead = t["ahead_steps"]
        self.beta1 = t["opt"]["beta1"]
        self.trace_steps = t["trace_steps"] if trace else 0
        self.watch, self.spans = watch, spans
        self.compiled = None
        self.compile_s = 0.0
        self.n = 0                   # steps dispatched
        self.losses = []             # per step, read in order
        self.ends = []               # host clock at each step's end
        self.open_at = None          # index in ``ends`` of the window start
        self.checked = self.mismatched = 0
        self.p0 = None
        self.names = None
        self.grads = None            # first gradient, by leaf name, host
        self.change = None           # change over the compared steps
        self.traced = None           # (first, last) step index traced
        self.dropped = []            # the traced steps' counter, on device

    def _check_batch(self, batch) -> None:
        want = step_batch(self.seed, self.n, self.vocab, self.batch, self.seq)
        self.checked += 1
        if sorted(batch) != sorted(want) or not all(
                np.array_equal(np.asarray(batch[k]), v)
                for k, v in want.items()):
            self.mismatched += 1

    def _dispatch(self, params, opt):
        """Fetches the next batch and dispatches its step: the step's
        (params, opt, metrics), not waited for."""
        import jax

        self.spans.begin("bench.next_batch")
        got, batch, _ = self.pipeline.next_batch()
        self.spans.end("bench.next_batch")
        if got != self.n:
            raise BenchError(f"the pipeline served step {got} for {self.n}")
        self.spans.begin("bench.check_batch")
        self._check_batch(batch)
        self.spans.end("bench.check_batch")
        n = self.n
        if n == 0:
            from reference.common import leaf_names

            self.names = leaf_names(params)
            self.p0 = jax.device_get(jax.tree.leaves(params))
            t0 = time.perf_counter()
            self.compiled = self.step.lower(params, opt, batch).compile()
            self.compile_s = time.perf_counter() - t0
        elif n == 1:
            # AdamW's first moment after one step is (1 - beta1) x gradient
            scale = np.float32(1.0 / (1.0 - self.beta1))
            self.grads = {k: np.asarray(m, np.float32) * scale for k, m in
                          zip(self.names, jax.device_get(
                              jax.tree.leaves(opt["m"])))}
        if n == self.compared:
            now = jax.device_get(jax.tree.leaves(params))
            self.change = {k: np.asarray(a, np.float32) - b for k, a, b in
                           zip(self.names, now, self.p0)}
            self.p0 = now = None
        self.spans.begin("bench.dispatch")
        if not isinstance(batch["tokens"], jax.Array):
            batch = jax.device_put(batch)
        out = self.compiled(params, opt, batch)
        self.spans.end("bench.dispatch")
        if self.spans.on:
            self.dropped.append(out[2].get("dropped_units"))
        self.n += 1
        return out

    def _wait(self, metrics) -> None:
        """Waits for the step whose ``metrics`` these are (its outputs are
        ready together) and reads its loss."""
        self.spans.begin("bench.block_loss")
        loss = float(metrics["loss"])
        self.spans.end("bench.block_loss")
        self.ends.append(time.perf_counter())
        self.losses.append(loss)

    def _run(self, params, opt, done):
        """Dispatches steps, keeping ``ahead`` beyond the one waited for,
        until ``done()``; then waits for all that were sent."""
        pending = collections.deque()
        while not done():
            params, opt, metrics = self._dispatch(params, opt)
            pending.append(metrics)
            if len(pending) > self.ahead:
                self._wait(pending.popleft())
        while pending:
            self._wait(pending.popleft())
        return params, opt

    def run(self, params, opt) -> None:
        import jax

        for _ in range(self.warm):
            params, opt, metrics = self._dispatch(params, opt)
            self._wait(metrics)
        self.open_at = len(self.ends) - 1
        opened = self.ends[-1]
        self.watch.in_window = True
        if self.trace_steps:
            TRACE_DIR.mkdir(exist_ok=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0   # spans, not every call
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            self.spans.on = True
            self.spans.begin("bench.window")
            last = self.n + self.trace_steps - 1
            self.traced = (self.n, last)
            params, opt = self._run(params, opt, lambda: self.n > last)
            self.spans.end("bench.window")
            self.spans.on = False
            jax.profiler.stop_trace()
        params, opt = self._run(
            params, opt, lambda: time.perf_counter() - opened >= self.seconds)
        self.watch.in_window = False
        jax.block_until_ready((params, opt))


# --- the comparison ---------------------------------------------------------------

def host_steps(losses, grads, change):
    """The program's first steps in the reference's form: losses, and the
    first gradient and the change by leaf name, with their norms."""
    from reference.common import Steps

    names = list(grads)
    return Steps(list(losses),
                 np.array([float(np.linalg.norm(grads[k])) for k in names]),
                 np.array([float(np.linalg.norm(change[k])) for k in names]),
                 names, grads, change)


#: the numbers a run compares besides the exact counts, in order
GAPS = ("loss_gap", "grad_gap", "change_gap", "grad_diff", "change_diff")


def gaps(x, ref, still_leaf_share: float):
    """({number: value}, {number: worst leaf}) of the first steps ``x``
    against the reference's ``ref`` (both ``reference.common.Steps``,
    with their trees).

    ``loss_gap`` is the largest |loss - reference loss| over the compared
    steps. Per leaf, ``grad_gap`` and ``change_gap`` are the gap between
    the two norms of the first gradient and of the steps' change, and
    ``grad_diff`` and ``change_diff`` the norm of the two trees'
    difference; each is taken over the larger of that leaf's reference
    norm and the median leaf's, and the worst leaf is the number. Leaves
    whose reference gradient is under ``still_leaf_share`` of the median
    leaf's are nought to rounding (a key bias under softmax): AdamW moves
    them by round-off alone, so their change is not compared. Router
    state (``ref.state_leaves``) has no gradient to compare; its change
    is compared like any other leaf's."""
    if set(x.leaf_names) != set(ref.leaf_names):
        raise BenchError(f"leaves {sorted(x.leaf_names)} differ from the "
                         f"reference's {sorted(ref.leaf_names)}")
    at = {k: i for i, k in enumerate(x.leaf_names)}
    order = [at[k] for k in ref.leaf_names]
    trained = np.array([k not in ref.state_leaves for k in ref.leaf_names])
    grad_median = float(np.median(ref.grad_norms[trained]))
    moving = ~trained | (ref.grad_norms >= still_leaf_share * grad_median)

    def diff(a, b, include):
        return np.array([float(np.linalg.norm(a[k] - b[k])) if i else 0.0
                         for k, i in zip(ref.leaf_names, include)])
    per_leaf = {
        "grad_gap": (np.abs(x.grad_norms[order] - ref.grad_norms),
                     ref.grad_norms, grad_median, trained),
        "change_gap": (np.abs(x.change_norms[order] - ref.change_norms),
                       ref.change_norms, None, moving),
        "grad_diff": (diff(x.grads, ref.grads, trained), ref.grad_norms,
                      grad_median, trained),
        "change_diff": (diff(x.change, ref.change, moving),
                        ref.change_norms, None, moving),
    }
    values = {"loss_gap": float(max(
        abs(a - b) for a, b in zip(x.losses, ref.losses)))}
    worst = {}
    for key, (gap, norm, median, include) in per_leaf.items():
        median = float(np.median(norm)) if median is None else median
        rel = np.where(include, gap / np.maximum(np.maximum(norm, median),
                                                 1e-30), 0.0)
        j = int(np.argmax(rel))
        values[key] = float(rel[j])
        worst[key] = ref.leaf_names[j]
    return values, worst


def compare(cell: Cell, step: TimedStep, losses, ref):
    """([(name, value, limit)] of every number that decides ``correct``,
    {number: worst leaf})."""
    lim = cell.limits
    nonfinite = sum(1 for v in losses if not math.isfinite(v))
    mine = host_steps(losses[:len(ref.losses)], step.grads, step.change)
    values, worst = gaps(mine, ref, lim["still_leaf_share"])
    return [
        ("batches_differing", step.mismatched, 0),
        ("batches_unchecked", step.n - step.checked, 0),
        ("losses_nonfinite", nonfinite, 0),
    ] + [(k, values[k], lim[k]) for k in GAPS], worst


# --- one run -------------------------------------------------------------------------

def program_config(cell: Cell):
    """(model config, train config, mesh, capacity groups) as the cell
    runs them."""
    import jax
    from jax.sharding import Mesh

    from repro.shuffle import ShuffleConfig
    from repro.training import OptConfig, TrainConfig

    t = cell.traffic
    model_cfg = cell.module("program").model_config(cell.config,
                                                    t["capacity_factor"])
    kw = {"expert_axes": tuple(t["expert_axes"])} if t["expert_axes"] else {}
    shuffle = ShuffleConfig(mode=t["moe_mode"],
                            capacity_factor=t["capacity_factor"],
                            norm_topk=cell.config["norm_topk_prob"], **kw)
    tcfg = TrainConfig(opt=OptConfig(**t["opt"]), shuffle=shuffle)
    mesh = None
    if t["mesh"]:
        shape = tuple(t["mesh"].values())
        devs = np.array(jax.devices()[:math.prod(shape)]).reshape(shape)
        mesh = Mesh(devs, tuple(t["mesh"]))
    return model_cfg, tcfg, mesh, capacity_groups(t)


def capacity_groups(traffic) -> int:
    """Token shards with a capacity each: every chip of the mesh in an
    expert-parallel mode, the whole batch in ``dense`` mode."""
    if traffic["mesh"] and traffic["moe_mode"] != "dense":
        return math.prod(traffic["mesh"].values())
    return 1


def reference_steps(cell: Cell, seed: int, groups: int, device, *,
                    fp8: bool = False, drop_rows: int = 0):
    """The reference's first ``compared_steps`` steps on the cell's
    batches, with their trees on the host (``fp8``: the control;
    ``drop_rows``: a planted fault)."""
    from reference.common import Numerics, Opt, model_from, train_steps

    t = cell.traffic
    model = model_from(cell.module("reference"), cell.config,
                       t["capacity_factor"], groups)
    batches = [step_batch(seed, i, cell.config["vocab_size"], t["batch"],
                          t["seq_len"]) for i in range(t["compared_steps"])]
    return train_steps(model, Opt(**t["opt"]), seed, batches,
                       num=Numerics(fp8=fp8), drop_rows=drop_rows,
                       trees=True, device=device)


def stream_steps(cell: Cell, seconds: float, peaks, chips: int) -> int:
    """Steps of the stream the engine is given: the warm-up, as many as
    the window could hold were each step to run at the chips' peak bf16
    rate (model FLOPs cannot pass it), and the ``ahead_steps`` and one
    more that may be sent before the window's last step ends."""
    t = cell.traffic
    flops = cell.module("flops").step_flops(cell.config, t["batch"],
                                            t["seq_len"])
    fastest_s = flops / (chips * peaks["bf16_flops"])
    return (t["warm_steps"] + math.ceil(seconds / fastest_s)
            + t["ahead_steps"] + 1)


def routed_units(cell: Cell, model_cfg) -> Optional[int]:
    """Routed units of one step: tokens x experts per token x MoE layers;
    None for a model with no MoE layer."""
    moe = model_cfg.moe
    if moe is None:
        return None
    t = cell.traffic
    return (t["batch"] * t["seq_len"] * moe.top_k
            * (model_cfg.num_layers - moe.first_dense_layers))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             peaks=None) -> dict:
    """One run of the cell: its result line. ``peaks`` given skips the look
    for a TPU (the tests' runs on the CPU)."""
    return run_once(cell, seed, seconds, trace, peaks=peaks)[0]


def run_once(cell: Cell, seed: int, seconds: float, trace: bool, *,
             peaks=None, keep: Optional[dict] = None):
    """(result line, the reference's steps with their trees). A traced
    run given ``keep`` leaves there the raw profile (``profile``, bytes)
    and the readers' context (``ctx``)."""
    import jax

    devices = check_devices(cell.chips, require_tpu=peaks is None)
    if peaks is None:
        peaks = peaks_of(devices[0].device_kind)
    used = devices[:cell.chips]
    enable_compile_cache()
    watch = CompileWatch()
    spans = Spans()
    t = cell.traffic

    import repro.train_input as ti
    import repro.training as training

    model_cfg, tcfg, mesh, groups = program_config(cell)
    stream = ti.TokenStreamConfig(vocab_size=cell.config["vocab_size"],
                                  batch=t["batch"], seq_len=t["seq_len"],
                                  seed=seed)
    n_steps = stream_steps(cell, seconds, peaks, len(used))
    pipeline = ti.ShuffleFedInput(make_engine(t["engine"], stream), stream,
                                  steps=n_steps, mesh=mesh,
                                  model_cfg=model_cfg, **t["pipeline"])
    pipeline.submit()
    params, opt = ti.init_train_state(model_cfg, seed, mesh)
    step = TimedStep(
        jax.jit(training.make_train_step(model_cfg, tcfg, mesh=mesh),
                donate_argnums=(0, 1)),
        pipeline, cell, seed, seconds, trace, watch, spans)
    try:
        step.run(params, opt)
    except StopIteration as e:
        raise BenchError(f"the stream of {n_steps} steps ended before the "
                         f"{seconds} s window closed") from e
    del params, opt, pipeline
    step.pipeline = None
    if watch.window_events:
        raise BenchError(f"compiled inside the window: "
                         f"{watch.window_events}")
    watch.close()
    memory_peak = max(int((d.memory_stats() or {}).get(
        "peak_bytes_in_use", 0)) for d in used)
    losses = step.losses
    hlo = step.compiled.as_text() if trace else None
    step.compiled = step.step = None
    gc.collect()

    ends = step.ends[step.open_at:]
    window_s = ends[-1] - ends[0]
    n_window = len(ends) - 1
    intervals = [b - a for a, b in zip(ends, ends[1:])]
    setup_s = step.ends[step.open_at] - _T0

    t_ref = time.perf_counter()
    ref = reference_steps(cell, seed, groups, used[0])
    checks, worst = compare(cell, step, losses, ref)
    step.grads = step.change = None
    ref_s = time.perf_counter() - t_ref
    correct = all(v <= lim for _, v, lim in checks)

    result = {"correct": correct, "attempted": step.n,
              "failed": step.mismatched + sum(
                  1 for v in losses if not math.isfinite(v))}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if trace:
        metrics, extra = read_trace(cell, step, peaks, len(used), hlo,
                                    routed_units(cell, model_cfg), keep)
        device.update(extra["device"])
        result["breakdown"] = extra["breakdown"]
    else:
        values = {
            "tokens_per_s": t["batch"] * t["seq_len"] * n_window / window_s,
            "step_p90_s": statistics.quantiles(intervals, n=10)[-1],
            "setup_s": setup_s,
        }
        metrics = {m["name"]: values[m["name"]] for m in cell.end_to_end}
    result["metrics"] = {k: {"value": v, "unit": units[k]}
                         for k, v in metrics.items()}
    result["device"] = device
    median = statistics.median(intervals)
    result["notes"] = {
        "window_steps": n_window, "window_s": window_s,
        "step_median_s": median, "step_max_s": max(intervals),
        "steps_over_twice_median": sum(1 for v in intervals
                                       if v > 2 * median),
        "gc_in_window_s": watch.gc_s, "compile_s": step.compile_s,
        "cache_hits": watch.hits, "cache_misses": watch.misses,
        "reference_s": ref_s, "losses_compared": losses[:len(ref.losses)],
        "reference_losses": ref.losses, "worst_leaves": worst}
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in checks}
    for name, v, lim in checks:
        log(f"check {name} {v!r} limit {lim!r}")
    return result, ref


def read_trace(cell: Cell, step: TimedStep, peaks, chips: int, hlo: str,
               routed: Optional[int], keep: Optional[dict] = None):
    """Per-layer metrics of the cell from the traced steps. The readers'
    ``ctx`` holds the reduced trace (``trace``), the traced ``steps``,
    ``chips``, ``peaks``, ``step_flops``, and of the program: ``layer_of``
    ({operation: layer} of the compiled step's ``hlo``, for the operations
    that ran), ``program_spans`` (its host spans in the profile),
    ``dropped`` (the traced steps' counter, or None where the step has
    none) and ``routed_units`` (per step, or None), and ``program``, the
    numbers ``program_trace.metrics`` works out from those, once."""
    import jax

    import program_trace
    import trace_reduce

    path = trace_reduce.find_profile(TRACE_DIR)
    profile = jax.profiler.ProfileData.from_file(path)
    if keep is not None:
        keep["profile"] = pathlib.Path(path).read_bytes()
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    red = trace_reduce.reduce_profile(profile)
    ran = {program_trace.instruction(n) for d in red.devices
           for n, _, _ in red.ops[d]}
    dropped = None
    if step.dropped and all(v is not None for v in step.dropped):
        dropped = [int(v) for v in jax.device_get(step.dropped)]
    step.dropped = []
    t = cell.traffic
    first, last = step.traced
    ctx = {
        "trace": red, "steps": last - first + 1, "chips": chips,
        "peaks": peaks,
        "step_flops": cell.module("flops").step_flops(
            cell.config, t["batch"], t["seq_len"]),
        "layer_of": {k: v for k, v in program_trace.layer_of_ops(
            hlo).items() if k in ran},
        "program_spans": program_trace.program_spans(profile),
        "dropped": dropped, "routed_units": routed,
    }
    ctx["program"] = program_trace.metrics(
        red, ctx["program_spans"], ctx["layer_of"], ctx["steps"], dropped,
        routed)
    if keep is not None:
        keep["ctx"] = ctx
    metrics = {}
    for m in cell.per_layer:
        value = metric_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = value
    busy = [red.busy_s(d) for d in red.devices]
    extra = {"device": {"busy_s": sum(busy) / len(busy),
                        "window_s": red.window_s},
             "breakdown": {"device_ops": red.top_ops(10),
                           "idle_gaps": red.idle_gaps(10)}}
    return metrics, extra


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except BenchError as e:
        log(f"bench: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
