"""What a profiler trace says about the program's own layers, spans and
counters, beside what ``trace_reduce.py`` reads.

The program marks its train step and its input pipeline for the profiler
(``repro.obs.scopes``):

- device scopes (``jax.named_scope``) carry a layer name into each HLO
  instruction's metadata; ``layer_of`` maps instruction names, which the
  device trace gives its operations, to those layers, from the compiled
  step's HLO text (``layer_of_ops``, a copy of the program's
  ``repro.obs.scopes.layer_of_ops`` kept here with the yardstick);
- host spans (``jax.profiler.TraceAnnotation``) named ``input.*`` and
  ``train.*``, one per call, on the trace's one clock;
- a counter: the step's ``dropped_units`` output, the routed units that
  found their expert's capacity full.

From a ``trace_reduce.Reduced`` of the traced window, the program's spans
and those records, ``metrics`` gives, per traced step:

- ``step.<layer>_ms``: device time of each layer, the union of its
  operations' intervals averaged over the chips; operations outside every
  layer are ``other``, so the seven partition ``step.device_ms``;
- ``input.engine_ms``, ``input.decode_ms``, ``input.assemble_ms``: host
  time in the spans ``input.advance``, ``input.drain`` and
  ``input.assemble`` with ``input.device_put``;
- ``device.idle_input_ms``, ``device.idle_wait_ms``: device idle time
  while the innermost host span open, the program's or the harness's, is
  one of the input's, or is a wait on a finished step (``train.sync``, or
  the harness's ``bench.block_loss``, which blocks first);
- ``moe.dropped_share``: dropped over routed units of the traced steps, %.

A traced run (``run.py --trace 1``) keeps what this reads and the numbers
of ``metrics`` in the readers' ``ctx`` (``run.read_trace``), and ``read``
gives each reader of ``metrics/`` its number; ``record_trace.py`` writes the same to files,
and ``tests/data/program_trace.*`` is one such recording.
"""

from __future__ import annotations

import bisect
import re
from collections import Counter, defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from trace_reduce import WINDOW, Reduced, length

Span = Tuple[str, float, float]

#: the program's host span names start with one of these
PREFIXES = ("input.", "train.")
#: device layers of the program's scopes, and what lies outside them
LAYERS = ("attention", "moe_dispatch", "moe_experts", "ffn", "head",
          "optimizer", "other")
#: the program's scope names, the layers but ``other``
SCOPES = LAYERS[:-1]
#: host spans in which the device waits on a step that has finished
WAIT = ("train.sync", "bench.block_loss")
#: {metric: host spans whose time it adds up}
INPUT_SPANS = {"input.engine_ms": ("input.advance",),
               "input.decode_ms": ("input.drain",),
               "input.assemble_ms": ("input.assemble", "input.device_put")}


def program_spans(profile) -> List[Span]:
    """The program's host spans of a ``jax.profiler.ProfileData``: (name,
    start ns, end ns)."""
    out = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIXES):
                        out.append((e.name, e.start_ns,
                                    e.start_ns + e.duration_ns))
    return out


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WORD = re.compile(r"\w+")


def _layer_in(op_name: str):
    """The last scope name among the scopes of an ``op_name`` path: the
    words of every component but the last, which names the primitive (a
    parameter's ``op_name`` is its argument path, with no scope)."""
    scopes = op_name.split("/")[:-1]
    words = [w for c in scopes for w in _WORD.findall(c) if w in SCOPES]
    return words[-1] if words else None


def _parse(hlo_text: str) -> Dict[str, List[Tuple[str, object, object]]]:
    """{computation: [(instruction, layer from its metadata or None,
    computation it calls or None)]}."""
    comps: Dict[str, list] = {}
    body = None
    for line in hlo_text.splitlines():
        if body is None:
            m = _COMPUTATION.match(line)
            if m:
                body = comps.setdefault(m.group(1), [])
            continue
        if line.startswith("}"):
            body = None
            continue
        m = _INSTRUCTION.match(line)
        if m:
            op = _OP_NAME.search(line)
            calls = _CALLS.search(line)
            body.append((m.group(1), _layer_in(op.group(1)) if op else None,
                         calls.group(1) if calls else None))
    return comps


def layer_of_ops(hlo_text: str) -> Dict[str, str]:
    """{instruction name: layer} of every instruction of a compiled HLO
    module's text (``Compiled.as_text()``).

    An instruction's layer is the last scope name in its
    ``metadata={op_name=...}`` path. One whose metadata names none (a
    fusion, a call) takes the most common layer among the instructions of
    the computation it calls; everything else is ``other``."""
    comps = _parse(hlo_text)
    memo: Dict[str, object] = {}

    def majority(comp: str):
        if comp not in memo:
            memo[comp] = None       # a computation never calls itself
            votes = Counter(resolve(layer, calls)
                            for _, layer, calls in comps.get(comp, ()))
            votes.pop(None, None)
            memo[comp] = votes.most_common(1)[0][0] if votes else None
        return memo[comp]

    def resolve(layer, calls):
        if layer is None and calls is not None:
            return majority(calls)
        return layer

    return {name: resolve(layer, calls) or "other"
            for body in comps.values() for name, layer, calls in body}


def instruction(op: str) -> str:
    """An operation's HLO instruction name (``fusion.85`` of
    ``fusion.85 f32[1,64,1408,2048]``)."""
    return op.split(" ", 1)[0]


def layer_s(red: Reduced, device: str,
            layer_of: Dict[str, str]) -> Dict[str, float]:
    """{layer: seconds} of one device's operations in the window, each
    layer the union of its operations' intervals."""
    by: Dict[str, list] = defaultdict(list)
    for name, a, b in red.ops[device]:
        by[layer_of.get(instruction(name), "other")].append((a, b))
    return {layer: length(by.get(layer, [])) * 1e-9 for layer in LAYERS}


class Innermost:
    """The innermost (shortest) host span open at a time, among ``spans``;
    ``none`` where none is open."""

    def __init__(self, spans: Sequence[Span]):
        self.spans = sorted(spans, key=lambda s: s[1])
        self.starts = [a for _, a, _ in self.spans]

    def cuts(self, a: float, b: float) -> List[float]:
        """The span boundaries inside (a, b)."""
        hi = bisect.bisect_left(self.starts, b)
        return sorted({t for _, s, e in self.spans[:hi]
                       for t in (s, e) if a < t < b})

    def at(self, t: float) -> str:
        best, best_len = "none", float("inf")
        for name, a, b in self.spans[:bisect.bisect_right(self.starts, t)]:
            if t < b and b - a < best_len:
                best, best_len = name, b - a
        return best


def idle_by_span(red: Reduced, spans: Sequence[Span]) -> Dict[str, float]:
    """{host span: seconds} of device idle time, averaged over the devices:
    every idle gap of the window split over time by the innermost span
    open on the host, the harness's (except the window) or the
    program's."""
    open_ = Innermost([s for s in list(red.host) + list(spans)
                       if s[0] != WINDOW])
    out: Dict[str, float] = defaultdict(float)
    for dev in red.devices:
        for a, b in red.gaps(dev):
            edges = [a] + open_.cuts(a, b) + [b]
            for s, e in zip(edges, edges[1:]):
                out[open_.at((s + e) / 2)] += (e - s) * 1e-9 / len(
                    red.devices)
    return dict(out)


def metrics(red: Reduced, spans: Sequence[Span], layer_of: Dict[str, str],
            steps: int, dropped: Optional[Sequence[int]] = None,
            routed_units: Optional[int] = None) -> Dict[str, float]:
    """The per-layer numbers of the traced window, per traced step (see the
    module's docstring). ``dropped`` are the traced steps' dropped units
    and ``routed_units`` the units one step routes."""
    out = {}
    per_device = [layer_s(red, d, layer_of) for d in red.devices]
    for layer in LAYERS:
        out[f"step.{layer}_ms"] = sum(
            s[layer] for s in per_device) / len(per_device) / steps * 1e3
    w0, w1 = red.window
    inside = [(n, a, b) for n, a, b in spans if a >= w0 and b <= w1]
    for metric, names in INPUT_SPANS.items():
        out[metric] = sum(b - a for n, a, b in inside
                          if n in names) * 1e-6 / steps
    idle = idle_by_span(red, spans)
    out["device.idle_input_ms"] = sum(
        v for k, v in idle.items() if k.startswith("input.")) / steps * 1e3
    out["device.idle_wait_ms"] = sum(idle.get(k, 0.0)
                                     for k in WAIT) / steps * 1e3
    if dropped and routed_units:
        out["moe.dropped_share"] = (sum(dropped) * 100.0
                                    / (routed_units * len(dropped)))
    return out


def read(ctx, metric: str) -> Optional[float]:
    """``metric``, one of ``metrics``' numbers, from a traced run's reader
    context: ``ctx["program"]``, the numbers ``run.read_trace`` works out
    once per run. None where ``ctx`` has no ``program`` or it lacks the
    metric (``moe.dropped_share`` of a step with no routed units)."""
    program = ctx.get("program")
    return None if program is None else program.get(metric)
