"""Names under which the training path shows up in a profiler trace.

Two kinds of name, each declared once here:

* **Layers** (``LAYERS``): ``jax.named_scope`` names on the device path of
  the train step. A scope is compile-time metadata: it lands in each HLO
  instruction's ``metadata={op_name=...}`` path and costs nothing at run
  time. Backward and rematerialised operations keep the forward scope in
  their path (``transpose(jvp(...))/.../moe_experts/dot_general``), so a
  layer counts its forward, backward and recompute together.
* **Spans** (``SPANS``): ``jax.profiler.TraceAnnotation`` names on the
  host, one per call of a function of the input pipeline or the training
  loop, never per record. The profiler writes them into the same trace as
  the device operations, on one clock, so a gap in which the device is
  idle can be put down to what the host was doing then.

``layer_of_ops`` maps the instructions of a compiled module (the text of
``Compiled.as_text()``, whose instruction names are the names the device
trace gives its operations) to these layers.
"""

from __future__ import annotations

import re
from collections import Counter
from typing import Dict, List, Tuple

import jax

#: device layers, in the order the step runs them
LAYERS = ("head", "attention", "ffn", "moe_dispatch", "moe_experts",
          "optimizer")
#: what ``layer_of_ops`` gives an instruction outside every layer
OTHER = "other"

#: host spans of the input pipeline and the training loop
SPANS = ("input.next_batch", "input.advance", "input.drain",
         "input.assemble", "input.device_put", "train.dispatch",
         "train.sync", "train.checkpoint")


def scope(layer: str):
    """``jax.named_scope`` of one of ``LAYERS``."""
    if layer not in LAYERS:
        raise ValueError(f"{layer!r} is not one of {LAYERS}")
    return jax.named_scope(layer)


def span(name: str):
    """Host span ``name`` (one of ``SPANS``) in the profiler's trace; it
    records nothing while no trace is being taken."""
    if name not in SPANS:
        raise ValueError(f"{name!r} is not one of {SPANS}")
    return jax.profiler.TraceAnnotation(name)


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WORD = re.compile(r"\w+")


def _layer_in(op_name: str):
    """The last layer name among the scopes of an ``op_name`` path: the
    words of every component but the last, which names the primitive (a
    parameter's ``op_name`` is its argument path, with no scope)."""
    scopes = op_name.split("/")[:-1]
    words = [w for c in scopes for w in _WORD.findall(c) if w in LAYERS]
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
    module's text.

    An instruction's layer is the last layer name in its
    ``metadata={op_name=...}`` path. One whose metadata names none (a
    fusion, a call) takes the most common layer among the instructions of
    the computation it calls; everything else is ``OTHER``."""
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

    return {name: resolve(layer, calls) or OTHER
            for body in comps.values() for name, layer, calls in body}
