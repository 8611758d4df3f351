"""The program's own spans and named scopes in a ``jax.profiler`` trace.

``bench.tracing`` reads the benchmark's spans round the program and XLA's
names for the device operations.  This module reads what the program
marks itself, from the same trace file:

- its host spans (``Trainer.train``'s ``train.*`` phases and
  ``train_step``; ``repro.obs.trace``), which say what the host was doing
  while the device sat idle;
- the ``jax.named_scope`` each device operation was traced under
  (``repro.obs.scopes``).  Device events carry no scope, so an operation
  is joined by its HLO instruction name with the compiled step's HLO
  text, whose ``op_name`` metadata holds the scopes open when it was
  traced; the innermost listed one owns the operation.

From these it computes device self time by scope, the causal attention's
share of its roofline, and the idle time a step spends waiting on the
host.  A trace of a program that marks nothing reads as nothing: every
operation is ``NO_SCOPE``, and the readings that need a scope or a span
are None.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from bench import tracing
from repro.obs import scopes

#: the program's span round one trainer step's device work
STEP_WORK_SPAN = "train_step"
#: prefix of the trainer's other spans (``repro.runtime.trainer``)
SPAN_PREFIX = "train."
#: the span in which the host blocks on the step's result: idle time
#: there is the device program's own, not the host's
WAIT_SPAN = "train.wait"
#: scope of an operation traced under no listed scope
NO_SCOPE = "(no scope)"
#: label of idle time under no program span
NO_SPAN = "(no program span)"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=(]+) = ")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_MODULE = re.compile(r"^HloModule ([^\s,]+)")
_WRAPPED = re.compile(r"(?:[\w.-]+\()*([\w.-]+)\)*")

Interval = Tuple[float, float]


def is_program_span(name: str) -> bool:
    return name == STEP_WORK_SPAN or name.startswith(SPAN_PREFIX)


def scope_of(op_name: str, names: Sequence[str] = scopes.ALL) -> str:
    """The innermost listed scope in an ``op_name`` path; a component
    counts in any transformation's wrapping (``jvp(attention_core)``,
    ``transpose(jvp(mlp))``)."""
    for part in reversed(op_name.split("/")):
        m = _WRAPPED.fullmatch(part)
        if m and m.group(1) in names:
            return m.group(1)
    return NO_SCOPE


def hlo_scopes(hlo_text: str, names: Sequence[str] = scopes.ALL
               ) -> Dict[str, str]:
    """Instruction name -> scope, over every computation of one HLO
    module's text (``Compiled.as_text()``); an instruction without
    ``op_name`` metadata (one XLA made) is ``NO_SCOPE``."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(line)
            out[m.group(1)] = scope_of(op.group(1), names) if op else NO_SCOPE
    return out


def hlo_module(hlo_text: str) -> Optional[str]:
    m = _MODULE.match(hlo_text)
    return m.group(1) if m else None


def instruction(event_name: str) -> str:
    """``%fusion.3 = f32[8]{0} fusion(...)`` or ``fusion.3`` ->
    ``fusion.3``."""
    return event_name.lstrip("%").partition(" = ")[0].strip()


@dataclass
class ProgramTrace:
    steps: List[Interval]                   # the benchmark's STEP_SPANs
    spans: List[Tuple[str, float, float]]   # the program's host spans
    #: plane -> line -> [(scope, start, end), ...]; a chip's plane has one
    #: line of ops, a CPU run's has one per thread
    ops: Dict[str, Dict[str, List[Tuple[str, float, float]]]]

    @property
    def window(self) -> Optional[Interval]:
        if not self.steps:
            return None
        return min(s for s, _ in self.steps), max(e for _, e in self.steps)


def load(path: str, hlo_text: str,
         is_device: Callable[[str, str], bool] = tracing.tpu_ops
         ) -> ProgramTrace:
    """The program's spans and scoped device operations of one
    ``.xplane.pb``.  An operation of another HLO module than the step's,
    where the event says which, is ``NO_SCOPE``.  A TPU's op events name
    no module; in the trainer's window the step is the only program that
    runs (its ``XLA Modules`` line holds one event a step)."""
    from jax.profiler import ProfileData

    by_instr = hlo_scopes(hlo_text)
    module = hlo_module(hlo_text)
    steps, spans = [], []
    ops: Dict[str, Dict[str, list]] = defaultdict(dict)
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            if is_device(plane.name, line.name):
                out = ops[plane.name].setdefault(line.name, [])
                for e in line.events:
                    mod = dict(e.stats).get("hlo_module")
                    scope = NO_SCOPE
                    if mod is None or module is None or mod == module:
                        scope = by_instr.get(instruction(e.name), NO_SCOPE)
                    out.append((scope, e.start_ns, e.start_ns + e.duration_ns))
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    iv = (e.start_ns, e.start_ns + e.duration_ns)
                    if e.name == tracing.STEP_SPAN:
                        steps.append(iv)
                    elif is_program_span(e.name):
                        spans.append((e.name,) + iv)
    return ProgramTrace(steps, spans, dict(ops))


def by_scope(pt: ProgramTrace) -> List[List]:
    """[[scope, device self seconds], ...] in the window, largest first,
    mean over chips; the scopes and ``NO_SCOPE`` sum to the operations'
    self time, which is the busy time where no two overlap."""
    win = pt.window
    if win is None or not pt.ops:
        return []
    lo, hi = win
    total: Dict[str, float] = defaultdict(float)
    for lines in pt.ops.values():
        for ops in lines.values():
            inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                      if min(e, hi) > max(s, lo)]
            for scope, t in tracing.self_times(inside).items():
                total[scope] += t / len(pt.ops)
    return [[n, t * 1e-9] for n, t in
            sorted(total.items(), key=lambda kv: -kv[1])]


def _innermost(spans, t: float) -> str:
    """The span open at ``t`` that opened last (of two opened together,
    the one that closes first)."""
    open_ = [(s, -e, n) for n, s, e in spans if s <= t < e]
    return max(open_)[2] if open_ else NO_SPAN


def idle_by_span(pt: ProgramTrace) -> Dict[str, float]:
    """Device-idle seconds in the window by the innermost program span
    open over them, mean over chips."""
    win = pt.window
    if win is None or not pt.ops:
        return {}
    lo, hi = win
    cuts = sorted({x for _, s, e in pt.spans for x in (s, e)
                   if lo < x < hi})
    out: Dict[str, float] = defaultdict(float)
    for lines in pt.ops.values():
        merged = tracing.union([(s, e) for ops in lines.values()
                                for _, s, e in ops], lo, hi)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            bounds = [s] + [c for c in cuts if s < c < e] + [e]
            for a, b in zip(bounds, bounds[1:]):
                if b > a:
                    out[_innermost(pt.spans, (a + b) / 2)] += (
                        (b - a) * 1e-9 / len(pt.ops))
    return dict(out)


def host_stall_ms(pt: ProgramTrace) -> Optional[float]:
    """Device-idle milliseconds a traced step spends outside ``WAIT_SPAN``:
    the time the chip waits on the host.  None without program spans."""
    if not pt.spans or not pt.steps or not pt.ops:
        return None
    stall = sum(t for n, t in idle_by_span(pt).items() if n != WAIT_SPAN)
    return 1e3 * stall / len(pt.steps)


def attention_flops_per_token(ref, config, seq_len: int) -> float:
    """The causal attention term of the dense reference's
    ``flops_per_token`` (q·k and p·v, forward and backward), taken from it
    as the part that grows with the sequence."""
    return (ref.flops_per_token(config, seq_len)
            - ref.flops_per_token(config, 0))


def attn_roofline(pt: ProgramTrace, flops_per_step: float,
                  peak_flops_per_s: float) -> Optional[float]:
    """Causal attention's work over the device self time under
    ``attention_core`` at the chip's peak, in percent; None where no
    operation maps to that scope."""
    t = dict(by_scope(pt)).get(scopes.ATTENTION_CORE, 0.0)
    if t <= 0:
        return None
    return 100.0 * flops_per_step * len(pt.steps) / (t * peak_flops_per_s)


def step_hlo_text(trainer) -> str:
    """The optimized HLO of the trainer's step, compiled afresh from the
    source that runs now, for the shapes its loader feeds.

    JAX keys its compilation caches without metadata, so the executable
    they hold may carry the ``op_name`` metadata of another build of the
    same program (one from before the scopes); this compile bypasses
    them.  Metadata does not change what XLA makes of a program, so the
    instruction names are those of the executable that ran (checked on
    the chip, PERF.md §6).  It clears JAX's in-memory caches."""
    import jax

    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in trainer.loader.batch(0).items()}
    jax.clear_caches()
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return trainer.step_fn.lower(trainer.state, batch).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
