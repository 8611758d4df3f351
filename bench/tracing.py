"""From a ``jax.profiler`` trace to device busy time, idle gaps and the
operations that took the most device time.

Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane.  Host spans are the benchmark's own
``TraceAnnotation`` events on the host plane.  The traced window runs from
the start of the first ``STEP_SPAN`` to the end of the last one; busy time
is the union of the device operations' intervals inside it.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

#: the benchmark's span around one call into the trainer
STEP_SPAN = "trainer.train"
#: the benchmark's spans, innermost names win when they overlap
HOST_SPANS = ("trainer.train", "loader.batch")
TOP = 10
_LAYOUT = re.compile(r"\{[^{}]*\}")

Interval = Tuple[float, float]  # (start_ns, end_ns)


def tpu_ops(plane: str, line: str) -> bool:
    return plane.startswith("/device:TPU:") and line == "XLA Ops"


@dataclass
class Trace:
    device_ops: Dict[str, List[Tuple[str, float, float]]]  # plane -> ops
    host_spans: List[Tuple[str, float, float]]


def load(path: str, is_device: Callable[[str, str], bool] = tpu_ops,
         ) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: Dict[str, list] = defaultdict(list)
    spans = []
    for plane in pd.planes:
        for line in plane.lines:
            evs = list(line.events)
            if is_device(plane.name, line.name):
                ops[plane.name] += [(e.name, e.start_ns,
                                     e.start_ns + e.duration_ns) for e in evs]
            elif plane.name.startswith("/host:"):
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in evs if e.name in HOST_SPANS]
    return Trace(dict(ops), spans)


def find_xplane(logdir: str) -> str:
    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {logdir}, "
                                f"found {len(files)}")
    return files[0]


def union(intervals: Sequence[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """Merged intervals, clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def op_name(event_name: str) -> str:
    """``%fusion.3 = f32[7,2048]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.3 = f32[7,2048]``: the HLO name and its result's type, without
    layouts."""
    name, _, rest = event_name.lstrip("%").partition(" = ")
    rest = _LAYOUT.sub("", rest)
    if rest.startswith("("):  # a tuple: up to its closing parenthesis
        depth = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rest[:end + 1]
    else:
        rest = rest.split(" ")[0]
    return f"{name} = {rest}"[:120] if rest else name


def self_times(ops: Sequence[Tuple[str, float, float]]
               ) -> Dict[str, float]:
    """Time of each operation outside the operations nested in it (a
    while loop's own time, not its body's), summed by name."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[List] = []  # [name, start, end, time of direct children]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])) + [
            (None, float("inf"), float("inf"))]:
        while stack and stack[-1][2] <= s:
            n, s0, e0, kids = stack.pop()
            out[n] += (e0 - s0) - kids
        if name is None:
            break
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0.0])
    return dict(out)


def _label(spans, t: float) -> str:
    """Names of the benchmark spans open at ``t``, outermost first."""
    open_ = sorted((s, n) for n, s, e in spans if s <= t < e)
    return ">".join(n for _, n in open_) or "outside benchmark spans"


@dataclass
class Reduced:
    busy_s: float          # device busy inside the window, mean over chips
    window_s: float        # traced window
    steps: int             # STEP_SPAN calls inside the window
    device_ops: List[List]  # [[name, self seconds], ...] most first
    idle_gaps: List[List]   # [[host spans, seconds], ...] longest first


def reduce(trace: Trace) -> Optional[Reduced]:
    """None where the trace holds no step span or no device operation."""
    steps = [(s, e) for n, s, e in trace.host_spans if n == STEP_SPAN]
    if not steps or not trace.device_ops:
        return None
    lo, hi = min(s for s, _ in steps), max(e for _, e in steps)
    busy, per_op, gaps = [], defaultdict(float), []
    for ops in trace.device_ops.values():
        merged = union([(s, e) for _, s, e in ops], lo, hi)
        busy.append(sum(e - s for s, e in merged))
        inside = [(op_name(n), max(s, lo), min(e, hi)) for n, s, e in ops
                  if min(e, hi) > max(s, lo)]
        for name, t in self_times(inside).items():
            per_op[name] += t
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((_label(trace.host_spans, (s + e) / 2), e - s))
    if not any(busy):
        return None
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    gaps = sorted(gaps, key=lambda g: -g[1])[:TOP]
    ns = 1e-9
    return Reduced(busy_s=sum(busy) / len(busy) * ns, window_s=(hi - lo) * ns,
                   steps=len(steps),
                   device_ops=[[n, t * ns] for n, t in top],
                   idle_gaps=[[n, t * ns] for n, t in gaps])
