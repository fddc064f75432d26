"""The program's own spans and scopes in a profiler trace.

``bench/trace.py`` keeps, of a trace, the device's operation and program
events and the benchmark's ``bench.*`` host spans.  This module reads the
same file for what the program itself writes there:

* its host spans, named ``repro.*`` (``jax.profiler.TraceAnnotation`` in
  ``api/loop.py`` and ``core/trainers/base.py``): ``repro.step`` around
  each iteration of the train loop, ``repro.loop.*`` and
  ``repro.trainer.*`` inside it;
* for each device operation, the program (XLA module) it ran in, found by
  the program event that holds it, and its scope path: the HLO
  ``op_name`` (``jit(_update)/while/body/.../dit_mlp/dot_general``), in
  which ``jax.named_scope`` writes ``dit_attention``, ``dit_mlp``,
  ``dit_adaln`` and ``optim_adamw``: the operation's ``tf_op`` stat, on
  the event or on its metadata (``bench/xspace.py``).

A scope is matched as one whole ``/``-separated segment of the path.  Every
quantity is taken inside the window [lo, hi] of ``trace.window``.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import tempfile
from typing import Dict, Iterable, List, Optional, Tuple

from bench import trace, xspace

PREFIX = "repro."
SCOPE_STAT = "tf_op"
# the scopes the program names, in the order a breakdown row shows them
SCOPES = ("dit_attention", "dit_mlp", "dit_adaln", "optim_adamw")
# where the harness profiles a run (``harness.measure``)
TRACE_GLOB = os.path.join("bench-trace-*", "plugins", "profile", "*",
                          "*.xplane.pb")


@dataclasses.dataclass(frozen=True)
class OpEvent(trace.Event):
    module: str = ""      # XLA module, ``jit__update``
    scope: str = ""       # HLO op_name path


@dataclasses.dataclass
class ScopedTrace(trace.Trace):
    program_spans: List[trace.Event] = dataclasses.field(
        default_factory=list)     # host repro.* spans


def _stat(e, name: str) -> str:
    for k, v in e.stats:
        if k == name:
            return str(v)
    return ""


def _module_name(event_name: str) -> str:
    """``jit__update(1234)`` -> ``jit__update``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def _scoped_ops(ops: Iterable, modules: List[trace.Event]) -> List[OpEvent]:
    """Operation events, each with the program event that holds its start
    and its scope stat."""
    mods = sorted(modules, key=lambda m: m.start)
    starts = [m.start for m in mods]
    out = []
    for e in ops:
        start, end = float(e.start_ns), float(e.end_ns)
        i = bisect.bisect_right(starts, start) - 1
        module = (_module_name(mods[i].name)
                  if i >= 0 and start < mods[i].end else "")
        out.append(OpEvent(e.name, start, end, module,
                           _stat(e, SCOPE_STAT).rstrip(":")))
    return out


def from_planes(planes: Iterable) -> ScopedTrace:
    """A ScopedTrace from planes shaped like ``jax.profiler.ProfileData``'s
    (see ``trace.from_planes``); events may carry ``.stats``, pairs of
    name and value."""
    planes = list(planes)
    base = trace.from_planes(planes)
    ops = {}
    spans = []
    for plane in planes:
        if trace.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    ops[plane.name] = _scoped_ops(
                        line.events, base.modules.get(plane.name, []))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(trace.Event(e.name, float(e.start_ns),
                                         float(e.end_ns))
                             for e in line.events
                             if e.name.startswith(PREFIX))
    spans.sort(key=lambda e: e.start)
    return ScopedTrace(ops, base.modules, base.spans, spans)


def load(path: str) -> ScopedTrace:
    """The device planes are read by ``xspace`` (``ProfileData`` does not
    show an operation's metadata stats), the host's by ``ProfileData``."""
    from jax.profiler import ProfileData
    host = [p for p in ProfileData.from_file(path).planes
            if not trace.DEVICE_PLANE.match(p.name)]
    return from_planes(xspace.planes(path, trace.DEVICE_PLANE.match) + host)


def find(lo: float, hi: float) -> Optional[ScopedTrace]:
    """The trace the harness profiled the window [lo, hi] into: of the
    ``bench-trace-*`` files in the temporary directory, newest first, the
    one whose ``bench.*`` spans give that window.  None where none does."""
    paths = sorted(glob.glob(os.path.join(tempfile.gettempdir(),
                                          TRACE_GLOB)),
                   key=os.path.getmtime, reverse=True)
    for path in paths:
        tr = load(path)
        try:
            if trace.window(tr) == (lo, hi):
                return tr
        except ValueError:
            continue
    return None


def trace_of(ctx) -> Optional[ScopedTrace]:
    """The ScopedTrace of a reader's context.  A context whose trace is
    one already is used as it is.  The harness hands its readers the plain
    reduction and not its file: the file is found once per context
    (``find``) and kept on it for the next reader."""
    if isinstance(ctx.trace, ScopedTrace):
        return ctx.trace
    if not hasattr(ctx, "scoped_trace"):
        ctx.scoped_trace = find(ctx.lo, ctx.hi)
    return ctx.scoped_trace


# ---------------------------------------------------------------- scopes
def in_scope(e: OpEvent, segment: str) -> bool:
    return segment in e.scope.split("/")


def scope_ns(tr: ScopedTrace, segment: str, lo: float, hi: float,
             module: Optional[str] = None) -> float:
    """Device time of the operations whose scope path holds ``segment`` as
    a whole segment (and that ran in ``module``, if given): the union of
    their intervals in [lo, hi], mean over devices."""
    devs = tr.devices
    if not devs:
        return 0.0
    return sum(sum(b - a for a, b in trace.union(
        ((e.start, e.end) for e in tr.ops.get(d, [])
         if in_scope(e, segment) and module in (None, e.module)),
        lo, hi)) for d in devs) / len(devs)


def scope_ms_per_step(ctx, segment: str) -> Optional[float]:
    """A scope's device time per step of the window, in ms; None where the
    trace holds no device, no step or no operation of that scope."""
    if not ctx.trace.devices or not ctx.steps:
        return None
    tr = trace_of(ctx)
    if tr is None:
        return None
    ns = scope_ns(tr, segment, ctx.lo, ctx.hi)
    return ns / 1e6 / ctx.steps if ns > 0 else None


def scope_label(e: OpEvent) -> str:
    """The program's scope an operation ran in, or ``-``."""
    segs = e.scope.split("/")
    return next((s for s in SCOPES if s in segs), "-")


# ------------------------------------------------------------- host spans
def _length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def host_ns(tr: ScopedTrace, lo: float, hi: float) -> float:
    """The host's own work in the train loop inside [lo, hi]: the time in
    ``repro.step`` spans less the time in their ``repro.loop.fetch``
    spans, the blocking wait for a step's metrics."""
    steps = trace.union(((s.start, s.end) for s in tr.program_spans
                         if s.name == "repro.step"), lo, hi)
    fetch = [(f.start, f.end) for f in tr.program_spans
             if f.name == "repro.loop.fetch"]
    waits = trace.union(((max(a, c), min(b, d)) for a, b in steps
                         for c, d in fetch), lo, hi)
    return _length(steps) - _length(waits)


# -------------------------------------------------------------- breakdown
def top_ops(tr: ScopedTrace, lo: float, hi: float, top: int = 10
            ) -> List[Tuple[str, float]]:
    """``trace.top_ops`` summed by program and instruction (an instruction
    name is unique only within its program), each row named
    ``<program>/<instruction> [<scope>]``, in seconds."""
    tot: Dict[Tuple[str, str], float] = {}
    label: Dict[Tuple[str, str], str] = {}
    for d in tr.devices:
        for e in tr.ops.get(d, []):
            if (e.start >= lo and e.end <= hi
                    and not trace.CONTAINERS.match(e.op)):
                key = (e.module, e.op)
                tot[key] = tot.get(key, 0.0) + e.dur
                label[key] = scope_label(e)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [(f"{m}/{op} [{label[(m, op)]}]", ns / 1e9)
            for (m, op), ns in best]


def idle_gaps(tr: ScopedTrace, lo: float, hi: float, top: int = 10
              ) -> List[Tuple[str, float]]:
    """``trace.idle_gaps`` with each gap named by the innermost host span,
    of the benchmark's or the program's, that covers its midpoint."""
    return trace.idle_gaps(dataclasses.replace(
        tr, spans=tr.spans + tr.program_spans), lo, hi, top)
