"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

From the trace it keeps three things: on each TPU device plane, the
per-operation events and the per-program (XLA module) events; on the host,
the benchmark's own ``bench.*`` spans (``jax.profiler.TraceAnnotation``).
Every quantity is taken inside a window [lo, hi] on the trace's clock: the
first dispatch span to the end of the last drain span.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float          # ns
    end: float            # ns

    @property
    def dur(self) -> float:
        return self.end - self.start

    @property
    def op(self) -> str:
        """The HLO instruction's name (``fusion.12``): a device operation
        event is named by its whole instruction text."""
        return self.name.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]        # device plane -> operation events
    modules: Dict[str, List[Event]]    # device plane -> program events
    spans: List[Event]                 # host bench.* spans

    @property
    def devices(self) -> List[str]:
        return sorted(set(self.ops) | set(self.modules))


def _event(e) -> Event:
    return Event(e.name, float(e.start_ns), float(e.end_ns))


def from_planes(planes: Iterable) -> Trace:
    """Build a Trace from objects shaped like ``jax.profiler.ProfileData``
    planes: ``.name``, ``.lines`` with ``.name`` and ``.events``, events
    with ``.name``, ``.start_ns`` and ``.end_ns``."""
    ops, modules, spans = {}, {}, []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [_event(e) for e in line.events]
                elif line.name == MODULES_LINE:
                    modules[plane.name] = [_event(e) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(_event(e) for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    spans.sort(key=lambda e: e.start)
    return Trace(ops, modules, spans)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    return from_planes(ProfileData.from_file(path).planes)


# ---------------------------------------------------------------- windows
def window(trace: Trace) -> Tuple[float, float]:
    """[first ``bench.dispatch`` start, last ``bench.drain`` end]."""
    starts = [s.start for s in trace.spans if s.name == "bench.dispatch"]
    ends = [s.end for s in trace.spans if s.name == "bench.drain"]
    if not starts or not ends:
        raise ValueError("trace holds no bench.dispatch/bench.drain spans")
    return min(starts), max(ends)


def union(intervals: Iterable[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """Merged intervals clipped to [lo, hi]."""
    out: List[List[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _device_events(trace: Trace, dev: str) -> List[Event]:
    return trace.ops.get(dev) or trace.modules.get(dev, [])


def busy_ns(trace: Trace, lo: float, hi: float) -> float:
    """Union of device operation intervals in [lo, hi], mean over devices."""
    devs = trace.devices
    if not devs:
        return 0.0
    return sum(sum(b - a for a, b in union(
        ((e.start, e.end) for e in _device_events(trace, d)), lo, hi))
        for d in devs) / len(devs)


def module_ns(trace: Trace, pattern: str, lo: float, hi: float) -> float:
    """Device time of the programs whose name matches ``pattern`` (a
    regular expression searched in the module name), mean over devices."""
    rx = re.compile(pattern)
    devs = trace.devices
    if not devs:
        return 0.0
    tot = 0.0
    for d in devs:
        tot += sum(b - a for a, b in union(
            ((e.start, e.end) for e in trace.modules.get(d, [])
             if rx.search(e.name)), lo, hi))
    return tot / len(devs)


def op_events(trace: Trace, pattern: str, lo: float, hi: float
              ) -> List[Event]:
    """Device operation events inside [lo, hi] whose instruction name
    matches ``pattern`` (``re.match``)."""
    rx = re.compile(pattern)
    return [e for d in trace.devices for e in trace.ops.get(d, [])
            if e.start >= lo and e.end <= hi and rx.match(e.op)]


def roofline_pct(events: List[Event], flops: float, nbytes: float,
                 peaks: Dict) -> "float | None":
    """A kernel's share of its roofline, in %: the least time the chip
    could take for the calls ``events`` -- each ``flops`` operations and
    ``nbytes`` bytes of HBM traffic, at the bf16 peak and the HBM bandwidth
    of ``peaks`` -- over their device time.  None without a call."""
    busy = sum(e.dur for e in events) / 1e9
    if not events or busy <= 0:
        return None
    least = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least * len(events) / busy


# -------------------------------------------------------------- breakdown
CONTAINERS = re.compile(r"(while|conditional|call)(\.\d+)?$")


def top_ops(trace: Trace, lo: float, hi: float, top: int = 10
            ) -> List[Tuple[str, float]]:
    """The device operations that took most time in [lo, hi], summed by
    instruction name over events and devices, in seconds.  Loops and calls
    are left out: the operations inside them have events of their own."""
    tot: Dict[str, float] = {}
    for d in trace.devices:
        for e in trace.ops.get(d, []):
            if e.start >= lo and e.end <= hi and not CONTAINERS.match(e.op):
                tot[e.op] = tot.get(e.op, 0.0) + e.dur
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [(name, ns / 1e9) for name, ns in best]


def idle_gaps(trace: Trace, lo: float, hi: float, top: int = 10
              ) -> List[Tuple[str, float]]:
    """The longest stretches of [lo, hi] with no device operation on the
    first device, each named by the innermost ``bench.*`` host span that
    covers its midpoint ("host: none" where no span does), in seconds."""
    devs = trace.devices
    if not devs:
        return []
    busy = union(((e.start, e.end) for e in _device_events(trace, devs[0])),
                 lo, hi)
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = b
    if hi > t:
        gaps.append((t, hi))
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = 0.5 * (a + b)
        cover = [s for s in trace.spans if s.start <= mid <= s.end]
        label = (min(cover, key=lambda s: s.dur).name if cover
                 else "host: none")
        out.append((label, (b - a) / 1e9))
    return out
