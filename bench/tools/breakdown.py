"""Split a traced window by the program's scopes and spans.

    python3 bench/tools/breakdown.py TRACE.xplane.pb

Reads a profiler trace of a benchmark window (the file ``bench/run.py
--trace 1`` profiles into) and prints one JSON object, in ms per step of
the window: each program's device time, split into the named scopes
(``program_trace.SCOPES``) and the rest, the rest's largest parts by
operation name, the device's idle time, the longest idle gaps named by the
innermost host span (``bench.*`` or ``repro.*``), the host's own work in
the loop (``program_trace.host_ns``), and the top operations by program,
instruction and scope.
"""
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import program_trace, trace  # noqa: E402

CONTAINER_SEGMENT = re.compile(r"^(while|body|cond|closed_call|checkpoint|"
                               r"jvp\(.*\)|transpose\(.*\)|jit\(.*\))$")


def rest_label(e) -> str:
    """An unscoped operation's path without its program and loop
    segments (``jit(_update)/while/body/closed_call/add`` -> ``add``), or
    the kind of its instruction where nothing else is left."""
    segs = [s for s in e.scope.split("/") if not CONTAINER_SEGMENT.match(s)]
    return "/".join(segs[-2:]) or e.op.split(".")[0]


def split(tr, lo: float, hi: float, steps: int, top: int = 8):
    per_step = 1e6 * steps
    programs = {}
    for module in sorted({e.module for d in tr.devices
                          for e in tr.ops.get(d, [])} - {""}):
        total = trace.module_ns(tr, f"^{re.escape(module)}\\(", lo, hi)
        scopes = {s: program_trace.scope_ns(tr, s, lo, hi, module=module)
                  for s in program_trace.SCOPES}
        rest: dict = {}
        for d in tr.devices:
            for e in tr.ops.get(d, []):
                if (e.module == module and lo <= e.start and e.end <= hi
                        and program_trace.scope_label(e) == "-"
                        and not trace.CONTAINERS.match(e.op)):
                    k = rest_label(e)
                    rest[k] = rest.get(k, 0.0) + e.dur / len(tr.devices)
        programs[module] = {
            "total": total / per_step,
            **{s: v / per_step for s, v in scopes.items() if v},
            "rest": (total - sum(scopes.values())) / per_step,
            "rest_parts": [[k, v / per_step] for k, v in sorted(
                rest.items(), key=lambda kv: -kv[1])[:top]]}
    busy = trace.busy_ns(tr, lo, hi)
    return {
        "steps": steps, "window_ms": (hi - lo) / 1e6,
        "programs": programs,
        "scopes": {s: program_trace.scope_ns(tr, s, lo, hi) / per_step
                   for s in program_trace.SCOPES},
        "idle": (hi - lo - busy) / per_step,
        "loop_host": program_trace.host_ns(tr, lo, hi) / per_step,
        "idle_gaps_ms": [[n, s * 1e3] for n, s in
                         program_trace.idle_gaps(tr, lo, hi)],
        "top_ops_s": program_trace.top_ops(tr, lo, hi)}


def main(path: str) -> None:
    tr = program_trace.load(path)
    lo, hi = trace.window(tr)
    steps = sum(s.name == "bench.dispatch" for s in tr.spans)
    print(json.dumps(split(tr, lo, hi, steps), indent=1))


if __name__ == "__main__":
    main(sys.argv[1])
