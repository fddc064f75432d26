"""Record the small TPU trace the program-span and scope tests read.

    python3 bench/tools/record_scoped_trace.py OUT.xplane.pb

Runs the awm-flux1dev-512px cell cut to tiny sizes (``tiny.shrink``) on
the chip, profiles the one whole step of its window, and writes the trace
to OUT trimmed to what ``bench/trace.py`` and ``bench/program_trace.py``
read: the TPU planes' operation and program lines, each operation with its
scope path (the ``tf_op`` stat), and the host's ``bench.*`` and ``repro.*``
spans.  Instruction texts are cut to 80 characters; scope paths are kept
whole, each stored once.  It prints the window's step count and the size.
"""
import glob
import json
import os
import shutil
import sys
import tempfile
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

NAME_CHARS = 80


def _quote(text: str) -> str:
    return '"' + text.replace("\\", "\\\\").replace('"', '\\"') + '"'


def trim(path: str) -> bytes:
    """The trace at ``path`` re-serialized with only the planes, lines,
    events and stats the two reductions read (an operation's scope stat
    is on its metadata: ``bench/xspace.py``)."""
    from jax.profiler import ProfileData
    from bench import program_trace, trace, xspace
    spans = (trace.SPAN_PREFIX, program_trace.PREFIX)
    planes = []
    host = [p for p in ProfileData.from_file(path).planes
            if not trace.DEVICE_PLANE.match(p.name)]
    for plane in xspace.planes(path, trace.DEVICE_PLANE.match) + host:
        if trace.DEVICE_PLANE.match(plane.name):
            keep = lambda line, e: line.name in (trace.OPS_LINE,
                                                 trace.MODULES_LINE)
        elif plane.name.startswith("/host:"):
            keep = lambda line, e: e.name.startswith(spans)
        else:
            continue
        lines = [SimpleNamespace(name=line.name, events=[
            SimpleNamespace(
                name=e.name[:NAME_CHARS], start_ns=e.start_ns,
                end_ns=e.end_ns,
                stats=[(k, v) for k, v in e.stats
                       if k == program_trace.SCOPE_STAT])
            for e in line.events if keep(line, e)])
            for line in plane.lines]
        planes.append(SimpleNamespace(name=plane.name, lines=lines))
    return serialize(planes)


def serialize(planes) -> bytes:
    """An ``.xplane.pb`` of planes shaped like ``ProfileData``'s: events
    with ``.name``, ``.start_ns``, ``.end_ns`` and string ``.stats``; each
    name and stat value is stored once."""
    from jax.profiler import ProfileData
    out = []
    for i, plane in enumerate(planes):
        ids = {}          # one id space for event and stat metadata

        def intern(kind, text):
            return ids.setdefault((kind, text), len(ids) + 1)

        lines = []
        for j, line in enumerate(plane.lines):
            evs = []
            for e in line.events:
                stats = "".join(
                    f" stats {{ metadata_id: {intern('s', k)} "
                    f"ref_value: {intern('s', str(v))} }}"
                    for k, v in getattr(e, "stats", ()))
                evs.append(f"events {{ metadata_id: {intern('e', e.name)} "
                           f"offset_ps: {int(e.start_ns * 1000)} "
                           f"duration_ps: "
                           f"{int((e.end_ns - e.start_ns) * 1000)}{stats} }}")
            if evs:
                lines.append(f"lines {{ id: {j + 1} name: "
                             f"{_quote(line.name)} timestamp_ns: 0 "
                             + " ".join(evs) + " }")
        meta = " ".join(
            f"{'event' if kind == 'e' else 'stat'}_metadata {{ key: {n} "
            f"value {{ id: {n} name: {_quote(text)} }} }}"
            for (kind, text), n in ids.items())
        out.append(f"planes {{ id: {i + 1} name: {_quote(plane.name)} "
                   + " ".join(lines) + f" {meta} }}")
    return ProfileData.text_proto_to_serialized_xspace("\n".join(out))


def main(out: str) -> None:
    from bench import harness, traffic as traffic_lib
    from bench.tools.tiny import shrink
    from repro import registry
    from repro.api import Experiment
    harness.check_chip(1)
    registry.register("dataset", "bench_prompts", override=True)(
        traffic_lib.prompt_cycle)
    pair = harness.load_pair("flux1dev-3l-awm", "train-512px-2x8")
    config, traffic = pair["config"], pair["traffic"]
    shrink(config, traffic)
    prompts = traffic_lib.make_prompts(traffic, 7)
    tmp = tempfile.mkdtemp(prefix="bench-record-")
    try:
        win = harness.Window(0.0, 0.9, [], os.path.join(tmp, "trace"))
        exp = Experiment.from_config(harness.run_config(
            config, traffic, 7, prompts, os.path.join(tmp, "cond")))
        exp.train(callbacks=[win])
        path = sorted(glob.glob(os.path.join(
            tmp, "trace", "plugins", "profile", "*", "*.xplane.pb")))[-1]
        with open(out, "wb") as f:
            f.write(trim(path))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps({"window_steps": len(win.window_rows),
                      "num_steps": traffic["num_steps"],
                      "bytes": os.path.getsize(out)}))


if __name__ == "__main__":
    main(sys.argv[1])
