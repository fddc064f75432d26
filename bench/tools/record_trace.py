"""Record the small TPU trace the trace-reduction tests read.

    python3 bench/tools/record_trace.py OUT.xplane.pb

Runs the flux1dev-4l-awm configuration trained by Flow-GRPO instead (the
path with the fused SDE step kernel) under the train-512px-2x8 mix, cut
to tiny sizes (``tiny.shrink``), on the chip;
profiles the one whole step of its window with the benchmark's host spans,
and writes the trace to OUT trimmed to what ``bench/trace.py`` reads: the
TPU planes' operation and program lines, the host's ``bench.*`` spans,
instruction texts cut to 160 characters.  It prints what the tests
assert: the window's step count and the denoising steps per step.
"""
import glob
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def _quote(text: str) -> str:
    return '"' + text.replace("\\", "\\\\").replace('"', '\\"') + '"'


def trim(path: str) -> bytes:
    """The trace at ``path`` re-serialized with only the planes, lines and
    events the reduction reads."""
    from jax.profiler import ProfileData
    from bench import trace
    planes = []
    for i, plane in enumerate(ProfileData.from_file(path).planes):
        if trace.DEVICE_PLANE.match(plane.name):
            keep = lambda line, e: line.name in (trace.OPS_LINE,
                                                 trace.MODULES_LINE)
        elif plane.name.startswith("/host:"):
            keep = lambda line, e: e.name.startswith(trace.SPAN_PREFIX)
        else:
            continue
        names, lines = {}, []
        for j, line in enumerate(plane.lines):
            evs = []
            for e in line.events:
                if keep(line, e):
                    mid = names.setdefault(e.name[:160], len(names) + 1)
                    evs.append(f"events {{ metadata_id: {mid} offset_ps: "
                               f"{int(e.start_ns * 1000)} duration_ps: "
                               f"{int((e.end_ns - e.start_ns) * 1000)} }}")
            if evs:
                lines.append(f"lines {{ id: {j + 1} name: "
                             f"{_quote(line.name)} timestamp_ns: 0 "
                             + " ".join(evs) + " }")
        meta = " ".join(f"event_metadata {{ key: {m} value {{ id: {m} "
                        f"name: {_quote(n)} }} }}" for n, m in names.items())
        planes.append(f"planes {{ id: {i + 1} name: {_quote(plane.name)} "
                      + " ".join(lines) + " " + meta + " }")
    return ProfileData.text_proto_to_serialized_xspace("\n".join(planes))


def main(out: str) -> None:
    from bench import harness, traffic as traffic_lib
    from bench.tools.tiny import shrink
    from repro import registry
    from repro.api import Experiment
    harness.check_chip(1)
    registry.register("dataset", "bench_prompts", override=True)(
        traffic_lib.prompt_cycle)
    pair = harness.load_pair("flux1dev-4l-awm", "train-512px-2x8")
    config, traffic = pair["config"], pair["traffic"]
    config["run"]["flow"]["trainer_type"] = "flow_grpo"
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
