"""Readings the limits of ``bench/limits/<workload>.json`` are set from.

    python3 bench/tools/calibrate.py --config C --traffic T --seeds 1,2,3 \
        --control-seeds 1,2,3 --out readings.jsonl [--workload W]
    python3 bench/tools/calibrate.py --judge readings.jsonl --workload W

For the configuration C under the traffic mix T, in one process on the
chip, for every seed: the system's first steps from
that seed against the reference (a run with a one-step window), which gives
the program's readings.  With ``--rollout-only``, the system's first
rollout against the reference's, and for control seeds the control's: the
``rollout_gap`` alone, without the reference's training steps.  For every control seed also, against the same
reference: the control (the reference computed with its matrix operands
rounded to float8_e4m3fn, below the configuration's bfloat16) and a planted
fault (the reference averaging over half of each batch).  One JSON line per
reading: {"seed", "kind": "program"|"control"|"half_batch", the compared
numbers, "side" and "reference": what each side reported}.  With
``--workload``, each line also says whether ``bench.compare.verdict``
finds it correct under that cell's limits, and which numbers fail them;
``--judge`` does that for the lines of an earlier readings file, from
the sides each line records.  The
benchmark's own runs never run this.
"""
import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

CONTROL_DTYPE = "float8_e4m3fn"


def judged(read, limits):
    """{"correct", "fails"} of readings under those of ``limits`` they
    hold ({} without limits)."""
    from bench import compare
    limits = {n: v for n, v in limits.items() if n in read}
    if not limits:
        return {}
    ok, checks = compare.verdict(read, limits)
    return {"correct": ok, "fails": sorted(
        n for n, c in checks.items() if not c["value"] <= c["limit"])}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config")
    ap.add_argument("--traffic")
    ap.add_argument("--seeds")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out")
    ap.add_argument("--workload", default="",
                    help="judge every reading under this cell's limits")
    ap.add_argument("--judge", default="",
                    help="judge the lines of this readings file and stop")
    ap.add_argument("--rollout-only", action="store_true",
                    help="read only rollout_gap: the first step's rollout "
                         "of the system, the reference and the control")
    args = ap.parse_args()
    from bench import compare, harness, traffic as traffic_lib
    limits = (harness.load_json(harness.ROOT / "bench" / "limits"
                                / f"{args.workload}.json")
              if args.workload else {})
    if args.judge:
        with open(args.judge) as f:
            for line in f:
                row = json.loads(line)
                read = {n: row[n] for n in limits if n in row}
                if "side" in row:
                    read.update(compare.readings(row["side"],
                                                 row["reference"]))
                print(json.dumps({"seed": row["seed"], "kind": row["kind"],
                                  **{n: read[n] for n in limits
                                     if n in read},
                                  **judged(read, limits)}))
        return
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    # a cell of its own, whether or not BENCHMARK.json lists the pair; no
    # limits, the readings are what counts here
    cell = {"spec": harness.load_json(harness.ROOT / "BENCHMARK.json"),
            "workload": {"name": f"{args.config}.{args.traffic}", "chips": 1},
            "limits": {},
            **harness.load_pair(args.config, args.traffic)}
    ref_mod = importlib.import_module(
        f"bench.reference.{cell['config']['reference']}")
    ref_cfg = harness.resolve_reference(cell["config"], cell["traffic"])
    with open(args.out, "a") as out:
        for seed in seeds:
            cycle = traffic_lib.PromptCycle(
                traffic_lib.make_prompts(cell["traffic"], seed),
                cell["traffic"]["batch_prompts"])
            batches = [cycle.batch(i) for i in range(harness.WARM_STEPS)]
            if args.rollout_only:
                r = harness.measure(cell, seed, 0.0, False, follow=False)
                x_ref = ref_mod.first_rollout(ref_cfg, seed, batches[0])
                rows = [("program", {"rollout_gap": compare.rollout_gap(
                    r["x0"]["program"], x_ref)}, None)]
                if seed in controls:
                    x_ctl = ref_mod.first_rollout(ref_cfg, seed, batches[0],
                                                  quant=CONTROL_DTYPE)
                    rows.append(("control", {"rollout_gap":
                                 compare.rollout_gap(x_ctl, x_ref)}, None))
                ref = None
            else:
                r = harness.measure(cell, seed, 0.0, False)
                ref, x_ref = r["readings"]["reference"], r["x0"]["reference"]
                rows = [("program", r["readings"]["read"],
                         r["readings"]["program"])]
                if seed in controls:
                    for kind, kw in (("control", {"quant": CONTROL_DTYPE}),
                                     ("half_batch", {"keep": 0.5})):
                        other = ref_mod.run_steps(ref_cfg, seed, batches,
                                                  **kw)
                        read = compare.readings(other, ref)
                        read["rollout_gap"] = compare.rollout_gap(
                            other.pop("x0"), x_ref)
                        rows.append((kind, read, other))
            for kind, read, side in rows:
                line = json.dumps({"seed": seed, "kind": kind, **read,
                                   **judged(read, limits),
                                   "side": side, "reference": ref})
                print(line, flush=True)
                out.write(line + "\n")
                out.flush()


if __name__ == "__main__":
    main()
