"""The benchmark harness end to end at tiny sizes on the CPU: every file a
cell names resolves, a run prints its result line, the control departs
from the reference, and the measuring path refuses a host without a
TPU."""
import json
import os
import subprocess
import sys

import pytest

from bench import compare, harness, traffic as traffic_lib
from bench.reference import flux_dit as reference
from bench.tools.tiny import shrink

ROOT = harness.ROOT
CELLS = [w["name"] for w in harness.load_json(
    ROOT / "BENCHMARK.json")["workloads"]]
SEED = 2 ** 31 + 11


def test_cells_resolve_by_name():
    spec = harness.load_json(ROOT / "BENCHMARK.json")
    for w in spec["workloads"]:
        cell = harness.resolve_cell(w["name"])
        assert cell["config"]["name"] == w["config"]
        for k in ("batch_prompts", "group_size", "num_steps",
                  "latent_tokens", "latent_dim"):
            assert cell["traffic"][k] > 0
        assert (ROOT / "bench" / "limits" / f"{w['name']}.json").exists()
        for m in harness.per_layer_for(spec, w["name"]):
            assert callable(harness.metric_reader(m["name"]))
    for c in spec["configs"]:
        assert (ROOT / c["file"]).exists()


def test_per_layer_metrics_follow_their_cells():
    """A metric that lists its cells is read there; one without a list is
    read in every cell that reports the end-to-end metric it moves."""
    spec = {"end_to_end": [{"name": "traj_per_s"},
                           {"name": "req_per_s", "workloads": ["serve"]}],
            "per_layer": [{"name": "a", "moves": "traj_per_s",
                           "workloads": ["train"]},
                          {"name": "b", "moves": "traj_per_s"},
                          {"name": "c", "moves": "req_per_s"}]}
    assert [m["name"] for m in harness.per_layer_for(spec, "train")] == \
        ["a", "b"]
    assert [m["name"] for m in harness.per_layer_for(spec, "serve")] == \
        ["b", "c"]


def test_peaks_are_keyed_by_device_kind():
    p = harness.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        harness.peaks_for("TPU v0 imaginary")


def test_no_tpu_is_refused():
    with pytest.raises(SystemExit, match="no TPU"):
        harness.check_chip(1)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         CELLS[0], "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


@pytest.mark.parametrize("workload,trace,seed", [(CELLS[0], False, SEED),
                                                 (CELLS[-1], True, SEED + 1)])
def test_tiny_run(workload, trace, seed, capsys, monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    cell = harness.resolve_cell(workload)
    r = harness.measure(cell, seed, 0.5, trace,
                        require_tpu=False, edit=shrink)
    assert set(r) >= {"correct", "attempted", "failed", "metrics",
                      "device", "checks"}
    assert list(r)[-3:] == ["checks", "readings", "x0"]
    assert r["attempted"] > 0 and r["failed"] == 0
    if trace:       # no device plane on the CPU: every reader is silent
        assert r["metrics"] == {}
        assert r["device"]["busy_s"] == 0.0 and r["device"]["window_s"] > 0
    else:
        assert set(r["metrics"]) == {"traj_per_s", "setup_s"}
    assert r["checks"]["window_compiles"]["value"] == 0
    read = r["readings"]["read"]
    for k in cell["limits"]:
        assert 0.0 <= read[k] < 0.05, (k, read)
    harness.report(r)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check failed_trajectories")

    # the control, the reference computed with fp8 operands, departs from
    # the reference by three times what the system (bfloat16) does, on
    # some compared number
    cycle = traffic_lib.PromptCycle(
        traffic_lib.make_prompts(cell["traffic"], seed),
        cell["traffic"]["batch_prompts"])
    control = reference.run_steps(
        harness.resolve_reference(cell["config"], cell["traffic"]), seed,
        [cycle.batch(i) for i in range(harness.WARM_STEPS)],
        quant="float8_e4m3fn")
    ctrl = compare.readings(control, r["readings"]["reference"])
    ctrl["rollout_gap"] = compare.rollout_gap(control["x0"],
                                              r["x0"]["reference"])
    assert max(ctrl[n] / max(read[n], 1e-12) for n in cell["limits"]) >= 3
