"""One benchmark run: a training cell of ``BENCHMARK.json`` on the chip.

The run, in order:

1. Resolve the cell by name: ``bench/configs/<config>.json`` (the model and
   trainer as run), ``bench/traffic/<traffic>.json`` (batch, steps, latent
   geometry, prompt pool), ``bench/peaks/<device kind>.json``.  Refuse a
   host without a TPU, with fewer chips than the cell asks for, or whose
   device kind has no peaks file.
2. Set-up: the system's ``Experiment`` builds its trainer (weights from the
   seed) and condition cache (prompts from the seed), then ``train()``
   runs the closed loop (``TrainLoop``, pipeline 1, donated state).  The
   first ``WARM_STEPS`` steps compile and warm the three programs
   (``sample``, ``rewards``, ``update``); the callback records what the
   reference comparison needs from them.
3. Window: whole steps of the same loop until ``--seconds`` have passed,
   then ``loop.request_stop()``.  Compilations inside the window are
   counted.  With ``--trace 1`` the window is profiled, and the host spans
   ``bench.dispatch``/``bench.conditions``/``bench.drain`` mark what the
   host did.
4. After the window: the device's peak memory; then, with the system's
   state freed, the plain float32 reference follows the same first steps
   from the seed and ``bench.compare`` decides ``correct``.
"""
from __future__ import annotations

import copy
import dataclasses
import gc
import glob
import importlib
import importlib.util
import json
import math
import os
import re
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, trace as trace_lib, traffic as traffic_lib

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
WARM_STEPS = 3            # set-up steps; the reference follows these
# every new executable, compiled or loaded from the persistent cache
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


# ------------------------------------------------------------------ files
def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_pair(config: str, traffic: str) -> Dict:
    """{config, traffic}: the files of that configuration and mix."""
    return {"config": load_json(BENCH / "configs" / f"{config}.json"),
            "traffic": load_json(BENCH / "traffic" / f"{traffic}.json")}


def resolve_cell(name: str) -> Dict:
    """{spec, workload, config, traffic, limits} of the cell ``name``."""
    spec = load_json(ROOT / "BENCHMARK.json")
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise SystemExit(f"bench: no workload {name!r}; "
                         f"known: {sorted(by_name)}")
    w = by_name[name]
    return {"spec": spec, "workload": w,
            "limits": load_json(BENCH / "limits" / f"{name}.json"),
            **load_pair(w["config"], w["traffic"])}


def peaks_for(kind: str) -> Dict:
    path = BENCH / "peaks" / (re.sub(r"[^A-Za-z0-9_.-]", "_", kind)
                              + ".json")
    if not path.exists():
        raise SystemExit(f"bench: no peaks for device kind {kind!r} "
                         f"({path.name}); nothing was measured")
    return load_json(path)


def check_chip(chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU (JAX sees {devs[0].platform} "
                         "devices); nothing was measured")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}; nothing was measured")
    return devs


def metric_reader(name: str) -> Callable:
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer_for(spec: Dict, workload: str) -> List[Dict]:
    """The per-layer metrics this cell reports: those that list it, and
    those without a list that move an end-to-end metric it reports."""
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    reported = {n for n, m in e2e.items()
                if workload in m.get("workloads", [workload])}
    return [m for m in spec["per_layer"]
            if workload in m.get("workloads", [])
            or ("workloads" not in m and m["moves"] in reported)]


# ---------------------------------------------------------------- configs
def resolve_reference(config: Dict, traffic: Dict) -> Dict:
    """The flat configuration the reference reads."""
    run = config["run"]
    f = run["flow"]
    out = {"arch": run["arch_overrides"], "param_dtype": run["param_dtype"],
           "optim": run["optim"], "microbatch": run["dist"]["microbatch"],
           "encoder": run["data"]["encoder"], "rewards": f["rewards"]}
    for k in ("trainer_type", "sde_type", "eta", "clip_range", "kl_coef",
              "advantage_agg", "timestep_sampling"):
        out[k] = f[k]
    for k in ("num_steps", "group_size", "latent_tokens", "latent_dim",
              "batch_prompts"):
        out[k] = traffic[k]
    return out


def run_config(config: Dict, traffic: Dict, seed: int, prompts: List[str],
               cond_dir: str):
    """The system's RunConfig: the configuration's ``run`` section, the
    traffic's sizes, the seed and a closed loop that runs until stopped."""
    from repro.config import RunConfig, from_dict
    run = copy.deepcopy(config["run"])
    run["flow"].update(
        num_steps=traffic["num_steps"], group_size=traffic["group_size"],
        latent_tokens=traffic["latent_tokens"],
        latent_dim=traffic["latent_dim"], preprocessing=True,
        cache_dir=cond_dir)
    run["data"].update(dataset="bench_prompts", n_prompts=len(prompts),
                       batch_prompts=traffic["batch_prompts"],
                       args={"prompts": prompts})
    run["optim"].update(total_steps=10 ** 9, warmup_steps=0)
    run["loop"] = {"steps": 10 ** 9, "pipeline": 1, "log_every": 0,
                   "save_every": 0, "resume": False,
                   "ckpt_dir": os.path.join(cond_dir, "ckpt")}
    run["seed"] = seed
    return from_dict(RunConfig, run)


# ----------------------------------------------------------------- window
def leaf_dict(tree) -> Dict[str, Any]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(getattr(k, "key", k)) for k in path): leaf
            for path, leaf in flat}


def host_change_norms(p0: Dict, p1: Dict) -> Dict[str, float]:
    return {k: float(np.linalg.norm(np.asarray(p1[k], np.float32).ravel()
                                    - np.asarray(p0[k], np.float32).ravel()))
            for k in p0}


class Window:
    """TrainLoop callback: records the set-up steps the reference follows,
    then times whole steps until ``seconds`` have passed."""

    def __init__(self, seconds: float, beta1: float, compiles: List,
                 trace_dir: Optional[str]):
        self.seconds = seconds
        self.beta1 = beta1
        self.compiles = compiles
        self.trace_dir = trace_dir
        self.rows: List[Dict] = []
        self.drained: List[float] = []
        self.norms = jax.jit(lambda t: jax.tree.map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))),
            t))
        self.loop = None
        self.t0 = self.t1 = None
        self.compiles_at_start = 0
        self.x0 = None

    def on_train_start(self, loop):
        self.loop = loop
        self.p0 = jax.device_get(leaf_dict(loop.trainer.state.params))
        sample = loop.trainer.sample

        def first_sample(*a, **kw):
            # the first step's rollout, fetched whole (no program of its own)
            traj = sample(*a, **kw)
            if self.x0 is None:
                self.x0 = np.asarray(jax.device_get(traj.xs))[-1]
            return traj

        loop.trainer.sample = first_sample
        if self.trace_dir:
            annotate(loop.trainer, "step", "bench.dispatch")
            annotate(loop.provider, "get", "bench.conditions")
            annotate(loop, "_drain_one", "bench.drain")

    def on_step(self, loop, step, row):
        self.rows.append(row)
        self.drained.append(time.perf_counter())
        n = len(self.rows)
        if n == 1:
            mu = leaf_dict(loop.trainer.state.opt.mu)
            self.first_grad = {k: float(v) / (1.0 - self.beta1)
                               for k, v in jax.device_get(
                                   self.norms(mu)).items()}
        if n == WARM_STEPS:
            self.p3 = jax.device_get(leaf_dict(loop.trainer.state.params))
            self.compiles_at_start = len(self.compiles)
            if self.trace_dir:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(self.trace_dir,
                                         profiler_options=opts)
            self.t0 = time.perf_counter()
        elif n > WARM_STEPS:
            self.t1 = time.perf_counter()
            if self.t1 - self.t0 >= self.seconds:
                loop.request_stop()

    def on_train_end(self, loop, history):
        if self.trace_dir and self.t0 is not None:
            jax.profiler.stop_trace()

    @property
    def window_rows(self) -> List[Dict]:
        return self.rows[WARM_STEPS:]


def annotate(obj, attr: str, span: str) -> None:
    """Wrap ``obj.attr`` (on the instance) in a profiler host span."""
    fn = getattr(obj, attr)

    def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation(span):
            return fn(*a, **kw)

    setattr(obj, attr, wrapped)


# -------------------------------------------------------------------- run
def measure(cell: Dict, seed: int, seconds: float, trace: bool, *,
            require_tpu: bool = True, edit: Optional[Callable] = None,
            t_start: Optional[float] = None, follow: bool = True) -> Dict:
    """One run of a cell (``resolve_cell``).  Returns the result object;
    ``checks`` holds the compared numbers beside their limits, ``x0`` the
    first step's final latents of both sides.  ``follow=False`` returns
    after the window with the program's ``x0`` alone (calibration of the
    rollout).
    ``edit(config, traffic)`` may change the resolved files in place
    (tests run the cells at tiny sizes); ``require_tpu=False`` skips the
    chip check, leaves JAX's compilation cache settings alone and keeps the
    condition cache in a temporary directory (tests)."""
    t_start = time.perf_counter() if t_start is None else t_start
    w, config, traffic = cell["workload"], cell["config"], cell["traffic"]
    workload = w["name"]
    if edit is not None:
        edit(config, traffic)

    if require_tpu:
        devs = check_chip(w["chips"])
        # the persistent compilation cache, at a fixed path in the checkout,
        # also where the environment names another: a checkout shares its
        # compiled programs with no other checkout
        cache = str(ROOT / ".jax_cache")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
        jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        # the system's encoder and reward programs hold their frozen
        # weights as constants: 0.2-0.5 GB executables
        jax.config.update("jax_compilation_cache_max_size", 4 << 30)
    else:
        devs = jax.devices()
    dev = devs[0]
    peaks = peaks_for(dev.device_kind) if require_tpu else None
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}", flush=True)

    from repro import registry
    from repro.api import Experiment
    registry.register("dataset", "bench_prompts", override=True)(
        traffic_lib.prompt_cycle)

    prompts = traffic_lib.make_prompts(traffic, seed)
    # the condition cache: a fixed path in the checkout on the chip, a
    # directory of the process's own where tests run side by side
    cond_root = (ROOT / ".bench_cache" / "cond" if require_tpu
                 else Path(tempfile.mkdtemp(prefix="bench-cond-")))
    cond_dir = cond_root / f"{workload}-{seed}"
    shutil.rmtree(cond_dir, ignore_errors=True)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    compiles: List[float] = []
    cache_hits: List[str] = []

    def on_compile(event, secs, **kw):
        if event == COMPILE_EVENT:
            compiles.append(secs)

    def on_cache_hit(event, **kw):
        if event == CACHE_HIT_EVENT:
            cache_hits.append(event)

    beta1 = config["run"]["optim"]["betas"][0]
    win = Window(seconds, beta1, compiles, trace_dir)
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    jax.monitoring.register_event_listener(on_cache_hit)
    try:
        exp = Experiment.from_config(run_config(config, traffic, seed,
                                                prompts, str(cond_dir)))
        exp.train(callbacks=[win])
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
        jax.monitoring.unregister_event_listener(on_cache_hit)
        shutil.rmtree(cond_dir if require_tpu else cond_root,
                      ignore_errors=True)
    setup_s = win.t0 - t_start
    window_s = win.t1 - win.t0
    rows = win.window_rows
    B = traffic["batch_prompts"] * traffic["group_size"]
    failed = B * sum(not (math.isfinite(r["loss"])
                          and math.isfinite(r["grad_norm"])) for r in rows)
    in_window = len(compiles) - win.compiles_at_start
    print(f"[setup] seconds={setup_s:.3f} set-up steps drained at "
          + " ".join(f"{t - t_start:.3f}" for t in
                     win.drained[:WARM_STEPS])
          + f" programs={win.compiles_at_start} of which from the "
          f"persistent cache {len(cache_hits)}", flush=True)
    print(f"[window] steps={len(rows)} seconds={window_s:.6f} "
          f"compiles={in_window}", flush=True)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())
    shapes = {k: tuple(v.shape) for k, v in win.p0.items()}
    prog = {"loss": [r["loss"] for r in win.rows[:WARM_STEPS]],
            "grad_norms": [win.first_grad],
            "change_norms": host_change_norms(win.p0, win.p3)}

    # free the system's state before the reference takes the device
    win.loop = win.p0 = win.p3 = None
    del exp
    gc.collect()
    jax.clear_caches()

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    if not follow:
        return {"x0": {"program": win.x0}, "prompts": prompts,
                "device": device}
    if trace:
        metrics, breakdown = traced_metrics(cell, trace_dir, rows, shapes,
                                            peaks, device)
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        units = {m["name"]: m["unit"] for m in cell["spec"]["end_to_end"]}
        metrics = {
            "traj_per_s": {"value": len(rows) * B / window_s,
                           "unit": units["traj_per_s"]},
            "setup_s": {"value": setup_s, "unit": units["setup_s"]}}
        breakdown = None

    ref_mod = importlib.import_module(f"bench.reference.{config['reference']}")
    ref_cfg = resolve_reference(config, traffic)
    cycle = traffic_lib.PromptCycle(prompts, traffic["batch_prompts"])
    t_ref = time.perf_counter()
    ref = ref_mod.run_steps(ref_cfg, seed,
                            [cycle.batch(i) for i in range(WARM_STEPS)])
    print(f"[reference] seconds={time.perf_counter() - t_ref:.3f}",
          flush=True)
    x0 = {"program": win.x0, "reference": ref.pop("x0")}
    read = compare.readings(prog, ref)
    read["rollout_gap"] = compare.rollout_gap(x0["program"],
                                              x0["reference"])
    correct, checks = compare.verdict(read, cell["limits"])
    correct = correct and failed == 0 and in_window == 0
    checks["window_compiles"] = {"value": in_window, "limit": 0}
    checks["failed_trajectories"] = {"value": failed, "limit": 0}

    result = {"correct": correct, "attempted": len(rows) * B,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    result["readings"] = {"program": prog, "reference": ref, "read": read}
    result["x0"] = x0
    return result


def traced_metrics(cell, trace_dir, rows, shapes, peaks, device):
    """(per-layer metrics, breakdown) from the window's trace."""
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    tr = trace_lib.load(files[-1])
    lo, hi = trace_lib.window(tr)
    device["busy_s"] = trace_lib.busy_ns(tr, lo, hi) / 1e9
    device["window_s"] = (hi - lo) / 1e9
    ctx = Context(trace=tr, lo=lo, hi=hi, steps=len(rows),
                  config=cell["config"], traffic=cell["traffic"],
                  peaks=peaks, shapes=shapes)
    metrics = {}
    for m in per_layer_for(cell["spec"], cell["workload"]["name"]):
        value = metric_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    breakdown = {"device_ops": [list(x) for x in
                                trace_lib.top_ops(tr, lo, hi)],
                 "idle_gaps": [list(x) for x in
                               trace_lib.idle_gaps(tr, lo, hi)]}
    return metrics, breakdown


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader gets: the reduced trace and its
    window, the window's step count, the cell's files, the device's peaks
    and the parameter shapes."""
    trace: Any
    lo: float
    hi: float
    steps: int
    config: Dict
    traffic: Dict
    peaks: Optional[Dict]
    shapes: Dict

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    @property
    def batch(self) -> int:
        return self.traffic["batch_prompts"] * self.traffic["group_size"]


def report(result: Dict) -> None:
    """The result line last on stdout; the compared numbers beside their
    limits last on stderr."""
    print("[compare] " + json.dumps(result["readings"]), flush=True)
    line = {k: v for k, v in result.items() if k not in ("readings", "x0")}
    print(json.dumps(line), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
