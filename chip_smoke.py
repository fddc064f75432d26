#!/usr/bin/env python3
"""Chip smoke run: the Flow-GRPO train step at FLUX.1 widths on a TPU.

Drives the training path a user runs -- ``Experiment`` -> ``flow_grpo``
trainer with the ``flow_sde`` scheduler -> ``TrainLoop`` -- for a few steps
at flux_dit's published widths (d_model 3072, 24 heads x 128, d_ff 12288,
qk_norm, bfloat16 params).  Only the depth is cut, to what fits one TPU
v5e's 16 GB of HBM.  Weights are random, made from ``--seed``.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # dp=2 x mp=2 mesh vs one device

Without a TPU the script exits non-zero before any phase runs.  A failed
phase raises.  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Step times printed here are smoke timings, not a benchmark.
"""
from __future__ import annotations

import argparse
import gc
import importlib.metadata
import json
import math
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import jaxlib
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro import perf  # noqa: E402
from repro.api import Experiment  # noqa: E402
from repro.api.loop import Callback  # noqa: E402
from repro.config import (DataConfig, DistConfig, FlowRLConfig,  # noqa: E402
                          LoopConfig, OptimConfig, PerfConfig, RewardSpec,
                          RunConfig)
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.perf.memory import state_bytes  # noqa: E402

# flux_dit publishes 38 layers.  At full width 4 layers do not fit one
# v5e with any remat/microbatch setting (AOT memory_analysis: 17.3 GiB at
# best, see PERF.md); 3 layers fit under remat="scan" with 16 microbatches.
N_LAYERS = 3
PUBLISHED_LAYERS = 38
STEPS = 3
FOUR_CHIP_STEPS = 2
PROMPTS = 2
GROUP = 8
# per-device microbatch of one trajectory: 16 chunks on one device, 8 on
# the dp=2 mesh (each chunk splits over the data axis)
MICROBATCH_ONE_DEVICE = 16
MICROBATCH_DP2 = 8
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def run_config(seed: int, dist: DistConfig, steps: int,
               cache_dir: str) -> RunConfig:
    return RunConfig(
        arch="flux_dit",
        arch_overrides={"n_layers": N_LAYERS},
        flow=FlowRLConfig(
            trainer_type="flow_grpo", sde_type="flow_sde",
            num_steps=10, group_size=GROUP,
            # FLUX's packed latent of a 512x512 image
            latent_tokens=1024, latent_dim=64,
            rewards=(RewardSpec("pickscore"), RewardSpec("text_render")),
            cache_dir=cache_dir),
        optim=OptimConfig(lr=1e-4, schedule="constant", total_steps=steps),
        dist=dist,
        perf=PerfConfig(remat="scan"),
        data=DataConfig(batch_prompts=PROMPTS),
        loop=LoopConfig(steps=steps, log_every=0, save_every=0,
                        resume=False),
        seed=seed)


class StepProbe(Callback):
    """TrainLoop callback: per-step wall time taken after
    ``block_until_ready`` on the state, and the compilations since the
    previous step."""

    def __init__(self, compiles):
        self.compiles = compiles
        self.rows = []

    def on_train_start(self, loop):
        self.t = time.perf_counter()
        self.seen = len(self.compiles)

    def on_step(self, loop, step, m):
        jax.block_until_ready(loop.trainer.state)
        now = time.perf_counter()
        wall, self.t = now - self.t, now
        n, self.seen = len(self.compiles) - self.seen, len(self.compiles)
        peak = max(d.memory_stats()["peak_bytes_in_use"]
                   for d in jax.local_devices())
        self.rows.append(dict(m, wall_s=wall, compiles=n))
        print(f"[train] step {step}: loss={m['loss']:+.6e} "
              f"reward_mean={m['reward']:+.6f} "
              f"grad_norm={m['grad_norm']:.6e} wall={wall:.3f}s "
              f"(smoke timing, not a benchmark) compiles={n} "
              f"peak_bytes_in_use={peak}", flush=True)


def check_kernels(seed: int) -> None:
    """Phase 2: the Pallas kernels on the chip against the jnp references
    of kernels/ref.py, at the shapes the step runs.  Every error is printed
    before the first comparison can fail."""
    assert ops.pallas_enabled(), "Pallas dispatch is off on the TPU"
    B = PROMPTS * GROUP
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    v, x, eps = (jax.random.normal(k, (B, 1024, 64), jnp.float32)
                 for k in ks[:3])
    checks = []       # (name, got, want, atol, rtol)
    for t, t_next in ((0.9, 0.8), (0.2, 0.1)):
        t, t_next = jnp.float32(t), jnp.float32(t_next)
        xn, lp = ops.sde_step(v, x, eps, t, t_next, eta=0.7)
        xr, lr = ref.sde_step_ref(v, x, t, t_next, eps, eta=0.7)
        name = f"sde_step {x.shape} t={float(t):.1f}"
        checks += [(f"{name} x_next", xn, xr, 1e-5, 1e-5),
                   (f"{name} logp", lp, lr, 1e-3, 1e-5)]

    # log-ratio spread wide enough that some ratios leave the clip band
    lpn = jax.random.normal(ks[3], (B,)) * 0.2
    lpo = jax.random.normal(ks[4], (B,)) * 0.2
    adv = jax.random.normal(ks[5], (B,))
    loss, frac = ops.grpo_loss(lpn, lpo, adv, clip=0.2)
    lref, fref = ref.grpo_loss_ref(lpn, lpo, adv, clip=0.2)
    # the trainer differentiates through this wrapper (closed-form VJP)
    g = jax.grad(lambda a: ops.grpo_loss_trainable(
        a, lpo, adv, clip=0.2)[0].sum())(lpn)
    gref = jax.grad(lambda a: ref.grpo_loss_ref(
        a, lpo, adv, clip=0.2)[0].sum())(lpn)
    checks += [(f"grpo_loss B={B} loss", loss, lref, 1e-5, 1e-5),
               (f"grpo_loss B={B} clip fraction", frac, fref, 0.0, 0.0),
               (f"grpo_loss B={B} grad", g, gref, 1e-5, 1e-4)]

    checks = [(n, np.asarray(a), np.asarray(b), at, rt)
              for n, a, b, at, rt in checks]
    for name, got, want, atol, rtol in checks:
        print(f"[kernels] {name}: max|kernel-ref|="
              f"{np.abs(got - want).max():.3e} max|ref|="
              f"{np.abs(want).max():.3e} (atol={atol:g}, rtol={rtol:g})",
              flush=True)
    for name, got, want, atol, rtol in checks:
        np.testing.assert_allclose(got, want, atol=atol, rtol=rtol,
                                   err_msg=name)


def describe_arch(exp) -> None:
    a = exp.arch
    print(f"[model] flux_dit d_model={a.d_model} n_heads={a.n_heads} "
          f"head_dim={a.resolved_head_dim} d_ff={a.d_ff} "
          f"qk_norm={a.qk_norm} n_layers={a.n_layers} (reduced from "
          f"{PUBLISHED_LAYERS}) params=bfloat16", flush=True)


def compile_step(exp):
    """Phase 3: build the trainer and compile the step's programs ahead of
    time (jit then reuses them in step 1).  Returns {name: Compiled}."""
    tr = exp.build_trainer()
    n_params = sum(leaf.size for leaf in jax.tree.leaves(tr.state.params))
    print(f"[model] {n_params} parameters; state {state_bytes(tr)}",
          flush=True)
    cond = jax.ShapeDtypeStruct((PROMPTS, exp.cond_len, exp.cond_dim),
                                jnp.float32)
    compiled = {}
    for name, lowered in perf.lower_step(tr, cond).items():
        t0 = time.perf_counter()
        compiled[name] = lowered.compile()
        mem = perf.analysis_dict(compiled[name])
        print(f"[compile] {name}: {time.perf_counter() - t0:.3f}s "
              f"memory_analysis={mem}", flush=True)
    return compiled


def check_history(history) -> None:
    for row in history:
        for k in ("loss", "reward", "grad_norm"):
            assert math.isfinite(row[k]), (k, row)


def one_chip(seed: int, compiles) -> None:
    with tempfile.TemporaryDirectory() as cache_dir:
        exp = Experiment.from_config(run_config(
            seed, DistConfig(microbatch=MICROBATCH_ONE_DEVICE), STEPS,
            cache_dir))
        describe_arch(exp)
        compiled = compile_step(exp)
        tr = exp.build_trainer()

        @jax.jit
        def fingerprint(params):
            return jax.tree.map(
                lambda p: jnp.sum(jnp.square(p.astype(jnp.float32))), params)

        before = jax.device_get(fingerprint(tr.state.params))
        probe = StepProbe(compiles)
        result = exp.train(callbacks=[probe])
        after = jax.device_get(fingerprint(tr.state.params))
    history = result["history"]
    assert len(history) == STEPS, history
    check_history(history)
    late = sum(r["compiles"] for r in probe.rows[1:])
    print(f"[train] compiles after step 1: {late}", flush=True)
    assert late == 0, probe.rows
    changed = sum(a != b for a, b in zip(jax.tree.leaves(before),
                                         jax.tree.leaves(after)))
    print(f"[train] parameter leaves changed: {changed} of "
          f"{len(jax.tree.leaves(before))}", flush=True)
    assert changed > 0, "the train steps left every parameter unchanged"
    for name in ("sample", "update"):
        assert "tpu_custom_call" in compiled[name].as_text(), \
            f"no Pallas kernel in the compiled {name} program"
    print("[check] sample and update programs contain tpu_custom_call",
          flush=True)


def per_device_bytes(tree):
    out = {}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            key = str(shard.device.id)
            out[key] = out.get(key, 0) + shard.data.nbytes
    return out


class ParamSnapshots(Callback):
    """Host copy of the params after every drained step."""

    def __init__(self):
        self.params = []

    def on_step(self, loop, step, m):
        self.params.append(jax.device_get(loop.trainer.state.params))


def param_band(p1, p2):
    """(max |diff|, elements outside 2e-4 + 1e-3|x|, element count)."""
    n_tot = n_out = 0
    max_diff = 0.0
    for x, y in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        x = np.asarray(x, np.float32)
        d = np.abs(np.asarray(y, np.float32) - x)
        max_diff = max(max_diff, float(d.max()))
        n_out += int((d > (2e-4 + 1e-3 * np.abs(x))).sum())
        n_tot += x.size
    return max_diff, n_out, n_tot


def four_chips(seed: int, compiles) -> None:
    """The same steps on a dp=2 x mp=2 mesh and on one device, one process;
    loss and updated params after every step compared within the band of
    tests/test_distributed.py."""
    layouts = (("one device", DistConfig(microbatch=MICROBATCH_ONE_DEVICE)),
               ("dp=2 x mp=2", DistConfig(data_parallel=2, model_parallel=2,
                                          microbatch=MICROBATCH_DP2)))
    runs = {}
    for name, dist in layouts:
        with tempfile.TemporaryDirectory() as cache_dir:
            exp = Experiment.from_config(run_config(seed, dist,
                                                    FOUR_CHIP_STEPS,
                                                    cache_dir))
            describe_arch(exp)
            tr = exp.build_trainer()
            state = per_device_bytes(tr.state)
            print(f"[{name}] state bytes per device: {state}", flush=True)
            snaps = ParamSnapshots()
            result = exp.train(callbacks=[StepProbe(compiles), snaps])
            check_history(result["history"])
            runs[name] = (result["history"], snaps.params, state)
        # free this layout's device state before the next one is built
        del exp, tr, result
        gc.collect()

    (h1, p1, s1), (h22, p22, s22) = runs["one device"], runs["dp=2 x mp=2"]
    ratio = max(s22.values()) / max(s1.values())
    print(f"[parity] largest per-device state under dp=2 x mp=2 is "
          f"{ratio:.4f} of the single-device state", flush=True)
    bands = [param_band(a, b) for a, b in zip(p1, p22)]
    for a, b, (max_diff, n_out, n_tot) in zip(h1, h22, bands):
        print(f"[parity] step {a['step']}: loss {a['loss']:+.6e} vs "
              f"{b['loss']:+.6e}; reward_mean {a['reward']:+.6f} vs "
              f"{b['reward']:+.6f}; grad_norm {a['grad_norm']:.6e} vs "
              f"{b['grad_norm']:.6e}; params max|diff|={max_diff:.3e} "
              f"(bound 5e-3), {n_out} of {n_tot} elements outside "
              f"2e-4+1e-3|x| (bound {max(1, n_tot // 10_000)})",
              flush=True)
    assert ratio < 0.6, (s1, s22)
    for a, b, (max_diff, n_out, n_tot) in zip(h1, h22, bands):
        assert abs(a["loss"] - b["loss"]) <= 2e-4 + 1e-3 * abs(a["loss"]), \
            (a, b)
        assert max_diff <= 5e-3, (a["step"], max_diff)
        assert n_out <= max(1, n_tot // 10_000), (a["step"], n_out)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the dp=2 x mp=2 path and its "
                         "single-device comparison (needs 4 chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX sees "
                 f"{devices[0].platform} devices); nothing was run")
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        sys.exit(f"chip_smoke: needs {need} TPU chips, found "
                 f"{len(devices)}")

    cache = enable_compile_cache()
    d = devices[0]
    print(f"[env] platform={d.platform} device_kind={d.device_kind} "
          f"devices={len(devices)}", flush=True)
    print(f"[env] jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={importlib.metadata.version('libtpu')} "
          f"compile_cache={cache}", flush=True)

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(secs)
        if event == COMPILE_EVENT else None)
    if args.four_chips:
        four_chips(args.seed, compiles)
    else:
        check_kernels(args.seed)
        one_chip(args.seed, compiles)
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
