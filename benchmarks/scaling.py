"""Benchmark: mesh-layout scaling of the RL train step
(``repro.distributed`` tentpole).

Trains a few reduced-scale steps under each dp×mp layout in {1×1, 2×1,
4×1, 2×2} that the devices present can hold, all in this one process (a
chip belongs to one process at a time, so no child processes), and
reports mean post-compile step time plus the per-device state bytes under
the active PartitionPlan.  Layouts needing more devices than exist are
skipped with a note on stderr.  Every row names the device it ran on.

On XLA:CPU, give the process faked host devices before JAX starts
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``).  They share the
same cores, so there this measures the *overhead* of the sharded paths
(resharding + collectives + gradient accumulation), not speedup — the
derived column reports the slowdown factor vs single-device, which should
stay near 1 for dp-only layouts and shows the gather/reduce-scatter cost
the model axis adds in exchange for the per-device memory drop
(``state_per_device_bytes``).
"""
from __future__ import annotations

import sys
import time
from typing import Dict, List

STEPS = 4
LAYOUTS = ((1, 1), (2, 1), (4, 1), (2, 2))


def _step_time(dp: int, mp: int) -> Dict:
    import jax
    import jax.numpy as jnp
    from repro import configs, registry
    from repro.config import DistConfig, FlowRLConfig, OptimConfig, RewardSpec
    from repro.perf.memory import state_bytes

    flow = FlowRLConfig(num_steps=4, group_size=4, latent_tokens=8,
                        latent_dim=8, clip_range=0.2,
                        rewards=(RewardSpec("text_render", 1.0,
                                            args={"latent_dim": 8,
                                                  "latent_tokens": 8}),))
    opt = OptimConfig(lr=1e-3, total_steps=50, warmup_steps=2)
    key = jax.random.PRNGKey(0)
    tr = registry.build("trainer", "flow_grpo",
                        configs.get_reduced("flux_dit"), flow, opt, key=key,
                        dist=DistConfig(data_parallel=dp, model_parallel=mp))
    cond = jax.random.normal(key, (4, 4, 512), jnp.float32)
    jax.block_until_ready(tr.step(cond, key, it=0))          # compile
    t0 = time.perf_counter()
    for it in range(1, 1 + STEPS):
        tr.step(cond, key, it=it)
    jax.block_until_ready(tr.state)
    return {"step_s": (time.perf_counter() - t0) / STEPS,
            "state": state_bytes(tr)}


def run() -> List[Dict]:
    import jax
    n = jax.local_device_count()
    dev = jax.local_devices()[0]
    rows: List[Dict] = []
    base_s = None
    for dp, mp in LAYOUTS:
        if dp * mp > n:
            print(f"scaling: skipping dp={dp} mp={mp}: needs {dp * mp} "
                  f"devices, {n} present", file=sys.stderr)
            continue
        out = _step_time(dp, mp)
        if base_s is None:
            base_s = out["step_s"]
        # dp-only rows keep their historical names so stored benchmark
        # trajectories stay comparable across runs
        name = (f"train_step_dp{dp}" if mp == 1
                else f"train_step_dp{dp}mp{mp}")
        rows.append({
            "name": name,
            "us_per_call": round(out["step_s"] * 1e6, 1),
            "derived": {"platform": dev.platform,
                        "device_kind": dev.device_kind,
                        "devices": dp * mp,
                        "overhead_vs_dp1": round(out["step_s"] / base_s, 3),
                        "state_per_device_bytes":
                            out["state"]["per_device_bytes"],
                        "state_sharded_leaves":
                            out["state"]["sharded_leaves"]},
        })
    return rows
