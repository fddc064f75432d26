"""Flow-Factory training launcher — a thin shell over the Experiment API.

One declarative :class:`RunConfig` drives both phases (paper §2.2):
preprocess-and-cache the prompt corpus, then RL fine-tune the selected
backbone via the shared :class:`repro.api.TrainLoop` with full-state
checkpointing (params + optimizer) and auto-resume.

Everything is config: pass a JSON file and/or dotted overrides — the
convenience flags (``--arch/--trainer/--sde``) derive their choices from
the registry, so they can never drift from what is registered.

  PYTHONPATH=src python -m repro.launch.train --reduced --steps 2
  PYTHONPATH=src python -m repro.launch.train --config run.json \\
      --set flow.eta=0.5 --set optim.lr=3e-4 --set loop.log_file=log.json

Distributed training runs on a 2-D (data × model) device mesh: prompt×group
batches shard over the "data" axis, params/optimizer moments over the
"model" axis per the PartitionPlan, with optional gradient-accumulation
microbatching (``repro.distributed``); on CPU, host devices are faked via
XLA_FLAGS:

  PYTHONPATH=src XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
      python -m repro.launch.train --reduced --steps 2 \\
      --set dist.data_parallel=4 --set dist.microbatch=2

  PYTHONPATH=src XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
      python -m repro.launch.train --reduced --steps 2 \\
      --set dist.data_parallel=2 --set dist.model_parallel=2

The equivalent programmatic path is ``Experiment.from_file("run.json")``
(see ROADMAP.md "Running experiments").
"""
from __future__ import annotations

import jax

from repro.api import Experiment
from repro.distributed import resolve_axes
from repro.launch.compile_cache import enable_compile_cache


def main(argv=None) -> None:
    enable_compile_cache()
    exp = Experiment.from_cli(argv)
    d = exp.describe()
    dp, mp = resolve_axes(exp.cfg.dist)
    print(f"[train] {d['trainer']['name']} on {d['arch']['name']} "
          f"({d['arch']['n_params']/1e6:.1f}M params), "
          f"sde={d['scheduler']['name']}, rewards={d['rewards']}")
    print(f"[train] devices={jax.local_device_count()} data_parallel={dp} "
          f"model_parallel={mp} microbatch={exp.cfg.dist.microbatch or 1}")
    p = exp.cfg.perf
    if exp.cfg.loop.pipeline != 1:
        print(f"[perf] loop.pipeline={exp.cfg.loop.pipeline} "
              "(metrics drain up to pipeline-1 steps late; computation "
              "is unchanged)")
    if p != type(p)():
        print(f"[perf] remat={p.remat} fuse_step={p.fuse_step}"
              + (f" policy_dtype={p.policy_dtype}" if p.policy_dtype else "")
              + (" offload_rewards=true" if p.offload_rewards else "")
              + (" remat_offload=true" if p.remat_offload else ""))
    if p.log_memory:
        tr = exp.build_trainer()
        d_cfg = exp.cfg.data
        cond = jax.ShapeDtypeStruct(
            (d_cfg.batch_prompts, exp.cond_len, exp.cond_dim),
            jax.numpy.float32)
        for name, mem in tr.memory_stats(cond).items():
            pretty = " ".join(
                f"{k[:-len('_bytes')]}={v / 1e6:.2f}MB"
                if k.endswith("_bytes") and isinstance(v, (int, float))
                else f"{k}={v}"
                for k, v in mem.items() if v is not None)
            print(f"[perf] {name} memory_analysis: {pretty}")
    result = exp.train()
    hist = result["history"]
    if hist:
        print(f"[train] steps {result['start_step']}..{result['final_step']}"
              f"; reward {hist[0]['reward']:+.4f} -> {hist[-1]['reward']:+.4f}")


if __name__ == "__main__":
    main()
