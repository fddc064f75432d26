"""End-to-end behaviour tests for the paper's system: full preprocessing →
rollout → multi-reward → update pipeline, and the dry-run/roofline path on a
small host mesh (subprocess — device count must be set before jax init)."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, registry
from repro.config import FlowRLConfig, OptimConfig, RewardSpec
from repro.core.preprocess import (ConditionProvider, FrozenTextEncoder,
                                   PreprocessCache, preprocess_dataset)
from repro.data import PromptDataset, synthetic_prompts

KEY = jax.random.PRNGKey(0)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_full_pipeline_end_to_end(tmp_path):
    """The paper's workflow: preprocess prompts once (frozen encoder then
    offloaded), train GRPO on cached conditions with two deduplicated
    rewards, reward improves."""
    prompts = synthetic_prompts(8)
    cache = PreprocessCache(str(tmp_path))
    enc_kw = dict(cond_dim=512, cond_len=4, vocab=512, hidden=64)
    preprocess_dataset(prompts, cache, encoder=FrozenTextEncoder(**enc_kw))
    provider = ConditionProvider(preprocessing=True, cache=cache)

    flow = FlowRLConfig(
        num_steps=4, group_size=4, latent_tokens=8, latent_dim=8,
        advantage_agg="gdpo",
        rewards=(RewardSpec("text_render", 1.0,
                            args={"latent_dim": 8, "latent_tokens": 8}),
                 RewardSpec("pickscore", 0.2, model_id="ps",
                            args={"latent_dim": 8}),
                 RewardSpec("pref_group", 0.2, model_id="ps",
                            args={"latent_dim": 8})))
    trainer = registry.build(
        "trainer", "flow_grpo", configs.get_reduced("flux_dit"), flow,
        OptimConfig(lr=3e-4, total_steps=40, warmup_steps=2), key=KEY)
    assert trainer.loader.unique_loads == 2      # dedup across 3 specs

    ds = PromptDataset(prompts, batch_size=4)
    rewards = []
    for it, batch_prompts in zip(range(16), ds.infinite()):
        cond = provider.get(batch_prompts)["cond"]
        m = trainer.step(cond, KEY, it=it)
        rewards.append(float(m["reward_mean"]))
    assert not provider.encoder_resident          # offload held throughout
    assert np.mean(rewards[-4:]) > np.mean(rewards[:4]), rewards


def test_trainer_switch_is_config_only():
    """Paper §4.2: switching trainer_type in config is the ONLY change
    needed to run a different algorithm on the same backbone + rewards."""
    arch_cfg = configs.get_reduced("flux_dit")
    flow_cfg = FlowRLConfig(num_steps=3, group_size=2, latent_tokens=8,
                            latent_dim=8)
    opt_cfg = OptimConfig(total_steps=4)
    for tname in ("flow_grpo", "mix_grpo", "grpo_guard", "nft", "awm"):
        tr = registry.build("trainer", tname, arch_cfg, flow_cfg, opt_cfg,
                            key=KEY)
        m = tr.step(jax.random.normal(KEY, (2, 4, 512)), KEY, it=0)
        assert jnp.isfinite(m["loss"]), tname


def test_dryrun_small_mesh_subprocess(tmp_path):
    """The dry-run machinery works end-to-end on a small host mesh: lower +
    compile + memory/collective analysis."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro import configs
from repro.config import InputShape
from repro.launch.specs import build_step
from repro.launch import hlo_stats
from repro.launch.mesh import make_local_mesh
mesh = make_local_mesh(4, 2)
cfg = configs.get_reduced("qwen3-32b")
shape = InputShape("t", 128, 8, "train")
with mesh:
    fn, args = build_step(cfg, shape, mesh)
    compiled = fn.lower(*args).compile()
coll = hlo_stats.collective_bytes(compiled.as_text())
assert coll["_total"]["count"] > 0, coll
mem = compiled.memory_analysis()
assert mem.argument_size_in_bytes > 0
print("SUBPROCESS_OK", coll["_total"]["count"])
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600,
                       env={**os.environ, "PYTHONPATH":
                            os.path.join(REPO, "src")})
    assert "SUBPROCESS_OK" in r.stdout, r.stderr[-2000:]


def test_dryrun_decode_small_mesh_subprocess():
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro import configs
from repro.config import InputShape
from repro.launch.specs import build_step
from repro.launch.mesh import make_local_mesh
mesh = make_local_mesh(4, 2)
for arch in ("mamba2-370m", "zamba2-2.7b", "deepseek-v2-236b"):
    cfg = configs.get_reduced(arch)
    shape = InputShape("d", 256, 8, "decode")
    with mesh:
        fn, args = build_step(cfg, shape, mesh)
        fn.lower(*args).compile()
print("SUBPROCESS_OK")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600,
                       env={**os.environ, "PYTHONPATH":
                            os.path.join(REPO, "src")})
    assert "SUBPROCESS_OK" in r.stdout, r.stderr[-2000:]


def test_costs_model_consistency():
    """Analytic cost model sanity: train > prefill > decode FLOPs; MoE
    active ≪ total; long-context decode uses the window."""
    from repro.launch import costs
    from repro.config import INPUT_SHAPES
    cfg = configs.get("yi-9b")
    tr = costs.step_costs(cfg, INPUT_SHAPES["train_4k"])
    pf = costs.step_costs(cfg, INPUT_SHAPES["prefill_32k"])
    dc = costs.step_costs(cfg, INPUT_SHAPES["decode_32k"])
    assert tr.flops > pf.flops > dc.flops
    assert tr.flops_kernel < tr.flops          # causal skipping helps
    moe = configs.get("deepseek-v2-236b")
    assert moe.n_active_params() < 0.2 * moe.n_params()
    lk = costs.step_costs(configs.get("yi-34b"), INPUT_SHAPES["long_500k"])
    assert "window" in lk.notes


def test_hlo_stats_trip_count_expansion():
    """Collectives inside a scanned body are multiplied by the trip count."""
    from repro.launch import hlo_stats
    hlo = """
HloModule test

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %ar = f32[8]{0} all-reduce(f32[8]{0} %x), replica_groups=[1,4]<=[4]
}

%cond (p: (s32[], f32[8])) -> pred[] {
  %c = s32[] constant(12)
  %cmp = pred[] compare(s32[] %i, s32[] %c), direction=LT
}

ENTRY %main () -> f32[8] {
  %w = (s32[], f32[8]) while((s32[], f32[8]) %init), condition=%cond, body=%body
  %ag = f32[16]{0} all-gather(f32[8]{0} %y), replica_groups=[2,2]<=[4]
}
"""
    coll = hlo_stats.collective_bytes(hlo)
    assert coll["all-reduce"]["count"] == 12
    assert coll["all-gather"]["count"] == 1
    assert coll["all-reduce"]["result_bytes"] == 12 * 32


def test_compile_cache_defaults_to_fixed_dir_in_checkout(monkeypatch):
    from repro.launch import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert got == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    from repro.launch import compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_importing_repro_leaves_compile_cache_off():
    code = ("import jax, repro, repro.api, repro.launch.train; "
            "print(repr(jax.config.jax_compilation_cache_dir))")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.stdout.strip().splitlines()[-1] == "None", r.stderr[-2000:]
