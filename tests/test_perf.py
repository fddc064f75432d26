"""repro.perf — remat / fused-step / specialization / precision semantics.

Exactness contract under test (see repro/perf/__init__.py and the ROADMAP
"Performance" section):

* ``perf.remat="scan"``  : BIT-IDENTICAL to ``"none"`` on XLA:CPU — a
  ``jax.checkpoint`` around a ``lax.scan`` body is structurally isolated,
  so the rematerialized backward matches the original exactly (params
  compared bitwise after several optimizer steps).
* ``perf.remat="block"`` : f32-rounding-equal only — XLA re-fuses the
  open-graph remat; losses agree at rtol 1e-5 / atol 1e-6, and bf16
  parameters drift by single ulps once AdamW's rsqrt amplifies the noise.
* ``perf.fuse_step``     : same ops, different compiled program —
  parameters agree at rtol 1e-5 / atol 1e-6 after training steps.
* dead-branch specialization (all_sde / all_ode rollout bodies) is exact:
  it only removes computations whose results the mixed path discards.

The dist-composition tests run for real under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (``make verify``)
and skip on a single device.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, registry
from repro.config import (DistConfig, FlowRLConfig, OptimConfig, PerfConfig,
                          RewardSpec)
from repro.core import schedulers
from repro.core.rollout import rollout
from repro.models import params as params_lib
from repro.models.flow import FlowAdapter

ARCH = configs.get_reduced("flux_dit")
FLOW = FlowRLConfig(num_steps=8, group_size=4, latent_tokens=8, latent_dim=8,
                    clip_range=0.2,
                    rewards=(RewardSpec("text_render", 1.0,
                             args={"latent_dim": 8, "latent_tokens": 8}),))
OPT = OptimConfig(lr=1e-3, total_steps=50, warmup_steps=2)
KEY = jax.random.PRNGKey(0)
COND = jax.random.normal(jax.random.PRNGKey(7), (2, 4, 512), jnp.float32)

# bf16 params: one ulp at |w|~0.25 is ~2e-3; AdamW's rsqrt amplifies
# single-ulp grad noise to a few ulps after a couple of steps
BF16_ATOL = 0.02


def make(trainer_type="flow_grpo", perf=None, dist=None, flow=FLOW):
    return registry.build("trainer", trainer_type, ARCH, flow, OPT,
                          key=jax.random.PRNGKey(0), dist=dist, perf=perf)


def run_steps(tr, n=2, cond=COND):
    m = None
    for it in range(n):
        m = tr.step(cond, KEY, it=it)
    jax.block_until_ready(tr.state.params)
    return jax.device_get(m)


def params_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def params_close(a, b, rtol=1e-5, atol=1e-6):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32),
                                   rtol=rtol, atol=atol)


# ---------------------------------------------------------------- remat

def test_remat_scan_bit_identical_training():
    base, scan = make(), make(perf=PerfConfig(remat="scan"))
    mb, ms = run_steps(base, 3), run_steps(scan, 3)
    assert params_equal(base.state.params, scan.state.params)
    assert mb["reward_mean"] == ms["reward_mean"]
    assert mb["loss"] == ms["loss"]


def test_remat_scan_bit_identical_mix_grpo():
    """The masked (non-static) MixGRPO body under scan checkpoint too."""
    base = make("mix_grpo")
    scan = make("mix_grpo", perf=PerfConfig(remat="scan"))
    run_steps(base), run_steps(scan)
    assert params_equal(base.state.params, scan.state.params)


def test_remat_block_rounding_equal():
    base, blk = make(), make(perf=PerfConfig(remat="block"))
    traj = base.sample(base.state.params, COND, KEY, 0)
    _, adv, _ = base._rewards_jit(traj.x0, {"cond": traj.cond})
    lb = jax.jit(lambda p: base.loss_fn(p, traj, adv, KEY)[0])(
        base.state.params)
    lk = jax.jit(lambda p: blk.loss_fn(p, traj, adv, KEY)[0])(
        blk.state.params)
    np.testing.assert_allclose(float(lb), float(lk), rtol=1e-5, atol=1e-6)
    run_steps(base), run_steps(blk)
    params_close(base.state.params, blk.state.params,
                 rtol=BF16_ATOL, atol=BF16_ATOL)


def test_memory_temp_bytes_drop_with_scan_remat():
    """memory_analysis() regression: the loss scan's stored residuals
    dominate update temp memory at num_steps=8; scan remat must cut peak
    temp bytes strictly — and by ≥30%, the bench acceptance threshold
    (deterministic compile-time analysis, so asserted here too)."""
    cond = jax.ShapeDtypeStruct(COND.shape, COND.dtype)
    mems = {mode: make(perf=PerfConfig(remat=mode)).memory_stats(cond)
            for mode in ("none", "scan", "block")}
    temp = {mode: m["update"]["temp_bytes"] for mode, m in mems.items()}
    assert temp["scan"] < temp["none"], temp
    assert temp["block"] < temp["none"], temp
    assert temp["scan"] <= 0.7 * temp["none"], temp


# ---------------------------------------------------------------- fusion

@pytest.mark.parametrize("trainer_type", ["flow_grpo", "nft", "awm"])
def test_fused_step_matches_unfused(trainer_type):
    base = make(trainer_type)
    fused = make(trainer_type, perf=PerfConfig(fuse_step=True))
    assert fused._fused_jit is not None
    mb, mf = run_steps(base), run_steps(fused)
    params_close(base.state.params, fused.state.params)
    np.testing.assert_allclose(mb["reward_mean"], mf["reward_mean"],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(mb["loss"], mf["loss"], rtol=1e-5, atol=1e-5)


def test_fused_composes_with_remat_and_microbatch():
    base = make()
    fused = make(perf=PerfConfig(remat="scan", fuse_step=True),
                 dist=DistConfig(microbatch=2))
    run_steps(base), run_steps(fused)
    # microbatching reorders the f32 grad reduction (test_distributed's
    # documented tolerance class); AdamW amplifies to bf16-ulp scale
    params_close(base.state.params, fused.state.params,
                 rtol=BF16_ATOL, atol=BF16_ATOL)


def test_step_metrics_are_device_scalars():
    """Both step paths return device values fetched with ONE device_get —
    reward_mean (weight_map-weighted) and per-reward means included."""
    for tr in (make(), make(perf=PerfConfig(fuse_step=True))):
        m = tr.step(COND, KEY, it=0)
        assert {"reward_mean", "reward/text_render:0", "loss",
                "grad_norm"} <= set(m)
        assert all(isinstance(v, jax.Array) for v in m.values()), {
            k: type(v) for k, v in m.items()}
        host = jax.device_get(m)
        w = tr.loader.weight_map()["text_render:0"]
        np.testing.assert_allclose(
            host["reward_mean"], w * host["reward/text_render:0"], rtol=1e-6)


def test_fuse_step_rejects_attached_engine():
    from repro.serving import ServingEngine
    tr = make(perf=PerfConfig(fuse_step=True))
    with pytest.raises(ValueError, match="fuse_step"):
        tr.attach_engine(ServingEngine.for_trainer(tr))


# --------------------------------------------- dead-branch specialization

def _adapter_setup():
    flow = FlowRLConfig(num_steps=6, latent_tokens=8, latent_dim=8)
    ad = FlowAdapter(ARCH, flow, 512)
    params = params_lib.init(ad.spec(), jax.random.PRNGKey(1), jnp.bfloat16)
    cond = jax.random.normal(jax.random.PRNGKey(2), (4, 4, 512), jnp.float32)
    return ad, params, cond


def test_rollout_all_sde_specialization_exact():
    ad, params, cond = _adapter_setup()
    sde = schedulers.build("flow_sde", 0.7)
    ones = jnp.ones((6,), bool)
    mixed = jax.jit(lambda p, c, k: rollout(ad, p, c, k, sde, 6, ones))(
        params, cond, KEY)
    spec = jax.jit(lambda p, c, k: rollout(ad, p, c, k, sde, 6, ones,
                                           sde_mode="all_sde"))(
        params, cond, KEY)
    assert np.array_equal(np.asarray(mixed.xs), np.asarray(spec.xs))
    assert np.array_equal(np.asarray(mixed.logps), np.asarray(spec.logps))


def test_rollout_all_ode_specialization_exact():
    ad, params, cond = _adapter_setup()
    ode = schedulers.build("ode", 0.0)
    ones = jnp.ones((6,), bool)
    mixed = jax.jit(lambda p, c, k: rollout(ad, p, c, k, ode, 6, ones))(
        params, cond, KEY)
    spec = jax.jit(lambda p, c, k: rollout(ad, p, c, k, ode, 6, ones,
                                           sde_mode="all_ode"))(
        params, cond, KEY)
    assert np.array_equal(np.asarray(mixed.xs), np.asarray(spec.xs))
    assert not np.asarray(spec.logps).any()


def test_rollout_scan_remat_exact():
    ad, params, cond = _adapter_setup()
    sde = schedulers.build("flow_sde", 0.7)
    plain = jax.jit(lambda p, c, k: rollout(ad, p, c, k, sde, 6))(
        params, cond, KEY)
    remat = jax.jit(lambda p, c, k: rollout(ad, p, c, k, sde, 6,
                                            remat="scan"))(params, cond, KEY)
    assert np.array_equal(np.asarray(plain.xs), np.asarray(remat.xs))


def test_trainer_static_sde_modes():
    assert make("flow_grpo").sde_mode == "all_sde"
    assert make("grpo_guard").sde_mode == "all_sde"
    assert make("mix_grpo").sde_mode == "mixed"
    assert make("nft").sde_mode == "all_ode"
    assert make("awm").sde_mode == "all_ode"


# ------------------------------------------------------- dtype policy

def test_policy_dtype_explicit_bf16_matches_default():
    """policy_dtype="bfloat16" is exactly today's implicit behaviour when
    params are stored bf16 — the knob makes the cast explicit, not new."""
    base = make()
    bf16 = make(perf=PerfConfig(policy_dtype="bfloat16"))
    run_steps(base), run_steps(bf16)
    assert params_equal(base.state.params, bf16.state.params)


def test_policy_dtype_f32_runs_and_differs():
    base = make()
    f32 = make(perf=PerfConfig(policy_dtype="float32"))
    x_t = jax.random.normal(jax.random.PRNGKey(3), (2, 8, 8), jnp.float32)
    v_b = base.adapter.velocity(base.state.params, x_t,
                                jnp.full((2,), 0.5), COND)
    v_f = f32.adapter.velocity(f32.state.params, x_t,
                               jnp.full((2,), 0.5), COND)
    assert v_b.dtype == jnp.float32 and v_f.dtype == jnp.float32
    # f32 activations genuinely change the compute (bf16 rounding scale)
    np.testing.assert_allclose(np.asarray(v_b), np.asarray(v_f),
                               rtol=0.1, atol=0.1)
    assert not np.array_equal(np.asarray(v_b), np.asarray(v_f))
    m = run_steps(f32)
    assert np.isfinite(m["loss"])


def test_perf_config_validation():
    with pytest.raises(ValueError, match="perf.remat"):
        make(perf=PerfConfig(remat="blocks"))
    with pytest.raises(ValueError, match="policy_dtype"):
        make(perf=PerfConfig(policy_dtype="fp8"))


# ------------------------------------------------------ front-door plumbing

def test_experiment_perf_plumbing(tmp_path):
    from repro.api import Experiment
    exp = Experiment.from_cli([
        "--reduced", "--set", "perf.remat=scan",
        "--set", "perf.fuse_step=true",
        "--set", f"flow.cache_dir={tmp_path}/cache",
    ])
    tr = exp.build_trainer()
    assert tr.perf.remat == "scan" and tr._fused_jit is not None
    # perf is runtime policy, not experiment identity: checkpoints move
    # freely between perf configurations (like dist)
    assert "perf" not in exp._ckpt_identity()


# ------------------------------------------------- dist composition (dp=4)

needs_dp4 = pytest.mark.skipif(
    jax.local_device_count() < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4")


@needs_dp4
@pytest.mark.parametrize("pc", [
    PerfConfig(remat="scan", fuse_step=True),
    PerfConfig(remat="block"),
], ids=["scan+fused", "block"])
def test_perf_composes_with_data_parallel_microbatch(pc):
    """remat × fusion × dp=4 × microbatch=2 matches the plain single-device
    step at the documented f32/bf16-reduction-order tolerances."""
    base = make()
    tr = make(perf=pc, dist=DistConfig(data_parallel=4, microbatch=2))
    mb, mt = run_steps(base), run_steps(tr)
    params_close(base.state.params, tr.state.params,
                 rtol=BF16_ATOL, atol=BF16_ATOL)
    np.testing.assert_allclose(mb["reward_mean"], mt["reward_mean"],
                               rtol=1e-4, atol=1e-4)


@needs_dp4
def test_fused_memory_stats_under_mesh():
    cond = jax.ShapeDtypeStruct(COND.shape, COND.dtype)
    tr = make(perf=PerfConfig(remat="scan", fuse_step=True),
              dist=DistConfig(data_parallel=4))
    mem = tr.memory_stats(cond)
    assert mem["update"]["temp_bytes"] and mem["fused"]["temp_bytes"]


def test_memory_analysis_missing_raises():
    """A backend that returns no analysis raises: a missing number never
    passes for a measured one (no ``{"error": ...}`` stand-in)."""
    from repro.perf import analysis_dict

    class NoAnalysis:
        def memory_analysis(self):
            return None

    with pytest.raises(RuntimeError, match="memory_analysis"):
        analysis_dict(NoAnalysis())
    compiled = jax.jit(lambda x: x * 2).lower(jnp.ones((8,))).compile()
    assert analysis_dict(compiled)["argument_bytes"] == 32


def test_offload_without_host_memory_kind_raises_on_accelerator(monkeypatch):
    """perf.offload_rewards never quietly moves the towers to host numpy on
    an accelerator that exposes no host memory kind; the CPU backend keeps
    its device_get path."""
    from repro.perf import offload
    monkeypatch.setattr(offload, "host_memory_kind", lambda device=None: None)
    tree = {"w": jnp.ones((4,))}
    assert isinstance(offload.offload_tree(tree)["w"], np.ndarray)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="host memory kind"):
        offload.offload_tree(tree)
