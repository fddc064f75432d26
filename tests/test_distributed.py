"""repro.distributed tests: gradient-accumulation microbatching equivalence,
batch/device validation, 2-D (data × model) mesh axis resolution and
PartitionPlan layouts, and multi-device (4 faked CPU host devices, spawned
in subprocesses so the single-device tier-1 environment stays untouched)
numerical equivalence of sharded vs single-device training — including
dp=2×mp=2 vs single-device for all four trainer families and checkpoint
portability across mesh layouts."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, distributed, registry
from repro.config import DistConfig, FlowRLConfig, OptimConfig, RewardSpec

KEY = jax.random.PRNGKey(3)

TINY_FLOW = FlowRLConfig(
    num_steps=3, group_size=4, latent_tokens=8, latent_dim=8,
    clip_range=0.2,
    rewards=(RewardSpec("text_render", 1.0,
                        args={"latent_dim": 8, "latent_tokens": 8}),))
TINY_OPT = OptimConfig(lr=1e-3, total_steps=20, warmup_steps=2)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build(tname="flow_grpo", dist=None, dtype=jnp.float32):
    cfg = configs.get_reduced("flux_dit")
    return registry.build("trainer", tname, cfg, TINY_FLOW, TINY_OPT,
                          key=KEY, dtype=dtype, dist=dist)


# ------------------------------------------------------------- microbatching

def test_microbatch_grads_match_full_batch():
    """k-chunk gradient accumulation equals the full-batch gradient on the
    jnp path.  Most leaves are bit-exact; a few differ only in f32 summation
    order (XLA reduces the full batch in one tree, the accumulator adds k
    partial sums), so the assertion is allclose at float32 resolution."""
    tr = _build()
    cond = jax.random.normal(KEY, (4, 4, 512), jnp.float32)
    traj = tr.sample(tr.state.params, cond, KEY, it=0)
    _, adv, _ = tr._rewards_jit(traj.x0, {"cond": traj.cond})

    vg = jax.jit(lambda p, t, a: jax.value_and_grad(
        tr.loss_fn, has_aux=True)(p, t, a, KEY))
    (loss_full, _), grads_full = vg(tr.state.params, traj, adv)
    for k in (2, 4):
        acc = jax.jit(lambda p, t, a, k=k: distributed.accumulated_value_and_grad(
            tr.loss_fn, p, t, a, KEY, (), k))
        (loss_k, _), grads_k = acc(tr.state.params, traj, adv)
        np.testing.assert_allclose(float(loss_k), float(loss_full),
                                   rtol=0, atol=1e-7)
        for gf, gk in zip(jax.tree.leaves(grads_full),
                          jax.tree.leaves(grads_k)):
            np.testing.assert_allclose(np.asarray(gk), np.asarray(gf),
                                       rtol=1e-4, atol=1e-6)


def test_microbatch_full_update_step_equivalent():
    """End-to-end: a trainer with dist.microbatch=2 produces the same params
    trajectory as the full-batch trainer (same keys, same data)."""
    t_full = _build()
    t_mb = _build(dist=DistConfig(microbatch=2))
    cond = jax.random.normal(KEY, (2, 4, 512), jnp.float32)
    for it in range(2):
        m_full = t_full.step(cond, KEY, it=it)
        m_mb = t_mb.step(cond, KEY, it=it)
        # the GRPO loss is a cancellation residue of ~0 at rollout params,
        # so compare absolutely at f32 cancellation noise scale
        np.testing.assert_allclose(float(m_mb["loss"]), float(m_full["loss"]),
                                   rtol=0, atol=1e-5)
    # AdamW amplifies reduction-order grad noise where vhat ~ 0 (the update
    # m/sqrt(v) is sign-like), so params get a looser absolute band than the
    # raw gradients above: ~2.5e-5 observed on 0.01% of elements at lr=1e-3
    for a, b in zip(jax.tree.leaves(t_full.state.params),
                    jax.tree.leaves(t_mb.state.params)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-3, atol=1e-4)


def test_microbatch_key_consuming_loss_steps():
    """NFT's loss draws timesteps/noise from the key; each chunk must get an
    independent fold of it (statistical, not numeric, equivalence)."""
    tr = _build("nft", dist=DistConfig(microbatch=2))
    cond = jax.random.normal(KEY, (2, 4, 512), jnp.float32)
    m = tr.step(cond, KEY, it=0)
    assert np.isfinite(float(m["loss"])) and np.isfinite(float(m["vel_err"]))


def test_microbatch_indivisible_batch_raises():
    tr = _build(dist=DistConfig(microbatch=3))
    cond = jax.random.normal(KEY, (2, 4, 512), jnp.float32)   # B = 8
    with pytest.raises(ValueError, match=r"8.*microbatch.*3"):
        tr.step(cond, KEY, it=0)


def test_negative_microbatch_rejected_at_construction():
    with pytest.raises(ValueError, match="microbatch"):
        _build(dist=DistConfig(microbatch=-1))


def test_batch_global_statistic_loss_rejects_microbatch():
    """GRPO-Guard's RatioNorm is a batch-global mean; chunked accumulation
    would silently recentre per chunk, so construction must refuse."""
    with pytest.raises(ValueError, match="batch-global"):
        _build("grpo_guard", dist=DistConfig(microbatch=2))
    _build("grpo_guard")                               # full-batch path fine


# ---------------------------------------------------------------- validation

def test_data_parallel_exceeding_devices_raises():
    too_many = jax.local_device_count() + 1
    with pytest.raises(ValueError, match="device"):
        distributed.data_mesh(DistConfig(data_parallel=too_many))


def test_single_device_resolves_to_no_mesh():
    assert distributed.data_mesh(DistConfig(data_parallel=1)) is None
    tr = _build(dist=DistConfig(data_parallel=1))
    assert tr.mesh is None


def test_group_size_validated_at_construction():
    cfg = configs.get_reduced("flux_dit")
    bad = FlowRLConfig(num_steps=3, group_size=0, latent_tokens=8,
                       latent_dim=8)
    with pytest.raises(ValueError, match="group_size"):
        registry.build("trainer", "flow_grpo", cfg, bad, TINY_OPT, key=KEY)


# ------------------------------------------------- multi-device (subprocess)

def _run_with_host_devices(code: str, n: int = 4) -> str:
    """Run ``code`` in a subprocess that fakes ``n`` CPU host devices (the
    flag must be set before jax initializes, hence the fresh process)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={n}")
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(REPO, "src")
    env["PYTHONPATH"] = (src + os.pathsep + env["PYTHONPATH"]
                         if env.get("PYTHONPATH") else src)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=540, cwd=REPO)
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    return proc.stdout


_EQUIV_SCRIPT = r"""
import jax, jax.numpy as jnp
import numpy as np
from repro import configs, registry
from repro.config import DistConfig, FlowRLConfig, OptimConfig, RewardSpec

assert jax.local_device_count() == 4, jax.devices()
FLOW = FlowRLConfig(num_steps=3, group_size=4, latent_tokens=8, latent_dim=8,
                    clip_range=0.2,
                    rewards=(RewardSpec("text_render", 1.0,
                             args={"latent_dim": 8, "latent_tokens": 8}),))
OPT = OptimConfig(lr=1e-3, total_steps=20, warmup_steps=2)
ARCH = configs.get_reduced("flux_dit")

def train(dist):
    key = jax.random.PRNGKey(0)
    tr = registry.build("trainer", "flow_grpo", ARCH, FLOW, OPT, key=key,
                        dtype=jnp.float32, dist=dist)
    cond = jax.random.normal(jax.random.PRNGKey(1), (4, 4, 512), jnp.float32)
    hist = [{k: float(v) for k, v in tr.step(cond, key, it=it).items()}
            for it in range(3)]
    return tr, hist

t1, h1 = train(DistConfig(data_parallel=1))
t4, h4 = train(DistConfig(data_parallel=4))
t4m, h4m = train(DistConfig(data_parallel=4, microbatch=2))

# the sharded trainer's state is really replicated across all 4 devices
leaf = jax.tree.leaves(t4.state.params)[0]
assert len(leaf.sharding.device_set) == 4, leaf.sharding
# and its rollouts are really batch-sharded
traj = t4.sample(t4.state.params, jax.random.normal(
    jax.random.PRNGKey(1), (4, 4, 512), jnp.float32), jax.random.PRNGKey(0))
assert len(traj.cond.sharding.device_set) == 4, traj.cond.sharding

for name, hx in (("dp4", h4), ("dp4+mb2", h4m)):
    for a, b in zip(h1, hx):
        for k in ("reward_mean", "loss", "grad_norm"):
            assert abs(a[k] - b[k]) <= 2e-4 + 1e-3 * abs(a[k]), \
                (name, k, a[k], b[k])
# AdamW turns reduction-order grad noise into ~lr-scale differences where
# vhat ~ 0, hence the absolute band of ~1e-4 on a tiny element fraction
for name, tx in (("dp4", t4), ("dp4+mb2", t4m)):
    for x, y in zip(jax.tree.leaves(t1.state.params),
                    jax.tree.leaves(tx.state.params)):
        np.testing.assert_allclose(np.asarray(y), np.asarray(x),
                                   rtol=1e-3, atol=2e-4, err_msg=name)
print("EQUIV-OK")
"""


def test_sharded_training_matches_single_device():
    """4-device data-parallel (and data-parallel + microbatch) training is
    numerically equivalent to single-device: same per-step metrics and the
    same final params within f32 reduction-order tolerance."""
    out = _run_with_host_devices(_EQUIV_SCRIPT)
    assert "EQUIV-OK" in out


_SHARD_MAP_SCRIPT = r"""
import jax, jax.numpy as jnp
import numpy as np
from repro import configs, registry
from repro.config import DistConfig, FlowRLConfig, OptimConfig, RewardSpec
from repro.core.rollout import group_repeat
from repro.distributed import data_mesh, make_rollout_sharded

assert jax.local_device_count() == 4
FLOW = FlowRLConfig(num_steps=3, group_size=4, latent_tokens=8, latent_dim=8,
                    rewards=(RewardSpec("text_render", 1.0,
                             args={"latent_dim": 8, "latent_tokens": 8}),))
OPT = OptimConfig(lr=1e-3, total_steps=20, warmup_steps=2)
tr = registry.build("trainer", "awm", configs.get_reduced("flux_dit"),
                    FLOW, OPT, key=jax.random.PRNGKey(0), dtype=jnp.float32)
mesh = data_mesh(DistConfig(data_parallel=4))
cond = group_repeat(jax.random.normal(jax.random.PRNGKey(1), (2, 4, 512),
                                      jnp.float32), 4)     # B = 8
run = make_rollout_sharded(tr.adapter, tr.scheduler, 3, mesh)  # build once
traj = run(tr.state.params, cond, jax.random.PRNGKey(2))
traj_b = run(tr.state.params, cond, jax.random.PRNGKey(3))     # ...reuse
assert not np.allclose(np.asarray(traj.x0), np.asarray(traj_b.x0))
assert traj.xs.shape == (4, 8, 8, 8), traj.xs.shape
assert np.isfinite(np.asarray(traj.xs)).all()
assert len(traj.xs.sharding.device_set) == 4
# per-shard key folds: different shards draw different noise
x0 = np.asarray(traj.x0)
assert not np.allclose(x0[:2], x0[2:4])
# indivisible batch is rejected clearly
try:
    run(tr.state.params, cond[:6], jax.random.PRNGKey(2))
except ValueError as e:
    assert "divisible" in str(e)
else:
    raise AssertionError("expected ValueError for B=6 on 4 devices")
print("SHARDMAP-OK")
"""


def test_shard_map_rollout_entry_point():
    """The communication-free shard_map rollout produces well-formed sharded
    trajectories with independent per-shard noise."""
    out = _run_with_host_devices(_SHARD_MAP_SCRIPT)
    assert "SHARDMAP-OK" in out

# ------------------------------------------------------- 2-D axis resolution

def test_resolve_axes_defaults_and_auto():
    n = jax.local_device_count()
    assert distributed.resolve_axes(DistConfig()) == (1, 1)
    # data_parallel=0 claims every device not claimed by model_parallel
    assert distributed.resolve_axes(DistConfig(data_parallel=0)) == (n, 1)
    # both auto resolves to all-data (the historical data_parallel=0)
    assert distributed.resolve_axes(
        DistConfig(data_parallel=0, model_parallel=0)) == (n, 1)
    # model_parallel=0 claims the devices data_parallel left over
    assert distributed.resolve_axes(
        DistConfig(data_parallel=1, model_parallel=0)) == (1, n)


def test_resolve_axes_validation():
    n = jax.local_device_count()
    with pytest.raises(ValueError, match="XLA_FLAGS"):
        distributed.resolve_axes(DistConfig(data_parallel=2 * n,
                                            model_parallel=n))
    with pytest.raises(ValueError, match="model_parallel"):
        distributed.resolve_axes(DistConfig(model_parallel=n + 1))
    with pytest.raises(ValueError, match=">= 0"):
        distributed.resolve_axes(DistConfig(data_parallel=-1))
    with pytest.raises(ValueError, match=">= 0"):
        distributed.resolve_axes(DistConfig(model_parallel=-2))


def test_train_mesh_degradation_tiers():
    """dp×mp=1 -> no mesh; mp=1 -> the historical 1-D ("data",) mesh."""
    assert distributed.train_mesh(
        DistConfig(data_parallel=1, model_parallel=1)) is None
    n = jax.local_device_count()
    if n > 1:
        mesh = distributed.train_mesh(DistConfig(data_parallel=n))
        assert mesh.axis_names == (distributed.DATA_AXIS,)
        assert distributed.mesh_dp(mesh) == n
        assert distributed.mesh_mp(mesh) == 1
    assert distributed.mesh_dp(None) == 1 and distributed.mesh_mp(None) == 1


def test_model_shard_dim_choices():
    from repro.models.params import model_shard_dim
    # mp=1 never shards
    assert model_shard_dim((8, 64), ("embed", "mlp"), 1) is None
    # priority: experts beats heads beats wide dims beats embed
    assert model_shard_dim((4, 16, 64), ("experts", "embed", "moe_f"), 2) == 0
    assert model_shard_dim((8, 16, 64), ("heads", "head_dim", "embed"), 2) == 0
    assert model_shard_dim((64, 256), ("embed", "mlp"), 2) == 1
    # norm / head_dim / conv scales stay replicated
    assert model_shard_dim((64,), ("norm",), 2) is None
    assert model_shard_dim((16,), ("head_dim",), 2) is None
    # indivisible or too-small dims are skipped, falling through by priority
    assert model_shard_dim((3, 64), ("experts", "embed"), 2) == 1
    assert model_shard_dim((1, 1), ("experts", "embed"), 2) is None


def test_partition_plan_layouts_and_bytes():
    """PartitionPlan on an explicitly built 2-D mesh: params shard along
    "model", AdamW moments inherit their param's sharding leaf-for-leaf,
    scalars stay replicated, and the per-device byte report shrinks."""
    if jax.local_device_count() < 4:
        pytest.skip("needs 4 (faked) devices — runs in make test-dist")
    from jax.sharding import Mesh, PartitionSpec
    mesh = Mesh(np.asarray(jax.local_devices()[:4]).reshape(2, 2),
                (distributed.DATA_AXIS, distributed.MODEL_AXIS))
    tr = _build()                                  # single-device trainer
    plan = distributed.partition_plan(mesh, tr.adapter.spec())
    psh = plan.param_shardings()
    specs = [s.spec for s in jax.tree.leaves(
        psh, is_leaf=lambda x: hasattr(x, "spec"))]
    assert any(distributed.MODEL_AXIS in [e for ent in s if ent is not None
               for e in (ent if isinstance(ent, tuple) else (ent,))]
               for s in specs), "no leaf sharded over the model axis"
    ssh = plan.state_shardings(tr.state)
    # mu/nu mirror params: same sharding tree; step counter replicated
    assert jax.tree.structure(ssh.opt.mu, is_leaf=lambda x: hasattr(
        x, "spec")) == jax.tree.structure(psh, is_leaf=lambda x: hasattr(
            x, "spec"))
    for a, b in zip(jax.tree.leaves(ssh.params,
                                    is_leaf=lambda x: hasattr(x, "spec")),
                    jax.tree.leaves(ssh.opt.mu,
                                    is_leaf=lambda x: hasattr(x, "spec"))):
        assert a.spec == b.spec
    assert ssh.opt.step.spec == PartitionSpec()
    rep = plan.bytes_report(tr.state)
    assert rep["sharded_leaves"] > 0
    assert rep["per_device_bytes"] < rep["total_bytes"]
    # the report is consistent with actually placing the state
    placed = jax.device_put(tr.state, ssh)
    leaf = jax.tree.leaves(placed.params)[0]
    assert len(leaf.sharding.device_set) == 4


_TWO_AXIS_SCRIPT = r"""
import jax, jax.numpy as jnp
import numpy as np
from repro import configs, registry, distributed
from repro.config import DistConfig, FlowRLConfig, OptimConfig, RewardSpec

assert jax.local_device_count() == 4, jax.devices()
FLOW = FlowRLConfig(num_steps=3, group_size=4, latent_tokens=8, latent_dim=8,
                    clip_range=0.2,
                    rewards=(RewardSpec("text_render", 1.0,
                             args={"latent_dim": 8, "latent_tokens": 8}),))
OPT = OptimConfig(lr=1e-3, total_steps=20, warmup_steps=2)
ARCH = configs.get_reduced("flux_dit")
TNAME = "__TNAME__"

def train(dist):
    key = jax.random.PRNGKey(0)
    tr = registry.build("trainer", TNAME, ARCH, FLOW, OPT, key=key,
                        dtype=jnp.float32, dist=dist)
    cond = jax.random.normal(jax.random.PRNGKey(1), (4, 4, 512), jnp.float32)
    hist = [{k: float(v) for k, v in tr.step(cond, key, it=it).items()}
            for it in range(3)]
    return tr, hist

# jax's default partitionable threefry is sharding-invariant, so the
# single-device reference draws the same random stream as the 2-D layout
t22, h22 = train(DistConfig(data_parallel=2, model_parallel=2))
t1, h1 = train(DistConfig())

assert t22.mesh.axis_names == ("data", "model"), t22.mesh
assert t22.plan is not None and t22.plan.model_parallel == 2
rep = t22.plan.bytes_report(t22.state)
assert rep["sharded_leaves"] > 0, rep
assert rep["per_device_bytes"] < rep["total_bytes"], rep
# at least one live param leaf is genuinely model-sharded across 4 devices
shards = [leaf.sharding for leaf in jax.tree.leaves(t22.state.params)]
assert any(len(s.device_set) == 4 and not s.is_fully_replicated
           for s in shards), shards

for a, b in zip(h1, h22):
    for k in ("reward_mean", "loss", "grad_norm"):
        assert abs(a[k] - b[k]) <= 2e-4 + 1e-3 * abs(a[k]), (k, a[k], b[k])
# documented f32 band: model-axis collectives reorder reductions, and AdamW
# turns that noise into ~lr-scale sign flips where vhat ~ 0.  Every element
# is capped at a few x lr (a flipped element moves <= 2*lr per step), and
# at most a 0.01% tail may sit outside the tight band the rest must meet.
n_tot = n_out = 0
for x, y in zip(jax.tree.leaves(t1.state.params),
                jax.tree.leaves(t22.state.params)):
    x, y = np.asarray(x), np.asarray(y)
    np.testing.assert_allclose(y, x, rtol=0, atol=5e-3)
    n_out += int((np.abs(y - x) > (2e-4 + 1e-3 * np.abs(x))).sum())
    n_tot += x.size
assert n_out <= max(1, n_tot // 10_000), (n_out, n_tot)
print("TWO-AXIS-OK")
"""


@pytest.mark.parametrize("tname", ["flow_grpo", "grpo_guard", "nft", "awm"])
def test_two_axis_training_matches_single_device(tname):
    """dp=2×mp=2 on 4 faked devices trains equivalently to single-device
    (documented f32 tolerance) for every trainer family, with params
    genuinely sharded over the model axis."""
    out = _run_with_host_devices(
        _TWO_AXIS_SCRIPT.replace("__TNAME__", tname))
    assert "TWO-AXIS-OK" in out


_PORTABLE_SCRIPT = r"""
import os, tempfile
import jax, jax.numpy as jnp
import numpy as np
from repro import checkpoint, configs, registry
from repro.config import DistConfig, FlowRLConfig, OptimConfig, RewardSpec

assert jax.local_device_count() == 4, jax.devices()
FLOW = FlowRLConfig(num_steps=3, group_size=4, latent_tokens=8, latent_dim=8,
                    clip_range=0.2,
                    rewards=(RewardSpec("text_render", 1.0,
                             args={"latent_dim": 8, "latent_tokens": 8}),))
OPT = OptimConfig(lr=1e-3, total_steps=20, warmup_steps=2)
ARCH = configs.get_reduced("flux_dit")
key = jax.random.PRNGKey(0)
cond = jax.random.normal(jax.random.PRNGKey(1), (4, 4, 512), jnp.float32)

# train under dp=4, checkpoint (device_get gathers -> canonical layout)
t4 = registry.build("trainer", "flow_grpo", ARCH, FLOW, OPT, key=key,
                    dtype=jnp.float32, dist=DistConfig(data_parallel=4))
for it in range(2):
    t4.step(cond, key, it=it)
ckpt_dir = tempfile.mkdtemp()
checkpoint.save_checkpoint(ckpt_dir, 2, t4.state)
saved = jax.device_get(t4.state)

# resume under dp=2×mp=2: restore canonical, re-place per the new plan
t22 = registry.build("trainer", "flow_grpo", ARCH, FLOW, OPT, key=key,
                     dtype=jnp.float32,
                     dist=DistConfig(data_parallel=2, model_parallel=2))
step, state = checkpoint.restore_latest(ckpt_dir, t22.state)
assert step == 2
t22.state = t22.place_state(state)

# params (and moments) are bitwise what dp=4 wrote...
for a, b in zip(jax.tree.leaves(saved), jax.tree.leaves(
        jax.device_get(t22.state))):
    assert np.array_equal(np.asarray(a), np.asarray(b))
# ...yet live on the 2-D layout, model-sharded
shards = [leaf.sharding for leaf in jax.tree.leaves(t22.state.params)]
assert any(len(s.device_set) == 4 and not s.is_fully_replicated
           for s in shards), shards
# and training continues from it
m = t22.step(cond, key, it=2)
assert np.isfinite(float(m["loss"]))
print("PORTABLE-OK")
"""


def test_checkpoint_portable_across_mesh_layouts():
    """A checkpoint written under dp=4 restores bitwise under dp=2×mp=2:
    layouts are a runtime choice, the on-disk layout is canonical."""
    out = _run_with_host_devices(_PORTABLE_SCRIPT)
    assert "PORTABLE-OK" in out


_MESH_KERNEL_SCRIPT = r"""
import os
os.environ["REPRO_PALLAS"] = "interpret"
import jax, jax.numpy as jnp
import numpy as np
from repro import distributed
from repro.config import DistConfig
from repro.distributed.sharding import _on_mesh, batch_sharding
from repro.kernels import ops, ref

mesh = distributed.train_mesh(DistConfig(data_parallel=2, model_parallel=2))
ks = jax.random.split(jax.random.PRNGKey(0), 3)
v, x, eps = (jax.random.normal(k, (8, 16, 8)) for k in ks)

def step(v, x, eps):
    return ops.sde_step(v, x, eps, 0.9, 0.8, eta=0.7)

# the trainer's mesh jits trace under the mesh the same way
fn = jax.jit(_on_mesh(step, mesh), in_shardings=(batch_sharding(mesh, 0),) * 3)
args = [jax.device_put(a, batch_sharding(mesh, 0)) for a in (v, x, eps)]
assert "shard_map" in str(fn.trace(*args).jaxpr), "kernel not split"
xn, lp = fn(*args)
xr, lr = ref.sde_step_ref(v, x, 0.9, 0.8, eps, eta=0.7)
np.testing.assert_allclose(xn, xr, atol=1e-5, rtol=1e-5)
np.testing.assert_allclose(lp, lr, atol=1e-3, rtol=1e-5)
# without a mesh in context the kernel is called directly
assert "shard_map" not in str(jax.make_jaxpr(step)(v, x, eps))
print("MESH-KERNEL-OK")
"""


def test_pallas_kernels_split_over_the_data_axis_under_a_mesh():
    """Mosaic kernels cannot be partitioned by the TPU compiler: under a
    trainer mesh jit the ops wrappers run them per data shard through
    shard_map, with the same results as the reference."""
    out = _run_with_host_devices(_MESH_KERNEL_SCRIPT)
    assert "MESH-KERNEL-OK" in out
