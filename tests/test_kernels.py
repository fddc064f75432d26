"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret
mode executes the kernel body in Python on CPU — assignment requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.grpo_loss import grpo_loss
from repro.kernels.sde_step import sde_step
from repro.kernels.ssd_scan import ssd_scan

KEY = jax.random.PRNGKey(42)


@pytest.mark.parametrize("B,Sq,Sk,H,K,D", [
    (2, 128, 128, 4, 2, 64),
    (1, 256, 256, 2, 1, 32),
    (2, 128, 128, 4, 4, 128),
    (1, 512, 512, 8, 2, 64),
])
@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, Sq, Sk, H, K, D, causal, window, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, Sk, K, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, Sk, K, D), jnp.float32).astype(dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_k=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("B,L,H,P,N,Q", [
    (2, 128, 2, 32, 64, 32),
    (1, 256, 4, 64, 128, 128),
    (3, 64, 1, 16, 32, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan(B, L, H, P, N, Q, dtype):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, L, H, P), jnp.float32).astype(dtype)
    dt = (jax.nn.softplus(jax.random.normal(ks[1], (B, L, H))) * 0.5)
    a = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    bm = (jax.random.normal(ks[3], (B, L, N)) * 0.5).astype(dtype)
    cm = (jax.random.normal(ks[4], (B, L, N)) * 0.5).astype(dtype)
    y, hT = ssd_scan(x, dt, a, bm, cm, chunk=Q, interpret=True)
    yr, hr = ref.ssd_scan_ref(x, dt, a, bm, cm)
    tol = 5e-3 if dtype == jnp.float32 else 8e-2
    np.testing.assert_allclose(y.astype(jnp.float32),
                               yr.astype(jnp.float32), atol=tol, rtol=0.1)
    np.testing.assert_allclose(hT, hr, atol=tol, rtol=0.1)


@pytest.mark.parametrize("B,Lt,ld", [(2, 8, 4), (4, 64, 16), (1, 16, 8)])
@pytest.mark.parametrize("eta", [0.3, 0.7])
@pytest.mark.parametrize("t,t_next", [(0.9, 0.8), (0.5, 0.4), (0.2, 0.1)])
def test_sde_step(B, Lt, ld, eta, t, t_next):
    ks = jax.random.split(KEY, 3)
    v = jax.random.normal(ks[0], (B, Lt, ld))
    x = jax.random.normal(ks[1], (B, Lt, ld))
    eps = jax.random.normal(ks[2], (B, Lt, ld))
    xn, lp = sde_step(v, x, eps, t, t_next, eta=eta, interpret=True)
    xr, lr = ref.sde_step_ref(v, x, t, t_next, eps, eta=eta)
    np.testing.assert_allclose(xn, xr, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(lp, lr, atol=1e-3, rtol=1e-5)


@pytest.mark.parametrize("B", [7, 64, 1031])
@pytest.mark.parametrize("clip", [0.1, 0.3])
@pytest.mark.parametrize("guard", [False, True])
def test_grpo_loss(B, clip, guard):
    ks = jax.random.split(KEY, 3)
    lpn = jax.random.normal(ks[0], (B,)) * 0.05
    lpo = jax.random.normal(ks[1], (B,)) * 0.05
    adv = jax.random.normal(ks[2], (B,))
    rm = jnp.exp(jnp.clip(lpn - lpo, -20, 20)).mean()
    loss, frac = grpo_loss(lpn, lpo, adv, rm, clip=clip, guard=guard,
                           interpret=True)
    lref, fref = ref.grpo_loss_ref(lpn, lpo, adv, clip=clip, guard=guard)
    np.testing.assert_allclose(loss, lref, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(frac, fref, atol=0)


def test_kernel_matches_model_attention_path():
    """The kernel and the model's chunked-jnp attention agree (the dispatch
    layer can swap them freely)."""
    from repro.models.layers import attention_chunked
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 256, 4, 64))
    k = jax.random.normal(ks[1], (2, 256, 2, 64))
    v = jax.random.normal(ks[2], (2, 256, 2, 64))
    a = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                        interpret=True)
    b = attention_chunked(q, k, v, causal=True, chunk_q=64)
    np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4)


def test_trainer_kernel_path_equivalence(monkeypatch):
    """The GRPO trainer produces identical losses/gradients whether the SDE
    step + GRPO loss run through the Pallas kernels (interpret mode) or the
    jnp reference path — the dispatch layer is behaviour-preserving."""
    import os
    from repro import configs, registry
    from repro.config import FlowRLConfig, OptimConfig, RewardSpec
    key = jax.random.PRNGKey(0)
    arch = configs.get_reduced("flux_dit")
    flow = FlowRLConfig(
        num_steps=3, group_size=2, latent_tokens=8, latent_dim=8,
        rewards=(RewardSpec("text_render", 1.0,
                            args={"latent_dim": 8, "latent_tokens": 8}),))
    opt = OptimConfig(total_steps=4)
    cond = jax.random.normal(key, (2, 4, 512))
    results = {}
    for mode in ("off", "interpret"):
        monkeypatch.setenv("REPRO_PALLAS", mode)
        tr = registry.build("trainer", "flow_grpo", arch, flow, opt, key=key)
        for it in range(2):
            m = tr.step(cond, key, it=it)
        results[mode] = (float(m["loss"]), float(m["reward_mean"]),
                         float(m["grad_norm"]))
    np.testing.assert_allclose(results["off"], results["interpret"],
                               atol=2e-3)


class TestOpsDispatchEquivalence:
    """Every ``kernels/ops.py`` wrapper, exercised THROUGH the dispatch
    layer: with REPRO_PALLAS=interpret the Pallas body must reproduce the
    ``kernels/ref.py`` oracle the ``off`` mode would have returned — the
    dispatch decision can never change results."""

    def _ops(self, monkeypatch, mode):
        monkeypatch.setenv("REPRO_PALLAS", mode)
        from repro.kernels import ops
        assert ops.pallas_enabled() == (mode != "off")
        return ops

    def test_flash_attention_wrapper(self, monkeypatch):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (2, 128, 4, 32))
        k = jax.random.normal(ks[1], (2, 128, 2, 32))
        v = jax.random.normal(ks[2], (2, 128, 2, 32))
        for kw in ({"causal": True}, {"causal": False},
                   {"causal": True, "window": 64}):
            got = self._ops(monkeypatch, "interpret").flash_attention(
                q, k, v, **kw)
            want = self._ops(monkeypatch, "off").flash_attention(q, k, v,
                                                                 **kw)
            np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

    def test_ssd_scan_wrapper(self, monkeypatch):
        ks = jax.random.split(KEY, 5)
        x = jax.random.normal(ks[0], (2, 128, 2, 16))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (2, 128, 2))) * 0.5
        a = -jnp.exp(jax.random.normal(ks[2], (2,)) * 0.3)
        bm = jax.random.normal(ks[3], (2, 128, 32)) * 0.5
        cm = jax.random.normal(ks[4], (2, 128, 32)) * 0.5
        y_i, h_i = self._ops(monkeypatch, "interpret").ssd_scan(
            x, dt, a, bm, cm, chunk=32)
        y_r, h_r = self._ops(monkeypatch, "off").ssd_scan(x, dt, a, bm, cm,
                                                          chunk=32)
        np.testing.assert_allclose(y_i, y_r, atol=5e-3, rtol=0.1)
        np.testing.assert_allclose(h_i, h_r, atol=5e-3, rtol=0.1)

    def test_sde_step_wrapper(self, monkeypatch):
        ks = jax.random.split(KEY, 3)
        v = jax.random.normal(ks[0], (4, 16, 8))
        x = jax.random.normal(ks[1], (4, 16, 8))
        eps = jax.random.normal(ks[2], (4, 16, 8))
        for t, t_next, eta in ((0.9, 0.8, 0.7), (0.3, 0.2, 0.3)):
            xn_i, lp_i = self._ops(monkeypatch, "interpret").sde_step(
                v, x, eps, t, t_next, eta=eta)
            xn_r, lp_r = self._ops(monkeypatch, "off").sde_step(
                v, x, eps, t, t_next, eta=eta)
            np.testing.assert_allclose(xn_i, xn_r, atol=1e-5, rtol=1e-5)
            np.testing.assert_allclose(lp_i, lp_r, atol=1e-3, rtol=1e-5)

    @pytest.mark.parametrize("guard", [False, True])
    def test_grpo_loss_wrapper(self, monkeypatch, guard):
        ks = jax.random.split(KEY, 3)
        lpn = jax.random.normal(ks[0], (64,)) * 0.05
        lpo = jax.random.normal(ks[1], (64,)) * 0.05
        adv = jax.random.normal(ks[2], (64,))
        rm = jnp.exp(jnp.clip(lpn - lpo, -20, 20)).mean()
        l_i, f_i = self._ops(monkeypatch, "interpret").grpo_loss(
            lpn, lpo, adv, rm, clip=0.2, guard=guard)
        l_r, f_r = self._ops(monkeypatch, "off").grpo_loss(
            lpn, lpo, adv, rm, clip=0.2, guard=guard)
        np.testing.assert_allclose(l_i, l_r, atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(f_i, f_r, atol=0)

    def test_grpo_loss_trainable_wrapper(self, monkeypatch):
        """Value, clip-fraction metric, AND gradient agree across dispatch
        modes (the trainer differentiates through this wrapper)."""
        ks = jax.random.split(KEY, 3)
        lpn = jax.random.normal(ks[0], (48,)) * 0.1
        lpo = jax.random.normal(ks[1], (48,)) * 0.1
        adv = jax.random.normal(ks[2], (48,))

        def run(mode):
            ops = self._ops(monkeypatch, mode)

            def scalar_loss(lpn_):
                loss, frac = ops.grpo_loss_trainable(lpn_, lpo, adv,
                                                     clip=0.2)
                return loss.sum(), frac

            (val, frac), grad = jax.value_and_grad(
                scalar_loss, has_aux=True)(lpn)
            return val, frac, grad

        v_i, f_i, g_i = run("interpret")
        v_r, f_r, g_r = run("off")
        np.testing.assert_allclose(v_i, v_r, atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(f_i, f_r, atol=0)
        np.testing.assert_allclose(g_i, g_r, atol=1e-5, rtol=1e-4)

    def test_keyed_rollout_dispatch_modes_agree(self, monkeypatch):
        """The serving engine's rollout (rollout_keyed -> step_with_eps)
        dispatches flow_sde steps through the fused sde_step kernel: the
        production serving path must be mode-invariant too."""
        from repro import configs
        from repro.config import FlowRLConfig
        from repro.core import schedulers
        from repro.core.rollout import request_keys, rollout_keyed
        from repro.models import params as params_lib
        from repro.models.flow import FlowAdapter
        arch = configs.get_reduced("flux_dit")
        flow = FlowRLConfig(num_steps=3, latent_tokens=8, latent_dim=8)
        adapter = FlowAdapter(arch, flow, 512)
        params = params_lib.init(adapter.spec(), KEY, jnp.float32)
        sched = schedulers.build("flow_sde", 0.7)
        cond = jax.random.normal(KEY, (4, 4, 512))
        keys = request_keys(KEY, 4)
        out = {}
        for mode in ("off", "interpret"):
            monkeypatch.setenv("REPRO_PALLAS", mode)
            out[mode] = rollout_keyed(adapter, params, cond, keys, sched, 3)
        np.testing.assert_allclose(out["off"].xs, out["interpret"].xs,
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(out["off"].logps, out["interpret"].logps,
                                   atol=1e-3, rtol=1e-5)

    def test_every_public_wrapper_is_covered(self):
        """Fail when a new ops.py wrapper lands without an equivalence case
        in this class (the gap this suite exists to close)."""
        import inspect
        from repro.kernels import ops
        wrappers = {n for n, f in vars(ops).items()
                    if inspect.isfunction(f) and not n.startswith("_")
                    and f.__module__ == "repro.kernels.ops"
                    and n not in ("pallas_enabled",)}
        covered = {n[len("test_"):-len("_wrapper")]
                   for n in dir(type(self))
                   if n.startswith("test_") and n.endswith("_wrapper")}
        assert wrappers <= covered, \
            f"ops wrappers without dispatch-equivalence tests: " \
            f"{sorted(wrappers - covered)}"


def test_grpo_loss_diff_gradient():
    """custom_vjp of the fused kernel matches autodiff of the jnp loss."""
    from repro.kernels.grpo_loss import grpo_loss_diff
    ks = jax.random.split(KEY, 3)
    lpn = jax.random.normal(ks[0], (32,)) * 0.1
    lpo = jax.random.normal(ks[1], (32,)) * 0.1
    adv = jax.random.normal(ks[2], (32,))

    def jnp_loss(lpn):
        loss, _ = ref.grpo_loss_ref(lpn, lpo, adv, clip=0.2)
        return loss.sum()

    def kern_loss(lpn):
        return grpo_loss_diff(lpn, lpo, adv, 0.2, True).sum()

    g_ref = jax.grad(jnp_loss)(lpn)
    g_kern = jax.grad(kern_loss)(lpn)
    np.testing.assert_allclose(g_kern, g_ref, atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("mode", ["off", "interpret"])
def test_cpu_only_pallas_modes_refused_on_tpu(monkeypatch, mode):
    """On a TPU backend the compiled kernels run: the CPU-only dispatch
    settings raise instead of running the reference or the interpreter
    under the name of a chip run."""
    from repro.kernels import ops
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("REPRO_PALLAS", mode)
    with pytest.raises(RuntimeError, match=f"REPRO_PALLAS={mode}"):
        ops.pallas_enabled()


def test_tpu_backend_dispatches_compiled_kernels(monkeypatch):
    from repro.kernels import ops
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("REPRO_PALLAS", raising=False)
    assert ops._mode() == "on"


def test_interpret_mode_still_runs_on_cpu(monkeypatch):
    from repro.kernels import ops
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    assert jax.default_backend() == "cpu"
    assert ops._mode() == "interpret"
    ks = jax.random.split(KEY, 3)
    v, x, eps = (jax.random.normal(k, (2, 8, 16)) for k in ks)
    xn, lp = ops.sde_step(v, x, eps, 0.9, 0.8, eta=0.7)
    xr, lr = ref.sde_step_ref(v, x, 0.9, 0.8, eps, eta=0.7)
    np.testing.assert_allclose(xn, xr, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(lp, lr, atol=1e-3, rtol=1e-5)
