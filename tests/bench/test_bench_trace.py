"""The reduction from a profiler trace to per-layer metrics, and the FLOP
count behind ``step_mfu``."""
import os
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import pytest

from bench import flops, harness, trace
from bench.metrics import (device_idle_share, rewards_ms, rollout_ms,
                           update_ms)

DATA = os.path.join(os.path.dirname(__file__), "data")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
KERNEL = r"sde_step(\.\d+)?$"   # the fused SDE step's HLO instruction


def ev(name, start, end):
    return NS(name=name, start_ns=start, end_ns=end)


def synthetic():
    """Two steps on one TPU: sample [100, 400], rewards [420, 440],
    update [450, 900], then sample [1100, 1400]; ops inside them overlap
    each other; host spans around dispatch and drain."""
    ops = [ev("%fusion.1 = f32[4] fusion(f32[4] %x)", 100, 250),
           ev("%sde_step.3 = (f32[4]) custom-call(f32[4] %y)", 200, 300),
           ev("%fusion.2 = f32[4] fusion(f32[4] %sde_step.3)", 300, 400),
           ev("%reduce.7 = f32[] reduce(f32[4] %z)", 420, 440),
           ev("%while.9 = (s32[]) while((s32[]) %t)", 450, 900),
           ev("%dot.4 = f32[4] dot(f32[4] %a)", 450, 900),
           ev("%fusion.1 = f32[4] fusion(f32[4] %x)", 1100, 1300),
           ev("%sde_step.3 = (f32[4]) custom-call(f32[4] %y)", 1300, 1400)]
    modules = [ev("jit__sample(1)", 100, 400),
               ev("jit__unknown(2)", 420, 440),
               ev("jit__update(3)", 450, 900),
               ev("jit__sample(1)", 1100, 1400)]
    host = [ev("bench.dispatch", 50, 90), ev("bench.drain", 90, 1000),
            ev("bench.conditions", 990, 1060), ev("bench.dispatch", 1060,
                                                  1090),
            ev("bench.drain", 1090, 1450), ev("other", 0, 2000)]
    return [NS(name="/device:TPU:0",
               lines=[NS(name="XLA Ops", events=ops),
                      NS(name="XLA Modules", events=modules)]),
            NS(name="/host:CPU", lines=[NS(name="python", events=host)])]


def test_union_merges_and_clips():
    assert trace.union([(5, 8), (1, 3), (2, 4), (7, 12)], 0, 10) == \
        [(1, 4), (5, 10)]
    assert trace.union([(1, 2)], 5, 10) == []


def test_synthetic_reduction():
    tr = trace.from_planes(synthetic())
    lo, hi = trace.window(tr)
    assert (lo, hi) == (50, 1450)
    assert [s.name for s in tr.spans][0] == "bench.dispatch"
    # busy: [100, 440] minus (400, 420), [450, 900], [1100, 1400]
    assert trace.busy_ns(tr, lo, hi) == 320 + 450 + 300
    assert trace.module_ns(tr, rollout_ms.MODULE, lo, hi) == 600
    assert trace.module_ns(tr, update_ms.MODULE, lo, hi) == 450
    assert trace.module_ns(tr, rewards_ms.MODULE, lo, hi) == 20
    kern = trace.op_events(tr, KERNEL, lo, hi)
    assert [e.dur for e in kern] == [100, 100]
    # each call: 2e-11 s of operations at the peak, 4.1e-8 s of bytes
    assert trace.roofline_pct(kern, 3940.0, 33540.0, PEAKS) == \
        pytest.approx(100 * 2 * 33540.0 / 819e9 / 200e-9)
    assert trace.roofline_pct(kern, 1e6, 8.0, PEAKS) == \
        pytest.approx(100 * 2 * 1e6 / 197e12 / 200e-9)
    assert trace.roofline_pct([], 1.0, 1.0, PEAKS) is None
    top = trace.top_ops(tr, lo, hi, top=2)
    assert top == [("dot.4", 450e-9), ("fusion.1", 350e-9)]
    gaps = trace.idle_gaps(tr, lo, hi, top=3)
    # (900, 1100) is covered by the drain, then the conditions span
    assert gaps[0] == ("bench.conditions", 200e-9)
    assert gaps[1] == ("bench.dispatch", 50e-9)


def ctx_for(tr, steps, traffic=None):
    lo, hi = trace.window(tr)
    traffic = traffic or {"batch_prompts": 2, "group_size": 8,
                          "latent_tokens": 1024, "latent_dim": 64}
    return harness.Context(trace=tr, lo=lo, hi=hi, steps=steps, config={},
                           traffic=traffic, peaks=PEAKS, shapes={})


def test_synthetic_metrics():
    c = ctx_for(trace.from_planes(synthetic()), steps=2)
    assert device_idle_share.read(c) == pytest.approx(
        100 * (1 - 1070 / 1400))
    assert rollout_ms.read(c) == pytest.approx(300e-6)
    assert update_ms.read(c) == pytest.approx(225e-6)
    assert rewards_ms.read(c) == pytest.approx(10e-6)


def test_readers_are_silent_without_a_device():
    planes = [p for p in synthetic() if p.name.startswith("/host")]
    c = ctx_for(trace.from_planes(planes), steps=2)
    for mod in (device_idle_share, rollout_ms, update_ms, rewards_ms):
        assert mod.read(c) is None


def test_recorded_v5e_trace():
    """A trace of the Flow-GRPO cell cut to tiny sizes, one whole step of
    its window, recorded on a TPU v5e (bench/tools/record_trace.py)."""
    tr = trace.load(os.path.join(DATA, "v5e_grpo_tiny.xplane.pb"))
    assert tr.devices == ["/device:TPU:0"]
    lo, hi = trace.window(tr)
    busy = trace.busy_ns(tr, lo, hi)
    assert 0 < busy < hi - lo
    parts = [trace.module_ns(tr, m.MODULE, lo, hi)
             for m in (rollout_ms, rewards_ms, update_ms)]
    assert all(p > 0 for p in parts)
    # operations run inside their programs, which also hold short gaps
    every_program = trace.module_ns(tr, ".", lo, hi)
    assert busy <= every_program and sum(parts) <= every_program
    # two denoising steps: two fused SDE steps in the step's rollout
    kern = trace.op_events(tr, KERNEL, lo, hi)
    assert len(kern) == 2
    assert all(e.dur > 0 for e in kern)
    assert trace.top_ops(tr, lo, hi)
    assert trace.idle_gaps(tr, lo, hi)


def test_velocity_flops_match_cost_analysis():
    """The count from parameter shapes against XLA's own count of one
    un-scanned (one-layer) velocity forward at the reduced size, on the
    CPU.  XLA also counts elementwise work, which the model count leaves
    out: they agree within 3 %."""
    from repro.config import FlowRLConfig
    from repro.configs.flux_dit import reduced
    from repro.models import params as params_lib
    from repro.models.flow import FlowAdapter
    import dataclasses
    arch = dataclasses.replace(reduced(), n_layers=1)
    flow = FlowRLConfig(latent_tokens=64, latent_dim=16)
    ad = FlowAdapter(arch, flow, cond_dim=32)
    p = params_lib.shape_tree(ad.spec(), jnp.bfloat16)
    B, Lc = 4, 8
    args = (jax.ShapeDtypeStruct((B, 64, 16), jnp.float32),
            jax.ShapeDtypeStruct((B,), jnp.float32),
            jax.ShapeDtypeStruct((B, Lc, 32), jnp.float32))
    compiled = jax.jit(ad.velocity).lower(p, *args).compile()
    xla = compiled.cost_analysis()["flops"]
    shapes = {k: tuple(v.shape) for k, v in harness.leaf_dict(p).items()}
    ours = flops.velocity_flops(shapes, B, 64, Lc)
    assert ours == pytest.approx(xla, rel=0.03)
    assert ours <= xla
