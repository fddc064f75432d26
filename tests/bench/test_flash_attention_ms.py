"""The ``flash_attention_ms`` reader on synthetic traces."""
from types import SimpleNamespace as NS

import pytest

from bench import harness, trace
from bench.metrics import flash_attention_ms


def ev(name, start, end):
    return NS(name=name, start_ns=start, end_ns=end)


def planes(ops):
    """Two steps on one TPU, sample [100, 400] and [1100, 1400], with the
    given operations; host spans bound the window to [50, 1450]."""
    modules = [ev("jit__sample(1)", 100, 400),
               ev("jit__sample(1)", 1100, 1400)]
    host = [ev("bench.dispatch", 50, 90), ev("bench.drain", 90, 1450)]
    return [NS(name="/device:TPU:0",
               lines=[NS(name="XLA Ops", events=ops),
                      NS(name="XLA Modules", events=modules)]),
            NS(name="/host:CPU", lines=[NS(name="python", events=host)])]


def ctx_for(planes_, steps=2):
    tr = trace.from_planes(planes_)
    lo, hi = trace.window(tr)
    return harness.Context(trace=tr, lo=lo, hi=hi, steps=steps, config={},
                           traffic={}, peaks={}, shapes={})


def test_flash_attention_ms_reads_the_kernel():
    """The kernel's calls in the window, whatever their instruction's
    number, per step; silent where the programs hold no such kernel."""
    ops = [ev("%flash_attention.2 = bf16[4] custom-call(bf16[4] %q)", 100,
              160),
           ev("%fusion.1 = f32[4] fusion(f32[4] %x)", 160, 200),
           ev("%flash_attention = bf16[4] custom-call(bf16[4] %q)", 1100,
              1140),
           ev("%flash_attention_like.1 = f32[4] fusion(f32[4] %x)", 1140,
              1200),
           ev("%flash_attention.3 = bf16[4] custom-call(bf16[4] %q)", 1500,
              1600)]                                 # after the window
    assert flash_attention_ms.read(ctx_for(planes(ops))) == \
        pytest.approx((60 + 40) / 2 / 1e6)
    assert flash_attention_ms.read(ctx_for(planes(ops[1:2]))) is None


def test_flash_attention_ms_is_silent_without_a_device():
    host = [p for p in planes([]) if p.name.startswith("/host")]
    assert flash_attention_ms.read(ctx_for(host)) is None
