"""Ahead-of-time compiles for a described TPU v5e (``v5e:2x2``): the main
path's Pallas kernels and the full-width velocity field go through the
chip's own compiler, which refuses what interpret mode accepts (block
shapes off the (8, 128) tiling, programs that do not fit HBM).  Nothing
runs, so these say nothing about results or speed.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  The persistent compilation cache is off around the compiles (a
cached entry for a described device cannot be read back without a chip).
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.config import FlowRLConfig
from repro.kernels.flash_attention import flash_attention
from repro.kernels.grpo_loss import grpo_loss, grpo_loss_diff
from repro.kernels.sde_step import sde_step
from repro.models import params as params_lib
from repro.models.flow import FlowAdapter

F32 = jnp.float32
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("shape", [(16, 1024, 64), (16, 64, 16)],
                         ids=["flux_512px", "default"])
def test_sde_step_compiles(one_chip, shape):
    x = _struct(shape, F32, one_chip)
    t = _struct((), F32, one_chip)
    compiled = jax.jit(
        lambda v, x, e, t, tn: sde_step(v, x, e, t, tn, eta=0.7)
    ).lower(x, x, x, t, t).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the kernel's name, which a profile's operation events carry
    assert re.search(r"%sde_step(\.\d+)? = ", text)


@pytest.mark.parametrize("B", [16, 1024])
@pytest.mark.parametrize("kernel", ["grpo_loss", "grpo_loss_diff"])
def test_grpo_loss_compiles(one_chip, kernel, B):
    fn = {"grpo_loss": lambda a, b, c: grpo_loss(a, b, c, clip=0.2)[0],
          "grpo_loss_diff": lambda a, b, c: grpo_loss_diff(a, b, c, 0.2,
                                                           False)}[kernel]
    x = _struct((B,), F32, one_chip)
    compiled = jax.jit(fn).lower(x, x, x).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert re.search(r"%grpo_loss(\.\d+)? = ", text)


# FLUX.1 at 512px: 16 trajectories of 16 condition + 1024 latent tokens,
# 24 heads of 128
FLUX_ATTENTION = (16, 1040, 24, 128)


@pytest.mark.parametrize("seq", [1040, 4112], ids=["flux_512px",
                                                    "flux_1024px"])
def test_flash_attention_compiles(one_chip, seq):
    """At 512px the whole key length is one kv block; at 1024px (4096
    latent tokens) the kv-blocked path with its masked key tail."""
    x = _struct((16, seq) + FLUX_ATTENTION[2:], jnp.bfloat16, one_chip)
    compiled = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, causal=False)
    ).lower(x, x, x).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert re.search(r"%flash_attention(\.\d+)? = ", text)


def test_flash_attention_gradient_compiles(one_chip):
    """Forward and custom VJP at the update's batch of one."""
    x = _struct((1,) + FLUX_ATTENTION[1:], jnp.bfloat16, one_chip)

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=False)
        return (o.astype(F32) ** 2).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile()
    assert re.search(r"%flash_attention(\.\d+)? = ", compiled.as_text())


def test_velocity_full_width_fits(one_chip):
    """flux_dit's velocity forward at its published widths (one layer) at
    the smoke run's batch: 16 trajectories of FLUX's 1024x64 packed
    latent."""
    arch = dataclasses.replace(configs.get("flux_dit"), n_layers=1)
    adapter = FlowAdapter(arch, FlowRLConfig(latent_tokens=1024,
                                             latent_dim=64), cond_dim=512)
    params = jax.tree.map(lambda s: _struct(s.shape, s.dtype, one_chip),
                          params_lib.shape_tree(adapter.spec(),
                                                jnp.bfloat16))
    x = _struct((16, 1024, 64), F32, one_chip)
    t = _struct((16,), F32, one_chip)
    cond = _struct((16, 16, 512), F32, one_chip)
    compiled = jax.jit(adapter.velocity).lower(params, x, t, cond).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert arch.d_model == 3072 and arch.d_ff == 12288
    assert 0 < total < V5E_HBM_BYTES, total


def _instructions(text):
    """{name: (opcode, operand names)} of a compiled HLO module's text."""
    out = {}
    for m in re.finditer(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$", text, re.M):
        op = re.search(r"[}\])] ([\w\-]+)\(([^)]*)\)", m.group(2))
        if op:
            out[m.group(1)] = (op.group(1),
                               re.findall(r"%([\w.\-]+)", op.group(2)))
    return out


def _kernel_neighbours(text, kernel):
    """The instructions that make the operands of ``kernel`` and that
    read its result, looking through bitcasts: (opcode, name) pairs."""
    ins = _instructions(text)

    def producer(name):
        while ins[name][0] == "bitcast":
            name = ins[name][1][0]
        return ins[name][0], name

    def consumers(name):
        for user, (op, args) in ins.items():
            if name in args:
                yield from (consumers(user) if op == "bitcast"
                            else [(op, user)])

    return ([producer(a) for a in ins[kernel][1]]
            + list(consumers(kernel)))


def _is_relayout(opcode, name):
    return (opcode in ("copy", "transpose")
            or re.match(r"(copy|transpose)", name) is not None)


def test_relayout_is_seen_around_a_kernel(one_chip):
    """The layout check below sees a relayout: a kernel whose operands
    arrive as (B, S, H, D) in the default layout gets transposes."""
    x = _struct(FLUX_ATTENTION, jnp.bfloat16, one_chip)
    text = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, causal=False)
    ).lower(x, x, x).compile().as_text()
    call = re.search(r"%(flash_attention(?:\.\d+)?) = ", text).group(1)
    assert any(_is_relayout(*n) for n in _kernel_neighbours(text, call))


def test_velocity_runs_the_kernel_in_its_scope(one_chip, monkeypatch):
    """With the kernels on, as on the chip, the velocity's bidirectional
    attention compiles to one flash kernel call for its one block, inside
    the ``dit_attention`` scope that ``attention_ms`` reads.  The kernel's
    (B*H, S, D) operands and result are bitcasts of what the projections
    make and the output projection reads: no copy or transpose feeds it or
    reads it, at FLUX widths."""
    monkeypatch.setenv("REPRO_PALLAS", "on")
    arch = dataclasses.replace(configs.get("flux_dit"), n_layers=1)
    adapter = FlowAdapter(arch, FlowRLConfig(latent_tokens=1024,
                                             latent_dim=64), cond_dim=512)
    params = jax.tree.map(lambda s: _struct(s.shape, s.dtype, one_chip),
                          params_lib.shape_tree(adapter.spec(),
                                                jnp.bfloat16))
    x = _struct((16, 1024, 64), F32, one_chip)
    t = _struct((16,), F32, one_chip)
    cond = _struct((16, 16, 512), F32, one_chip)
    text = jax.jit(adapter.velocity).lower(params, x, t, cond).compile(
        ).as_text()
    calls = re.findall(r"%flash_attention(?:\.\d+)? = .*", text)
    assert len(calls) == 1
    op_name = re.search(r'op_name="([^"]*)"', calls[0]).group(1)
    assert "dit_attention" in op_name.split("/"), op_name
    call = re.match(r"%([\w.]+)", calls[0]).group(1)
    neighbours = _kernel_neighbours(text, call)
    assert len(neighbours) >= 4, neighbours
    assert not [n for n in neighbours if _is_relayout(*n)], neighbours
