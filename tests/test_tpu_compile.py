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
