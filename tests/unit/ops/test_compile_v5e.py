"""Kernels of the serving path compiled at their real widths for a DESCRIBED
v5e chip (none is attached here): what interpret mode cannot show — a slice
not aligned to the tiling, more VMEM than a kernel may use. Nothing runs, so
nothing here is a result or a time. One file, one fixture: only the worker
that is given this file loads the TPU's compiler."""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("tokens", [32, 64, 512])
def test_the_grouped_expert_kernel_compiles_at_the_published_widths(
        one_chip, tokens, monkeypatch):
    from deepspeed_tpu.moe.latent_moe import relu2
    from deepspeed_tpu.ops.moe import grouped_ffn as g
    monkeypatch.setattr(g, "_interpret", lambda: False)     # compile, do not interpret
    held, latent, width, k = 128, 1024, 2688, 22
    tm = g.tile_rows(tokens * k)
    tiles = tokens * k // tm + held

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(functools.partial(g.grouped_ffn, act=relu2, tm=tm)).lower(
        sds((tiles * tm, latent), jnp.bfloat16), sds((tiles,), jnp.int32),
        sds((tiles,), jnp.int32), sds((held, latent, width), jnp.bfloat16),
        sds((held, width, latent), jnp.bfloat16)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "moe_grouped_ffn" in text
    # the plan lowers for the chip too (no sort, no scatter in it)
    plan = jax.jit(functools.partial(g.dispatch_plan, first=0, count=held, tm=tm)).lower(
        sds((tokens, k), jnp.int32)).compile().as_text()
    assert " sort(" not in plan and " scatter(" not in plan
