"""``analysis/lowered.py``: Mosaic kernels read off lowered StableHLO text.

Lowering FOR the TPU platform needs no TPU, so the parser is pinned here on
the CPU host against a real compiled-Pallas lowering."""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from deepspeed_tpu.analysis.lowered import main_int_arg_shapes, mosaic_calls


def _double(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2


def _call(x, name):
    return pl.pallas_call(
        _double, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype), name=name)(x)


def _lower(fn, *args):
    return jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).as_text()


def test_kernels_counted_and_loop_residency_found():
    def fn(ids, x):
        x = _call(x, "outside_kernel")

        def body(i, x):
            return _call(_call(x, "inside_kernel"), "inside_kernel") \
                + jnp.sum(ids)
        return jax.lax.fori_loop(0, 3, body, x)

    text = _lower(fn, jnp.zeros((1, 64), jnp.int32),
                  jnp.ones((128, 128), jnp.float32))
    calls = {c.kernel: c for c in mosaic_calls(text)}
    assert set(calls) == {"inside_kernel", "outside_kernel"}
    assert calls["inside_kernel"].count == 2 and calls["inside_kernel"].in_loop
    assert calls["outside_kernel"].count == 1
    assert not calls["outside_kernel"].in_loop
    assert main_int_arg_shapes(text) == ["1x64"]


def test_plain_xla_program_holds_no_mosaic_call():
    text = _lower(lambda ids, x: x * 2 + jnp.sum(ids),
                  jnp.zeros((1, 8), jnp.int32), jnp.ones((8, 128)))
    assert mosaic_calls(text) == []
    assert main_int_arg_shapes(text) == ["1x8"]
