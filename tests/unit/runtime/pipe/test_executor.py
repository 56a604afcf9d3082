"""Eager schedule-executor tests: heterogeneous stages, gradient correctness, and the
1F1B activation-stash bound.

Mirrors the territory of reference ``tests/unit/runtime/pipe/test_pipe.py`` for models that
are NOT one repeated block — the SPMD loop requires a homogeneous body; this path does not.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.runtime.pipe.executor import EagerPipelineExecutor
from deepspeed_tpu.runtime.pipe.module import LambdaLayer, PipeLayer
from deepspeed_tpu.runtime.pipe.schedule import TrainSchedule


class Dense(PipeLayer):
    """fan_in -> fan_out linear + optional relu; every instance a different shape."""

    def __init__(self, fan_in, fan_out, act=False):
        self.fan_in, self.fan_out, self.act = fan_in, fan_out, act

    def init(self, rng, x):
        k1, _ = jax.random.split(rng)
        return {"w": jax.random.normal(k1, (self.fan_in, self.fan_out),
                                       jnp.float32) * 0.2,
                "b": jnp.zeros((self.fan_out,), jnp.float32)}

    def apply(self, params, x, rng=None):
        y = x @ params["w"] + params["b"]
        return jax.nn.relu(y) if self.act else y


def _heterogeneous_layers():
    # widths vary, an activation-only lambda sits mid-stream: no homogeneous body exists
    return [Dense(8, 32, act=True), Dense(32, 32, act=True),
            LambdaLayer(lambda x: x * 0.5), Dense(32, 16, act=True),
            Dense(16, 16, act=True), Dense(16, 4)]


def _mse(out, label):
    return jnp.mean((out - label) ** 2)


def _make(num_stages):
    return EagerPipelineExecutor(_heterogeneous_layers(), num_stages=num_stages,
                                 loss_fn=_mse, sample_input=jnp.zeros((2, 8)))


def _microbatches(m, rng_seed=0):
    rng = np.random.default_rng(rng_seed)
    return [(jnp.asarray(rng.standard_normal((2, 8)), jnp.float32),
             jnp.asarray(rng.standard_normal((2, 4)), jnp.float32))
            for _ in range(m)]


@pytest.mark.parametrize("num_stages", [2, 3])
def test_heterogeneous_grads_match_sequential(num_stages):
    ex = _make(num_stages)
    params = ex.init_params(jax.random.PRNGKey(0))
    mbs = _microbatches(4)

    loss, grads, stats = ex.train_batch_grads(params, mbs)

    def seq_loss(ps):
        total = 0.0
        for x, lab in mbs:
            h = x
            for layer, p in zip(ex._layers, ps):
                h = layer.apply(p, h, None)
            total = total + _mse(h, lab)
        return total / len(mbs)

    ref_loss, ref_grads = jax.value_and_grad(seq_loss)(params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    for g, r in zip(grads, ref_grads):
        flat_g = jax.tree_util.tree_leaves(g)
        flat_r = jax.tree_util.tree_leaves(r)
        for a, b in zip(flat_g, flat_r):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)


def test_stash_bound_is_1f1b_not_gpipe():
    """Peak live stage-input stashes never exceed num_pipe_buffers (≤ stages), flat as
    M doubles — the memory property GPipe lacks."""
    ex = _make(3)
    params = ex.init_params(jax.random.PRNGKey(0))
    peaks = {}
    for m in (4, 8, 16):
        _, _, stats = ex.train_batch_grads(params, _microbatches(m))
        peaks[m] = stats["peak_stash"]
        bound = max(TrainSchedule(m, 3, s).num_pipe_buffers() for s in range(3))
        assert stats["peak_stash"] <= bound, (m, stats["peak_stash"], bound)
    assert peaks[16] == peaks[4], f"stash grew with M: {peaks}"


def test_heterogeneous_partition_balances_parameters():
    ex = _make(3)
    # parts cover all layers contiguously
    assert ex.parts[0] == 0 and ex.parts[-1] == len(ex._layers)
    # parameter-weighted: the big 32x32 block should not share a stage with both
    # neighbours' heavies at once (bottleneck minimised)
    weights = [2 * 8 * 32, 32 * 32, 0, 32 * 16, 16 * 16, 16 * 4]
    loads = [sum(weights[ex.parts[i]:ex.parts[i + 1]]) for i in range(3)]
    assert max(loads) < sum(weights)


def test_inference_schedule_outputs():
    ex = _make(2)
    params = ex.init_params(jax.random.PRNGKey(0))
    mbs = _microbatches(3)
    outs = ex.infer_batch(params, [x for x, _ in mbs])
    for (x, _), y in zip(mbs, outs):
        h = x
        for layer, p in zip(ex._layers, params):
            h = layer.apply(p, h, None)
        np.testing.assert_allclose(np.asarray(y), np.asarray(h), rtol=1e-6)


def test_tied_layers_share_params_and_sum_grads():
    """TiedLayerSpec members alias one parameter set; their gradient is the group sum
    (ReduceTiedGrads semantics) so aliased copies stay identical under any update."""
    from deepspeed_tpu.runtime.pipe.module import TiedLayerSpec

    layers = [TiedLayerSpec("w", Dense, 8, 8, act=True), Dense(8, 8, act=True),
              TiedLayerSpec("w", Dense, 8, 8)]
    ex = EagerPipelineExecutor(layers, num_stages=2, loss_fn=_mse,
                               sample_input=jnp.zeros((2, 8)))
    params = ex.init_params(jax.random.PRNGKey(0))
    assert params[0] is params[2]

    rng = np.random.default_rng(1)
    mbs = [(jnp.asarray(rng.standard_normal((2, 8)), jnp.float32),
            jnp.asarray(rng.standard_normal((2, 8)), jnp.float32)) for _ in range(2)]
    loss, grads, _ = ex.train_batch_grads(params, mbs)

    # ground truth: differentiate wrt the SHARED weight (appears at both positions)
    def seq_loss(shared, mid):
        total = 0.0
        for x, lab in mbs:
            h = ex._layers[0].apply(shared, x, None)
            h = ex._layers[1].apply(mid, h, None)
            h = ex._layers[2].apply(shared, h, None)
            total = total + _mse(h, lab)
        return total / len(mbs)

    ref_shared, ref_mid = jax.grad(seq_loss, argnums=(0, 1))(params[0], params[1])
    for a, b in zip(jax.tree_util.tree_leaves(grads[0]),
                    jax.tree_util.tree_leaves(ref_shared)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(grads[2]),
                    jax.tree_util.tree_leaves(ref_shared)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(grads[1]),
                    jax.tree_util.tree_leaves(ref_mid)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
