"""What a rematerialised layer keeps of the flash kernel for its backward:
under ``remat_policy="dots"`` the kernel's output and log-sum-exp (tagged in
``ops/attention/flash.py``), so the backward runs no second ``flash_fwd``;
under ``"full"`` nothing, as it says. Counts and gradients only (the kernels
run in interpret mode here); the primal path carries no tag."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals

from deepspeed_tpu.analysis.jaxpr_passes import subjaxprs
from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config, cross_entropy_loss
from deepspeed_tpu.ops.attention.flash import (FLASH_LSE_NAME, FLASH_OUT_NAME,
                                               flash_attention)
from deepspeed_tpu.parallel.mesh import MeshSpec, set_global_mesh

LAYERS, BATCH, SEQ, HEADS, WIDTH, VOCAB = 2, 4, 128, 2, 64, 128


def _count(jaxpr, match):
    """``match(eqn)`` -> a name or None, counted over every equation of the
    jaxpr and of the jaxprs its equations hold (scan, remat, shard_map)."""
    found = collections.Counter()

    def walk(jx):
        for eqn in jx.eqns:
            found.update([match(eqn)])
            for sub in subjaxprs(eqn):
                walk(sub)

    walk(jaxpr.jaxpr)
    del found[None]
    return found


def _kernel(eqn):
    return eqn.params["name"] if eqn.primitive.name == "pallas_call" else None


def _tag(eqn):
    return eqn.params["name"] if eqn.primitive.name == "name" else None


def _model_loss(**options):
    cfg = GPT2Config(vocab_size=VOCAB, n_positions=SEQ, n_embd=WIDTH, n_layer=LAYERS,
                     n_head=HEADS, dtype=jnp.float32, attention_impl="flash", **options)
    model = GPT2(cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, VOCAB, (BATCH, SEQ)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)

    def loss(p):
        return cross_entropy_loss(model.apply(p, ids)[:, :-1], ids[:, 1:])

    return loss, params


@pytest.mark.parametrize("scan_layers", [True, False], ids=["scanned", "unrolled"])
@pytest.mark.parametrize("policy,forwards_a_layer", [("dots", 1), ("full", 2)])
def test_the_backward_of_a_remat_layer_runs_flash_fwd_again_only_under_full(
        scan_layers, policy, forwards_a_layer):
    loss, params = _model_loss(remat=True, remat_policy=policy, scan_layers=scan_layers)
    bodies = 1 if scan_layers else LAYERS
    assert _count(jax.make_jaxpr(jax.grad(loss))(params), _kernel) == {
        "flash_fwd": forwards_a_layer * bodies, "flash_bwd_dq": bodies,
        "flash_bwd_dkv": bodies}


def test_the_shard_map_route_keeps_the_residuals_too(eight_devices):
    """ZeRO-3 over fsdp=4, the mesh of ``configs/gpt2-1.3b-zero3.json``'s
    rehearsal: the kernel sits inside a ``shard_map`` there, whose partial
    evaluation hands the policy to its body."""
    set_global_mesh(MeshSpec({"fsdp": 4}, devices=eight_devices[:4]))
    loss, params = _model_loss(remat=True, remat_policy="dots", scan_layers=True)
    jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    assert "shard_map" in str(jaxpr)
    assert _count(jaxpr, _kernel) == {"flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1}
    plain, plain_params = _model_loss(remat=False, scan_layers=True)
    got, want = jax.jit(jax.grad(loss))(params), jax.jit(jax.grad(plain))(plain_params)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_the_saved_residuals_hold_the_kernels_output_and_log_sum_exp():
    """``saved_residuals`` names a residual by the equation it comes out of:
    the log-sum-exp by its tag; the output, which the layer also uses forward,
    by the ``reduce_precision`` jax puts on such a residual, so it is found
    by the kernel's layout (b*h, t, d_head), which no matmul output has."""
    loss, params = _model_loss(remat=True, remat_policy="dots", scan_layers=False)
    saved = saved_residuals(loss, params)
    assert sum(f"named '{FLASH_LSE_NAME}'" in why for _, why in saved) == LAYERS
    outputs = [why for aval, why in saved
               if aval.shape == (BATCH * HEADS, SEQ, WIDTH // HEADS)]
    assert len(outputs) == LAYERS and all("flash.py" in why for why in outputs)
    full, _ = _model_loss(remat=True, remat_policy="full", scan_layers=False)
    assert not any("flash" in why for _, why in saved_residuals(full, params))


@pytest.mark.parametrize("scan_layers", [True, False], ids=["scanned", "unrolled"])
def test_the_kept_residuals_give_the_gradients_of_no_remat_exactly(scan_layers):
    loss, params = _model_loss(remat=True, remat_policy="dots", scan_layers=scan_layers)
    plain, plain_params = _model_loss(remat=False, scan_layers=scan_layers)
    got, want = jax.grad(loss)(params), jax.grad(plain)(plain_params)
    leaves = jax.tree_util.tree_leaves_with_path(got)
    assert len(leaves) > 4
    for (path, a), b in zip(leaves, jax.tree_util.tree_leaves(want)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), jax.tree_util.keystr(path)


def test_the_primal_path_carries_no_tag():
    """A serving prefill calls ``flash_attention`` and never differentiates
    it: its jaxpr holds the kernel and no ``name`` equation, so the tags
    cannot have changed a serving program. A gradient holds both tags."""
    rng = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 256, 2, 32)), jnp.float32)
               for _ in range(3))
    primal = jax.make_jaxpr(flash_attention)(q, k, v)
    assert _count(primal, _kernel) == {"flash_fwd": 1}
    assert not _count(primal, _tag)
    grad = jax.make_jaxpr(jax.grad(lambda q, k, v: flash_attention(q, k, v).sum()))(q, k, v)
    assert _count(grad, _tag) == {FLASH_OUT_NAME: 1, FLASH_LSE_NAME: 1}
