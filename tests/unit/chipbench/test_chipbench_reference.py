"""The plain float32 references against the program, on the CPU at a small
size and seeded random weights. (On the chip, at published widths:
``run.py --check-reference bloom-7b1``.)"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.chipbench.reference import bloom, gpt2  # noqa: E402


def test_alibi_slopes_follow_the_paper():
    assert np.allclose(bloom.alibi_slopes(8), [2.0 ** -(i + 1) for i in range(8)])
    from deepspeed_tpu.models.causal_lm import alibi_slopes
    for n in (1, 4, 12, 32):
        assert np.allclose(bloom.alibi_slopes(n), alibi_slopes(n), rtol=1e-6)


def test_bloom_reference_agrees_with_the_program_in_float32():
    """float32 against float32: only the order of operations differs, so the
    tolerance is a few float32 roundings of logits of size ~1 (1e-4); a missing
    ALiBi bias or embedding layernorm gives 1e-1."""
    from deepspeed_tpu.models import causal_lm
    cfg = causal_lm.bloom_cfg(vocab_size=384, max_seq_len=64, n_embd=64,
                              n_layer=2, n_head=4, dtype=jnp.float32)
    module = causal_lm.CausalLM(cfg)
    ids = np.random.default_rng(0).integers(1, 384, size=(2, 40)).astype(np.int32)
    params = module.init(jax.random.PRNGKey(3), jnp.asarray(ids))["params"]
    # biases and norms away from their zero/one initial values
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    params = jax.tree_util.tree_unflatten(
        tree, [l + 0.05 * jax.random.normal(k, l.shape) for l, k in zip(leaves, keys)])
    with jax.default_matmul_precision("highest"):
        served = np.asarray(module.apply({"params": params}, jnp.asarray(ids)))
    ref = np.asarray(bloom.forward(params, jnp.asarray(ids), cfg.n_head))
    assert served.shape == ref.shape == (2, 40, 384)
    assert np.abs(served - ref).max() < 1e-4 * max(1.0, np.abs(ref).max())
    no_alibi = np.asarray(bloom.forward(
        params, jnp.asarray(ids[:, ::-1].copy()), cfg.n_head))
    assert np.abs(no_alibi - ref).max() > 1e-2          # the check can fail


@pytest.mark.parametrize("scan_layers", [True, False])
def test_gpt2_reference_loss_and_gradients_agree_with_the_program(scan_layers):
    from deepspeed_tpu.models import GPT2Config, gpt2_model
    cfg = GPT2Config(vocab_size=256, n_positions=48, n_embd=64, n_layer=2, n_head=4,
                     dropout=0.0, scan_layers=scan_layers, dtype=jnp.float32)
    model = gpt2_model(cfg, sample_seq_len=48)
    params = model.init_fn(jax.random.PRNGKey(1))
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 256, size=(3, 48)), jnp.int32)

    def program_loss(p):
        out = model.loss_fn(p, {"input_ids": ids}, jax.random.PRNGKey(0))
        return out[0] if isinstance(out, tuple) else out

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(program_loss)(params)
    # the program's layernorms keep flax's epsilon (1e-6), the published config
    # says 1e-5: compared at the program's value; at the published one the
    # gradients differ by about one percent, which the last lines show
    lr, gr = jax.value_and_grad(lambda p: gpt2.loss(p, ids, cfg.n_head, 1e-6))(params)
    assert abs(float(lp) - float(lr)) < 1e-4 * float(lr)
    for a, b in zip(jax.tree_util.tree_leaves(gp), jax.tree_util.tree_leaves(gr)):
        scale = max(1e-6, float(jnp.abs(b).max()))
        assert float(jnp.abs(a - b).max()) < 2e-3 * scale
    published = jax.grad(lambda p: gpt2.loss(p, ids, cfg.n_head))(params)
    worst = max(float(jnp.abs(a - b).max() / jnp.abs(b).max()) for a, b in zip(
        jax.tree_util.tree_leaves(gp), jax.tree_util.tree_leaves(published)))
    assert 2e-3 < worst < 5e-2
