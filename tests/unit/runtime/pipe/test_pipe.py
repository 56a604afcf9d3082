"""Pipeline module + engine tests on the virtual 8-device CPU mesh.

Analogue of reference ``tests/unit/runtime/pipe/test_pipe.py`` (pipeline vs data-parallel
convergence) and ``test_pipe_module.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models.gpt2 import GPT2Config
from deepspeed_tpu.models.gpt2_pipe import gpt2_pipeline_module
from deepspeed_tpu.parallel.mesh import MeshSpec, set_global_mesh
from deepspeed_tpu.runtime.pipe.module import partition_balanced


TINY = dict(vocab_size=128, n_positions=32, n_embd=32, n_layer=4, n_head=4,
            dropout=0.0, dtype=jnp.float32, scan_layers=False)


def _batch(rng, m, mb, t, vocab):
    ids = rng.integers(0, vocab, size=(m, mb, t)).astype(np.int32)
    labels = np.concatenate([ids[..., 1:], np.full((m, mb, 1), -100, np.int32)], axis=-1)
    return ids, labels


# ----------------------------------------------------------------- partition_balanced
def test_partition_balanced_uniform():
    assert partition_balanced([1, 1, 1, 1], 2) == [0, 2, 4]
    assert partition_balanced([1, 1, 1, 1, 1, 1], 3) == [0, 2, 4, 6]


def test_partition_balanced_weighted():
    # heavy head: bottleneck minimised by isolating it
    bounds = partition_balanced([10, 1, 1, 1], 2)
    assert bounds[0] == 0 and bounds[-1] == 4
    loads = [sum([10, 1, 1, 1][bounds[i]:bounds[i + 1]]) for i in range(2)]
    assert max(loads) == 10


def test_partition_balanced_all_parts_cover():
    w = [3, 1, 4, 1, 5, 9, 2, 6]
    for parts in (2, 3, 4):
        b = partition_balanced(w, parts)
        assert b[0] == 0 and b[-1] == len(w)
        assert all(b[i] <= b[i + 1] for i in range(parts))


# ----------------------------------------------------------------- module structure
def test_module_structure():
    cfg = GPT2Config(**TINY)
    mod = gpt2_pipeline_module(cfg, num_stages=4, sample_seq_len=32)
    # layers: embed + 4 blocks + ln_f + tied head
    assert len(mod) == cfg.n_layer + 3
    assert mod.body_end - mod.body_start == cfg.n_layer
    assert mod.layers_per_stage == 1
    params = mod.init_fn(jax.random.PRNGKey(0))
    # body stacked on leading dim
    leaves = jax.tree_util.tree_leaves(params["body"])
    assert all(l.shape[0] == cfg.n_layer for l in leaves)
    assert "embed" in params["tied"]
    assert params["tied"]["embed"]["wte"].shape == (cfg.vocab_size, cfg.n_embd)


def test_module_spill_to_pre():
    """5 blocks over 4 stages: one block spills into the pre segment."""
    cfg = GPT2Config(**{**TINY, "n_layer": 5})
    mod = gpt2_pipeline_module(cfg, num_stages=4, sample_seq_len=32)
    assert mod.body_end - mod.body_start == 4
    assert mod.layers_per_stage == 1


def test_module_too_few_layers():
    cfg = GPT2Config(**{**TINY, "n_layer": 2})
    with pytest.raises(ValueError, match="homogeneous"):
        gpt2_pipeline_module(cfg, num_stages=4, sample_seq_len=32)


# ----------------------------------------------------------------- numerics
def test_pipelined_equals_reference(eight_devices):
    """The collective-permute pipeline computes exactly the sequential forward."""
    cfg = GPT2Config(**TINY)
    mod = gpt2_pipeline_module(cfg, num_stages=4, sample_seq_len=32,
                               activation_checkpoint_interval=0)
    mesh = MeshSpec({"pipe": 4, "data": 2}, eight_devices)
    set_global_mesh(mesh)
    params = mod.init_fn(jax.random.PRNGKey(0))

    rng = np.random.default_rng(0)
    M, mb, t = 4, 2, 32
    ids, labels = _batch(rng, M, mb, t, cfg.vocab_size)
    model = mod.to_model(mesh_spec=mesh, remat=False)

    pipe_loss = jax.jit(model.loss_fn)(params, (ids, labels), jax.random.PRNGKey(7))

    # sequential ground truth per microbatch
    from deepspeed_tpu.models.gpt2 import cross_entropy_loss
    ref_losses = []
    for m in range(M):
        logits = mod.reference_apply(params, jnp.asarray(ids[m]), rng=None)
        ref_losses.append(cross_entropy_loss(logits, jnp.asarray(labels[m])))
    ref_loss = jnp.mean(jnp.stack(ref_losses))
    np.testing.assert_allclose(np.asarray(pipe_loss), np.asarray(ref_loss),
                               rtol=2e-5, atol=2e-5)


def test_pipelined_grads_match_reference(eight_devices):
    cfg = GPT2Config(**{**TINY, "n_layer": 4})
    mod = gpt2_pipeline_module(cfg, num_stages=2, sample_seq_len=32,
                               activation_checkpoint_interval=1)
    mesh = MeshSpec({"pipe": 2, "data": 4}, eight_devices)
    set_global_mesh(mesh)
    params = mod.init_fn(jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    M, mb, t = 2, 2, 32
    ids, labels = _batch(rng, M, mb, t, cfg.vocab_size)
    model = mod.to_model(mesh_spec=mesh)

    from deepspeed_tpu.models.gpt2 import cross_entropy_loss

    def ref_loss_fn(p):
        losses = [cross_entropy_loss(mod.reference_apply(p, jnp.asarray(ids[m]), None),
                                     jnp.asarray(labels[m])) for m in range(M)]
        return jnp.mean(jnp.stack(losses))

    g_pipe = jax.jit(jax.grad(lambda p: model.loss_fn(p, (ids, labels),
                                                      jax.random.PRNGKey(3))))(params)
    g_ref = jax.jit(jax.grad(ref_loss_fn))(params)
    flat_p, _ = jax.tree_util.tree_flatten_with_path(g_pipe)
    flat_r = jax.tree_util.tree_leaves(g_ref)
    for (path, a), b in zip(flat_p, flat_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5,
                                   err_msg=str(path))


# ----------------------------------------------------------------- engine integration
def test_pipeline_engine_trains(eight_devices):
    cfg = GPT2Config(**TINY)
    mod = gpt2_pipeline_module(cfg, num_stages=4, sample_seq_len=32)
    config = {
        "train_batch_size": 8,
        "gradient_accumulation_steps": 4,   # = microbatches through the pipe
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "mesh": {"pipe": 4, "data": 2},
        "steps_per_print": 10 ** 9,
    }
    engine, _, _, _ = ds.initialize(model=mod, config=config)
    from deepspeed_tpu.runtime.pipe.engine import PipelineEngine
    assert isinstance(engine, PipelineEngine)

    rng = np.random.default_rng(2)
    losses = []
    ids, labels = _batch(rng, 1, 8, 32, cfg.vocab_size)
    batch = (ids[0], labels[0])  # (B=8, T) split into gas=4 microbatches by the engine
    for _ in range(15):
        losses.append(float(engine.train_batch(batch=batch)))
    assert losses[-1] < losses[0] * 0.9, f"no learning: {losses[0]} -> {losses[-1]}"


def test_pipeline_engine_rejects_micro_api(eight_devices):
    cfg = GPT2Config(**TINY)
    mod = gpt2_pipeline_module(cfg, num_stages=2, sample_seq_len=32)
    config = {
        "train_batch_size": 8,
        "gradient_accumulation_steps": 2,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "mesh": {"pipe": 2, "data": 4},
        "steps_per_print": 10 ** 9,
    }
    engine, _, _, _ = ds.initialize(model=mod, config=config)
    with pytest.raises(RuntimeError, match="train_batch"):
        engine.forward(None)


# ----------------------------------------------------------------- 1F1B schedule path
def test_1f1b_matches_gpipe_loss_and_grads(eight_devices):
    """The interleaved 1F1B loop (manual in-loop backward) computes the same loss and
    gradients as autodiff through the GPipe fill-drain loop."""
    cfg = GPT2Config(**TINY)
    mod = gpt2_pipeline_module(cfg, num_stages=4, sample_seq_len=32)
    mesh = MeshSpec({"pipe": 4, "data": 2}, eight_devices)
    set_global_mesh(mesh)
    params = mod.init_fn(jax.random.PRNGKey(0))
    rng = np.random.default_rng(9)
    ids, labels = _batch(rng, 4, 2, 32, cfg.vocab_size)
    key = jax.random.PRNGKey(11)

    out = {}
    for sched in ("1f1b", "gpipe"):
        model = mod.to_model(mesh_spec=mesh, remat=True, schedule=sched)
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: model.loss_fn(p, (ids, labels), key)))(params)
        out[sched] = (float(loss), grads)
    assert out["1f1b"][0] == pytest.approx(out["gpipe"][0], rel=2e-5)
    flat_a, _ = jax.tree_util.tree_flatten_with_path(out["1f1b"][1])
    flat_b = jax.tree_util.tree_leaves(out["gpipe"][1])
    for (path, a), b in zip(flat_a, flat_b):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5,
                                   err_msg=str(path))


def test_1f1b_memory_flat_in_microbatches(eight_devices):
    """Peak activation (temp) memory must stay flat as the
    microbatch count doubles — the property 1F1B exists for. The GPipe autodiff path
    grows O(M); the 1F1B path's stash is O(stages)."""
    cfg = GPT2Config(**TINY)
    mod = gpt2_pipeline_module(cfg, num_stages=4, sample_seq_len=32)
    mesh = MeshSpec({"pipe": 4, "data": 2}, eight_devices)
    set_global_mesh(mesh)
    params = mod.init_fn(jax.random.PRNGKey(0))

    def temp_bytes(schedule, M):
        model = mod.to_model(mesh_spec=mesh, remat=True, schedule=schedule)
        ids = np.zeros((M, 2, 32), np.int32)
        labels = np.zeros((M, 2, 32), np.int32)
        f = jax.jit(lambda p: jax.value_and_grad(
            lambda pp: model.loss_fn(pp, (ids, labels), jax.random.PRNGKey(0)))(p))
        ma = f.lower(params).compile().memory_analysis()
        if ma is None or not hasattr(ma, "temp_size_in_bytes"):
            pytest.skip("backend does not expose memory analysis")
        return ma.temp_size_in_bytes

    t4, t16 = temp_bytes("1f1b", 4), temp_bytes("1f1b", 16)
    assert t16 <= t4 * 1.05, f"1f1b temp memory grew with M: {t4} -> {t16}"
    g4, g16 = temp_bytes("gpipe", 4), temp_bytes("gpipe", 16)
    assert g16 > g4 * 2, f"expected gpipe O(M) growth as the contrast: {g4} -> {g16}"
