"""Pipe×expert: MoE blocks as 1F1B pipeline body layers.

Expert all-to-all inside the stage_fn (the ``expert`` axis stays
under GSPMD while the shard_map is manual over ``pipe``), per-layer load-balancing
aux losses aggregated across layers/stages/microbatches, and the full
pipe×expert×data engine composition. Reference: ``deepspeed/utils/groups.py:109``,
``runtime/pipe/topology.py:243``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import deepspeed_tpu as ds
from deepspeed_tpu.models.gpt2_moe import GPT2MoEConfig
from deepspeed_tpu.models.gpt2_moe_pipe import gpt2_moe_pipeline_module
from deepspeed_tpu.models.gpt2 import cross_entropy_loss
from deepspeed_tpu.parallel.mesh import MeshSpec, set_global_mesh

TINY = dict(vocab_size=64, n_positions=32, n_embd=32, n_head=4, n_layer=4,
            dropout=0.0, dtype=jnp.float32, remat=False, scan_layers=False,
            num_experts=2, moe_layer_interval=2, top_k=1,
            noisy_gate_policy="RSample", moe_loss_coef=0.01)


def _batch(M=4, mb=2, t=32, seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, 64, size=(M, mb, t)).astype(np.int32)
    labels = np.concatenate([ids[:, :, 1:], np.full((M, mb, 1), -100, np.int32)],
                            axis=2)
    return {"inputs": ids, "labels": labels}


def _sequential_loss(mod, coef):
    """Sequential reference replaying the 1F1B executor's exact rng folds so the
    RSample gating noise (and any dropout) matches microbatch-for-microbatch."""
    body_layer = mod._layers[mod.body_start]
    L_per = mod.layers_per_stage
    n_body = mod.body_end - mod.body_start

    def loss(params, batch, rng):
        inputs, labels = batch["inputs"], batch["labels"]
        M = inputs.shape[0]
        rng_pre = jax.random.fold_in(rng, 1)
        rng_body = jax.random.fold_in(rng, 2)
        rng_tail = jax.random.fold_in(rng, 3)

        def one(m):
            inp = jax.tree_util.tree_map(lambda a: a[m], inputs)
            lab = jax.tree_util.tree_map(lambda a: a[m], labels)
            view = {"pre": params["pre"], "post": {}, "tied": params["tied"]}
            x = mod._segment_apply(view, inp, jax.random.fold_in(rng_pre, m),
                                   0, mod.body_start)
            aux_total = jnp.float32(0.0)
            for jg in range(n_body):
                s, j_in = jg // L_per, jg % L_per
                p_j = jax.tree_util.tree_map(lambda a: a[jg], params["body"])
                srng = jax.random.fold_in(jax.random.fold_in(rng_body, m), s)
                r = jax.random.split(srng, L_per)[j_in]
                x, aux = body_layer.apply_with_aux(p_j, x, r)
                aux_total = aux_total + aux
            view = {"pre": {}, "post": params["post"], "tied": params["tied"]}
            out = mod._segment_apply(view, x, jax.random.fold_in(rng_tail, m),
                                     mod.body_end, len(mod._layers))
            return cross_entropy_loss(out, lab) + jnp.float32(coef) * aux_total

        return jnp.mean(jnp.stack([one(m) for m in range(M)]))

    return loss


class TestMoE1F1B:
    def test_1f1b_matches_sequential(self, eight_devices):
        """pipe=2×expert=2×data=2 1F1B loss AND grads == the sequential reference
        with identical rng folds (incl. the RSample gating noise)."""
        cfg = GPT2MoEConfig(**TINY)
        mod = gpt2_moe_pipeline_module(cfg, num_stages=2, sample_seq_len=32)
        params = mod.init_fn(jax.random.PRNGKey(0))
        batch = _batch()
        rng = jax.random.PRNGKey(7)

        mesh = MeshSpec({"pipe": 2, "expert": 2, "data": 2}, eight_devices)
        set_global_mesh(mesh)
        try:
            fn_pipe = mod.make_1f1b_loss_fn(mesh,
                                            aux_loss_coef=cfg.moe_loss_coef)
            loss_p, grads_p = jax.jit(jax.value_and_grad(fn_pipe))(params, batch,
                                                                   rng)
            fn_seq = _sequential_loss(mod, cfg.moe_loss_coef)
            loss_s, grads_s = jax.jit(jax.value_and_grad(fn_seq))(params, batch,
                                                                  rng)
            np.testing.assert_allclose(float(loss_p), float(loss_s), rtol=1e-5)
            assert float(loss_p) > 0
            flat_s = jax.tree_util.tree_leaves_with_path(grads_s)
            flat_p = dict(jax.tree_util.tree_leaves_with_path(grads_p))
            for path, g_s in flat_s:
                np.testing.assert_allclose(
                    np.asarray(flat_p[path]), np.asarray(g_s), rtol=2e-4,
                    atol=2e-5, err_msg=jax.tree_util.keystr(path))
            # the aux loss is live: gate gradients are not identically zero
            gate_g = [g for path, g in flat_s
                      if "gate_wg" in jax.tree_util.keystr(path)]
            assert gate_g and any(float(jnp.abs(g).max()) > 0 for g in gate_g)
        finally:
            set_global_mesh(None)

    def test_engine_pipe_expert_data(self, eight_devices):
        """Full composition: pipe=2 × expert=2 × data=2 through the engine; expert
        weights physically sharded over the expert axis; loss decreases."""
        cfg = GPT2MoEConfig(**TINY)
        mod = gpt2_moe_pipeline_module(cfg, num_stages=2, sample_seq_len=32)
        config = {
            "train_batch_size": 8,
            "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 2,
            "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 0},
            "mesh": {"pipe": 2, "expert": 2, "data": 2},
            "steps_per_print": 10**9,
        }
        eng, *_ = ds.initialize(model=mod, config=config)
        w1 = eng.state.params["body"]["moe"]["moe"]["experts"]["w1"]
        assert "expert" in tuple(jax.tree_util.tree_leaves(
            [w1.sharding.spec], is_leaf=lambda x: isinstance(x, P))[0]), \
            w1.sharding.spec
        b = _batch(seed=0)
        flat = {"inputs": b["inputs"].reshape(-1, 32),
                "labels": b["labels"].reshape(-1, 32)}
        losses = [float(eng.train_batch(batch=flat)) for _ in range(5)]
        assert losses[-1] < losses[0]
        assert all(np.isfinite(losses))

    def test_gpipe_schedule_rejected(self):
        """Aux-loss body layers are 1F1B-only — fill-drain would drop the aux."""
        cfg = GPT2MoEConfig(**TINY)
        mod = gpt2_moe_pipeline_module(cfg, num_stages=2, sample_seq_len=32)
        with pytest.raises(NotImplementedError, match="1F1B|1f1b"):
            mod.to_model(schedule="gpipe")
