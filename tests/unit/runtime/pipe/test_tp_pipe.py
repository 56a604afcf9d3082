"""Body tensor-parallelism inside the SPMD 1F1B pipeline.

The manual-collective stage_fn (``models.gpt2.block_tp_apply``) lets
pipe×tensor shard body weights physically instead of replicating them — the reference's
3D parallelism with TP inside pipeline stages (``deepspeed/runtime/pipe/topology.py:243``).
These tests pin: exact grad equality against the replicated run, physical sharding of
the body weights over the tensor axis, and the full pipe×tensor×fsdp engine composition.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import deepspeed_tpu as ds
from deepspeed_tpu.models.gpt2 import GPT2Config, block_tp_apply
from deepspeed_tpu.models.gpt2_pipe import gpt2_pipeline_module
from deepspeed_tpu.parallel.mesh import MeshSpec
from deepspeed_tpu.utils.jax_compat import shard_map

TINY = dict(vocab_size=64, n_positions=32, n_embd=32, n_head=4, n_layer=4,
            dropout=0.0, dtype=jnp.float32, split_qkv=True, remat=False,
            scan_layers=False)


def _batch(M=4, mb=2, t=32, seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, 64, size=(M, mb, t)).astype(np.int32)
    labels = np.concatenate([ids[:, :, 1:], np.full((M, mb, 1), -100, np.int32)],
                            axis=2)
    return {"inputs": ids, "labels": labels}


def _place(params, specs, mesh):
    return jax.tree_util.tree_map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh.mesh, s)), params, specs)


class TestTPBlock:
    def test_tp1_matches_flax_block(self):
        """block_tp_apply at tp=1 reproduces the flax Block exactly (same params)."""
        from deepspeed_tpu.runtime.pipe.module import FlaxPipeLayer
        from deepspeed_tpu.models.gpt2 import Block
        cfg = GPT2Config(**TINY)
        layer = FlaxPipeLayer(Block(cfg), deterministic_kwarg=True)
        x = jnp.asarray(np.random.RandomState(0).standard_normal((2, 32, 32)),
                        jnp.float32)
        p = layer.init(jax.random.PRNGKey(0), x)
        ref = layer.apply(p, x)
        # tp=1 manual apply outside any mesh: psum over a 1-sized axis via shard_map
        mesh = MeshSpec({"tensor": 1}, jax.devices()[:1])
        fn = block_tp_apply(cfg, 1, "tensor")
        got = jax.jit(shard_map(lambda pp, xx: fn(pp, xx), mesh=mesh.mesh,
                                    axis_names={"tensor"}, in_specs=(P(), P()),
                                    out_specs=P(), check_vma=False))(p, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestTP1F1B:
    def test_grads_match_replicated(self, eight_devices):
        """pipe=2×tensor=2 1F1B == pipe=2 replicated 1F1B: same loss, same grads,
        body weights PHYSICALLY sharded over tensor."""
        cfg = GPT2Config(**TINY)
        mod = gpt2_pipeline_module(cfg, num_stages=2, sample_seq_len=32)
        params = mod.init_fn(jax.random.PRNGKey(0))
        batch = _batch()
        rng = jax.random.PRNGKey(7)

        mesh_ref = MeshSpec({"pipe": 2}, eight_devices[:2])
        fn_ref = mod.make_1f1b_loss_fn(mesh_ref)
        loss_ref, grads_ref = jax.jit(jax.value_and_grad(fn_ref))(params, batch, rng)

        mesh_tp = MeshSpec({"pipe": 2, "tensor": 2}, eight_devices[:4])
        specs = mod.param_specs(tp_axis="tensor", tp_size=2)
        placed = _place(params, specs, mesh_tp)
        # physical sharding proof: column kernel last dim / row kernel first weight
        # dim carry the tensor axis
        q_kernel = placed["body"]["q_attn"]["kernel"]
        assert q_kernel.sharding.spec == P("pipe", None, "tensor")
        row_kernel = placed["body"]["c_proj"]["kernel"]
        assert row_kernel.sharding.spec == P("pipe", "tensor", None)
        fn_tp = mod.make_1f1b_loss_fn(mesh_tp, tp_axis="tensor")
        loss_tp, grads_tp = jax.jit(jax.value_and_grad(fn_tp))(placed, batch, rng)

        np.testing.assert_allclose(float(loss_tp), float(loss_ref), rtol=1e-5)
        flat_ref = jax.tree_util.tree_leaves_with_path(grads_ref)
        flat_tp = dict(jax.tree_util.tree_leaves_with_path(grads_tp))
        for path, g_ref in flat_ref:
            g_tp = flat_tp[path]
            np.testing.assert_allclose(
                np.asarray(g_tp), np.asarray(g_ref), rtol=2e-4, atol=2e-5,
                err_msg=jax.tree_util.keystr(path))

    def test_engine_pipe_tensor_fsdp(self, eight_devices):
        """Full 3D: pipe=2 × tensor=2 × fsdp=2 engine run matches the pipe×data
        run batch-for-batch, with body params sharded over tensor."""
        cfg = GPT2Config(**TINY)
        batches = [_batch(seed=s) for s in range(3)]

        def run(mesh_axes, gas):
            mod = gpt2_pipeline_module(cfg, num_stages=2, sample_seq_len=32)
            config = {
                "train_batch_size": 8,
                "train_micro_batch_size_per_gpu": 1,
                "gradient_accumulation_steps": gas,
                "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 0},
                "mesh": mesh_axes,
                "steps_per_print": 10**9,
            }
            eng, *_ = ds.initialize(model=mod, config=config)
            losses = []
            for b in batches:
                # 1f1b loss consumes pre-microbatched (M, mb, ...) trees directly
                flat = {"inputs": b["inputs"].reshape(-1, 32),
                        "labels": b["labels"].reshape(-1, 32)}
                losses.append(float(eng.train_batch(batch=flat)))
            return eng, losses

        eng_tp, got = run({"pipe": 2, "tensor": 2, "fsdp": 2}, gas=4)
        spec = eng_tp.state.params["body"]["q_attn"]["kernel"].sharding.spec
        assert "tensor" in tuple(spec), spec
        _, ref = run({"pipe": 2, "data": 4}, gas=2)
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)
        assert got[-1] < got[0]


class TestVocabChunkPipe:
    def test_chunked_tail_matches_full(self, eight_devices):
        """GPT2Config(vocab_chunk=N) in the PIPELINE: the tied head passes
        (hidden, wte) through and the loss runs the online-logsumexp CE — loss
        and grads equal the full-logits pipeline (no (b, t, V) buffer on the
        last stage)."""
        import numpy as np
        batch_cfg = dict(TINY)
        results = {}
        for chunk in (0, 16):
            cfg = GPT2Config(**batch_cfg, vocab_chunk=chunk)
            mod = gpt2_pipeline_module(cfg, num_stages=2, sample_seq_len=32)
            params = mod.init_fn(jax.random.PRNGKey(0))
            batch = _batch()
            mesh = MeshSpec({"pipe": 2}, eight_devices[:2])
            fn = mod.make_1f1b_loss_fn(mesh)
            loss, grads = jax.jit(jax.value_and_grad(fn))(
                params, batch, jax.random.PRNGKey(7))
            results[chunk] = (float(loss),
                              jax.tree_util.tree_map(np.asarray, grads))
        np.testing.assert_allclose(results[16][0], results[0][0], rtol=1e-5)
        flat_c = dict(jax.tree_util.tree_leaves_with_path(results[16][1]))
        for path, g in jax.tree_util.tree_leaves_with_path(results[0][1]):
            np.testing.assert_allclose(flat_c[path], g, rtol=2e-4, atol=2e-5,
                                       err_msg=jax.tree_util.keystr(path))

    def test_chunked_apply_fn_keeps_logits_contract(self, eight_devices):
        """apply_fn returns (b, t, V) logits even in chunked mode (the head's
        (hidden, wte) payload is an internal loss detail)."""
        import numpy as np
        cfg = GPT2Config(**TINY, vocab_chunk=16)
        mod = gpt2_pipeline_module(cfg, num_stages=2, sample_seq_len=32)
        from deepspeed_tpu.parallel.mesh import set_global_mesh
        set_global_mesh(MeshSpec({"pipe": 2}, eight_devices[:2]))
        try:
            model = mod.to_model()
            params = mod.init_fn(jax.random.PRNGKey(0))
            ids = np.random.RandomState(0).randint(0, 64, size=(2, 32)
                                                   ).astype(np.int32)
            out = model.apply_fn(params, {"inputs": ids, "labels": ids})
            assert out.shape == (2, 32, 64), out.shape
        finally:
            set_global_mesh(None)
