"""The loop over GPT-2's layers is ONE traced body over ONE parameter stack,
lowered unrolled where no mesh axis shards a layer (``models/gpt2.py:
unroll_layer_loop``) and as a ``while`` where one does: the two lowerings are
the same function of the same tree, and the rule reads the mesh alone. Counts
and values only (a CPU run); what the unrolled program saves is a chip number
(PERF.md section 6, PR 54)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import gpt2
from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config, cross_entropy_loss
from deepspeed_tpu.observability import get_tracer
from deepspeed_tpu.parallel.mesh import MeshSpec, set_global_mesh

LAYERS, BATCH, SEQ, HEADS, WIDTH, VOCAB = 3, 4, 128, 2, 64, 128


def _model(attention_impl, dtype=jnp.float32, **options):
    return GPT2(GPT2Config(vocab_size=VOCAB, n_positions=SEQ, n_embd=WIDTH, n_layer=LAYERS,
                           n_head=HEADS, dtype=dtype, attention_impl=attention_impl, **options))


def _model_loss(attention_impl, dtype=jnp.float32, **options):
    model = _model(attention_impl, dtype, **options)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, VOCAB, (BATCH, SEQ)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)
    return (lambda p: cross_entropy_loss(model.apply(p, ids)[:, :-1], ids[:, 1:])), params


def _loops(loss, params):
    """``while`` ops in the lowered gradient. XLA attention: an interpreted
    kernel is a ``while`` over its grid here and a custom call on the chip."""
    return jax.jit(jax.grad(loss)).lower(params).as_text().count("stablehlo.while")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("options", [dict(remat=True, remat_policy="dots"), dict(remat=False)],
                         ids=["dots", "no-remat"])
def test_the_unrolled_and_the_looped_lowering_are_one_function_of_one_tree(
        options, dtype, monkeypatch):
    assert gpt2.unroll_layer_loop()                           # no mesh: unrolled
    loss, params = _model_loss("flash", dtype, **options)
    unrolled = jax.jit(jax.value_and_grad(loss))(params)
    assert _loops(*_model_loss("xla", dtype, **options)) == 0
    monkeypatch.setattr(gpt2, "unroll_layer_loop", lambda: False)
    looped_loss, looped_params = _model_loss("flash", dtype, **options)
    looped = jax.jit(jax.value_and_grad(looped_loss))(looped_params)
    assert _loops(*_model_loss("xla", dtype, **options)) == 2  # forward, backward
    stacked = jax.tree_util.tree_leaves(params["params"]["h"])
    assert len(stacked) == 12 and all(leaf.shape[0] == LAYERS for leaf in stacked)
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(looped_params)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(looped_params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    got, want = jax.tree_util.tree_leaves_with_path(unrolled), jax.tree_util.tree_leaves(looped)
    assert len(got) == 1 + 16                                  # the loss, a gradient a leaf
    for (path, a), b in zip(got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if dtype == jnp.float32:
            assert np.array_equal(a, b), jax.tree_util.keystr(path)
        else:       # XLA fuses straight-line bf16 code across a layer's edge: a few
            # roundings (2**-8 each) of the leaf's largest value move
            np.testing.assert_allclose(a, b, rtol=0, atol=2 ** -5 * np.abs(b).max(),
                                       err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("axes,layer_loop", [
    (None, "unrolled"), ({"data": 2}, "unrolled"), ({"fsdp": 2}, "scan"),
    ({"data": 2, "tensor": 2}, "scan"), ({"seq": 2}, "scan"), ({"expert": 2}, "scan"),
    ({"pipe": 2}, "scan")], ids=lambda v: v if isinstance(v, str) else "-".join(v or ["none"]))
def test_the_loop_is_unrolled_exactly_where_no_mesh_axis_shards_a_layer(
        eight_devices, axes, layer_loop):
    if axes is not None:
        set_global_mesh(MeshSpec(axes, devices=eight_devices[:int(np.prod(list(axes.values())))]))
    loss, params = _model_loss("xla", remat=True, remat_policy="dots")
    # the set-up phase a program is traced under says which way it went
    with get_tracer().phase("setup.build_train_step") as phase:
        loops = _loops(loss, params)
    assert phase.attrs == {"layer_loop": layer_loop, "layers": LAYERS}
    assert loops == (0 if layer_loop == "unrolled" else 2)
    assert gpt2.unroll_layer_loop() is (layer_loop == "unrolled")
    # an initialisation saves no activation: one body, whatever the mesh
    init = jax.make_jaxpr(_model("xla").init)(jax.random.PRNGKey(0),
                                              jnp.zeros((1, SEQ), jnp.int32))
    assert [eqn.params["unroll"] for eqn in init.jaxpr.eqns
            if eqn.primitive.name == "scan" and eqn.params["length"] == LAYERS] == [1]


@pytest.mark.parametrize("stage,layer_loop", [(0, "unrolled"), (3, "scan")],
                         ids=["zero-0-data-only", "zero-3-fsdp"])
def test_the_engines_first_step_says_which_way_its_program_went(stage, layer_loop):
    """ZeRO folds the data axis into ``fsdp``, which shards a layer: the same
    devices unroll under stage 0 and loop under stage 3, and the phase the
    engine already keeps around the step's first call carries it (tracer off,
    as set-up runs). An initialisation is one body either way and says nothing."""
    import deepspeed_tpu as ds
    tracer = get_tracer()
    seen = len(tracer.phases)
    model = gpt2.gpt2_model(GPT2Config(vocab_size=VOCAB, n_positions=32, n_embd=32, n_layer=2,
                                       n_head=2), sample_seq_len=32)
    engine = ds.initialize(model=model, config={
        "train_batch_size": 16, "zero_optimization": {"stage": stage},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}})[0]
    ids = np.random.default_rng(0).integers(0, VOCAB, size=(16, 32), dtype=np.int32)
    assert np.isfinite(float(engine.train_batch(batch={"input_ids": ids})))
    phases = {p["name"]: p["attrs"] for p in tracer.phases[seen:]}
    assert phases["setup.build_train_step"] == {"layer_loop": layer_loop, "layers": 2}
    assert phases["setup.engine_init"] == phases["setup.init_params"] == {}
