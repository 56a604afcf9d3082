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
                                               FLASH_QKV_NAME,
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


# ------------------------------------------------------------- no copies around the kernels
def _wide_model_loss(**options):
    """Two heads of 64: the shape whose heads the kernels read in place, two a block."""
    cfg = GPT2Config(vocab_size=VOCAB, n_positions=SEQ, n_embd=128, n_layer=LAYERS,
                     n_head=2, dtype=jnp.float32, attention_impl="flash", remat=True,
                     remat_policy="dots", **options)
    model = GPT2(cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, VOCAB, (BATCH, SEQ)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)
    return (lambda p: cross_entropy_loss(model.apply(p, ids)[:, :-1], ids[:, 1:])), params


def _moves_heads(eqn):
    """A transpose of a 4-D array: heads moved around the kernels."""
    if eqn.primitive.name == "transpose" and eqn.invars[0].aval.ndim == 4:
        return "transpose4"
    return None


def _cuts_the_projection(eqn):
    """A split, slice or dynamic_slice of a (.., 3*n_embd) array outside the
    kernels."""
    if eqn.primitive.name in ("split", "slice", "dynamic_slice") and \
            eqn.invars[0].aval.shape[-1:] == (3 * 128,):
        return eqn.primitive.name
    return None


@pytest.mark.parametrize("scan_layers", [True, False], ids=["scanned", "unrolled"])
def test_the_gradient_of_a_fused_layer_moves_no_head_and_cuts_no_projection(scan_layers):
    loss, params = _wide_model_loss(scan_layers=scan_layers)
    jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    bodies = 1 if scan_layers else LAYERS
    assert _count(jaxpr, _kernel) == {"flash_fwd": bodies, "flash_bwd_dq": bodies,
                                      "flash_bwd_dkv": bodies}
    assert _count(jaxpr, _tag) == {FLASH_OUT_NAME: bodies, FLASH_LSE_NAME: bodies,
                                   FLASH_QKV_NAME: bodies}
    assert not _count(jaxpr, _moves_heads)
    assert not _count(jaxpr, _cuts_the_projection)
    # dq | dk | dv go back to the projection as the sum of the three, each padded to
    # its lanes (what XLA fuses into the projection's backward: PR 56), and no
    # concatenate; the pads are the join's three and no slice's transpose
    joins = _count(jaxpr, lambda e: _wide(e, "pad") or _wide(e, "concatenate"))
    assert joins == {"pad": 3 * bodies}


def _makes_delta_in_xla(eqn):
    """``delta`` made outside the kernels: a product of two (b, t, h*d) arrays
    (``do * o``), any array with the heads apart (b, t, h, d), or a ``reduce_sum``
    over such an array's trailing axis."""
    flat, heads = (BATCH, SEQ, 128), (BATCH, SEQ, 2, 64)
    if eqn.primitive.name == "mul" and all(
            getattr(v.aval, "shape", None) == flat for v in eqn.invars):
        return "mul"
    if eqn.primitive.name == "reduce_sum" and eqn.invars[0].aval.shape == heads \
            and tuple(eqn.params["axes"]) == (3,):
        return "reduce_sum"
    if any(v.aval.shape == heads for v in eqn.outvars):
        return "heads apart"
    return None


@pytest.mark.parametrize("scan_layers", [True, False], ids=["scanned", "unrolled"])
def test_the_gradient_of_a_fused_layer_makes_delta_in_no_xla_op(scan_layers):
    """``flash_bwd_dq`` makes ``delta`` from ``do`` and the kept ``o`` and hands it to
    ``flash_bwd_dkv``. Outside the ``pallas_call``s (whose bodies work on 2-D tiles,
    which the matcher's shapes leave out) the gradient of the fused attention
    multiplies no two (b, t, h*d) arrays, and a whole layer's (whose norms do
    multiply such arrays) takes no head apart and sums over no head's lanes; the
    matcher finds all three in the XLA form of the same lines."""
    from deepspeed_tpu.ops.attention.flash import flash_attention_qkv
    x = jnp.zeros((BATCH, SEQ, 128), jnp.float32)
    qkv, w = jnp.zeros((BATCH, SEQ, 3 * 128), jnp.float32), jnp.zeros((128, 128))
    attention = jax.make_jaxpr(jax.grad(
        lambda qkv, w: (flash_attention_qkv(qkv, 2) @ w).sum()))(qkv, w)
    assert _count(attention, _kernel) == {"flash_fwd": 1, "flash_bwd_dq": 1,
                                          "flash_bwd_dkv": 1}
    assert not _count(attention, _makes_delta_in_xla)
    loss, params = _wide_model_loss(scan_layers=scan_layers)
    layer = _count(jax.make_jaxpr(jax.grad(loss))(params), _makes_delta_in_xla)
    assert set(layer) == {"mul"}

    def xla_delta(do, o):
        return jnp.sum((do * o).reshape(BATCH, SEQ, 2, 64), axis=-1)

    assert _count(jax.make_jaxpr(xla_delta)(x, x), _makes_delta_in_xla) == {
        "mul": 1, "reduce_sum": 1, "heads apart": 1}


def _wide(eqn, name):
    """An equation ``name`` whose result is as wide as the fused projection."""
    if eqn.primitive.name == name and eqn.outvars[0].aval.shape[-1:] == (3 * 128,):
        return name
    return None


@pytest.mark.parametrize("scan_layers", [True, False], ids=["scanned", "unrolled"])
def test_the_backward_of_a_fused_layer_adds_no_bias_and_multiplies_no_projection_again(
        scan_layers):
    """Under ``dots`` the kept residual is the projection the kernels READ (named
    ``flash_qkv``), bias and all: c_attn's bias is added once a layer, in the forward,
    and its matmul runs once; the bias's gradient is one ``reduce_sum`` a layer, c_attn's
    own."""
    loss, params = _wide_model_loss(scan_layers=scan_layers)
    jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    bodies = 1 if scan_layers else LAYERS
    # one add of the bias a layer, beside the two that join dq | dk | dv
    assert _count(jaxpr, lambda e: _wide(e, "add")) == {"add": 3 * bodies}
    # c_attn's forward product and no second one (its two gradients are 128 wide)
    assert _count(jaxpr, lambda e: _wide(e, "dot_general")) == {"dot_general": bodies}
    sums = _count(jaxpr, lambda e: "sum" if e.primitive.name == "reduce_sum"
                  and e.outvars[0].aval.shape == (3 * 128,) else None)
    assert sums == {"sum": bodies}


def test_the_fused_layer_saves_one_projection_a_layer_the_one_the_kernels_read():
    """51 residuals of the two unrolled layers: the 53 of PR 45's parent, of their
    shapes, less c_attn's bias a layer (nothing adds it again). The kept (b, t,
    3*n_embd) array was c_attn's matmul output; now it is the operand the kernels read,
    kept under its name, and no second one beside it."""
    loss, params = _wide_model_loss(scan_layers=False)
    saved = saved_residuals(loss, params)
    assert len(saved) == 51
    assert not [why for _, why in saved if "['c_attn']['bias']" in why]
    wide = [why for aval, why in saved if aval.shape == (BATCH, SEQ, 3 * 128)]
    assert len(wide) == LAYERS and all("flash_attention_qkv" in why for why in wide)
    by_shape = collections.Counter(aval.shape for aval, _ in saved)
    assert by_shape[(BATCH, SEQ, 128)] == 10 and by_shape[(BATCH, SEQ, 512)] == 2
    assert by_shape[(BATCH, 2, SEQ)] == LAYERS          # the log-sum-exp


def test_the_parameter_tree_is_the_one_nn_dense_made():
    """The fused branch holds c_attn as ``nn.Dense`` does everywhere else, so a
    checkpoint written by any branch loads into any other: the flash model's tree
    against the XLA attention path's, leaf for leaf, with initial values from a key."""
    from deepspeed_tpu.models.gpt2 import gpt2_model
    ids = jnp.zeros((BATCH, SEQ), jnp.int32)
    trees = []
    for impl in ("flash", "xla"):
        cfg = GPT2Config(vocab_size=VOCAB, n_positions=SEQ, n_embd=128, n_layer=LAYERS,
                         n_head=2, dtype=jnp.bfloat16, attention_impl=impl)
        trees.append(GPT2(cfg).init(jax.random.PRNGKey(7), ids))
    fused, dense = (jax.tree_util.tree_leaves_with_path(t) for t in trees)
    assert [jax.tree_util.keystr(k) for k, _ in fused] == \
        [jax.tree_util.keystr(k) for k, _ in dense]
    assert any("c_attn']['kernel" in jax.tree_util.keystr(k) for k, _ in fused)
    for (path, a), (_, b) in zip(fused, dense):
        assert a.shape == b.shape and a.dtype == b.dtype == jnp.float32
        assert np.array_equal(np.asarray(a), np.asarray(b)), jax.tree_util.keystr(path)
    assert sum(int(np.prod(a.shape)) for _, a in fused) == cfg.num_params()
    assert gpt2_model(cfg, sample_seq_len=SEQ) is not None


def test_the_gradient_of_separate_projections_moves_no_head_either():
    loss, params = _wide_model_loss(scan_layers=True, split_qkv=True)
    jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    assert _count(jaxpr, _kernel) == {"flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1}
    assert not _count(jaxpr, _moves_heads)


def test_heads_outside_lane_tiles_are_still_transposed_into_the_kernels():
    """The folded (b*h, t, d) call is the one place the transposes survive: the
    module's own model (two heads of 32) takes it, and splits its projection."""
    loss, params = _model_loss(remat=True, remat_policy="dots", scan_layers=True)
    jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    assert _count(jaxpr, _moves_heads)["transpose4"] >= 4


def test_the_fused_layer_gives_the_gradients_of_the_split_one():
    """Same parameters, c_attn read fused by the kernels or split for XLA attention:
    the same loss and gradients to float32 rounding."""
    loss, params = _wide_model_loss(scan_layers=True)
    cfg = GPT2Config(vocab_size=VOCAB, n_positions=SEQ, n_embd=128, n_layer=LAYERS,
                     n_head=2, dtype=jnp.float32, attention_impl="xla")
    model = GPT2(cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, VOCAB, (BATCH, SEQ)), jnp.int32)

    def plain(p):
        return cross_entropy_loss(model.apply(p, ids)[:, :-1], ids[:, 1:])

    np.testing.assert_allclose(float(loss(params)), float(plain(params)), rtol=1e-5)
    got, want = jax.grad(loss)(params), jax.grad(plain)(params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5,
                                   err_msg=jax.tree_util.keystr(path))
