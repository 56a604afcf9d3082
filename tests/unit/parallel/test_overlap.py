"""Comm-compute overlap parity suite (8-virtual-CPU-device mesh).

Pins the contract of ``parallel/overlap.py``: the decomposed (chunked,
ppermute-ring) collective matmuls agree with their monolithic forms —
bit-exact for allgather-matmul (row blocks are independent matmuls over
unchanged operands), last-ulp for matmul-reduce-scatter (cross-shard fp
summation order differs; fp32 tolerance documented at 1e-5) — and the int8
blockwise quantized allreduce (EQuARX-style) preserves convergence through
error feedback. Runs inside the tier-1 window (``comm_overlap`` marker,
hoisted by conftest collection ordering).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu as ds
from deepspeed_tpu.parallel import overlap as ov
from deepspeed_tpu.parallel.mesh import (AXIS_DATA, AXIS_EXPERT, AXIS_TENSOR,
                                         MeshSpec, set_global_mesh)
from deepspeed_tpu.utils.comms_logging import (collective_spans,
                                               spans_overlap_ratio,
                                               spans_total_bytes)
from deepspeed_tpu.utils.jax_compat import shard_map

pytestmark = pytest.mark.comm_overlap


@pytest.fixture(autouse=True)
def _reset_overlap():
    yield
    ov.set_overlap_config(None)


def _tp_mesh(tp, devices):
    return MeshSpec({"tensor": tp}, devices[:tp])


# ------------------------------------------------------------ ring primitives
@pytest.mark.parametrize("tp", [2, 4, 8])
@pytest.mark.parametrize("bidir", [False, True])
def test_chunked_allgather_matmul_bitwise(tp, bidir, eight_devices):
    mesh = _tp_mesh(tp, eight_devices)
    rng = np.random.default_rng(tp)
    # ragged-ish shapes: m_loc deliberately odd, n not a multiple of tp
    m_loc, k, n = 5, 24, 9 if tp != 8 else 11
    x = jnp.asarray(rng.standard_normal((tp * m_loc, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    specs = dict(mesh=mesh.mesh, axis_names={AXIS_TENSOR},
                 in_specs=(P(AXIS_TENSOR, None), P(None, None)),
                 out_specs=P(None, None), check_vma=False)
    chunked = jax.jit(shard_map(
        lambda a, b: ov.chunked_allgather_matmul(a, b, AXIS_TENSOR,
                                                 bidirectional=bidir), **specs))
    mono = jax.jit(shard_map(
        lambda a, b: ov.allgather_matmul_monolithic(a, b, AXIS_TENSOR), **specs))
    np.testing.assert_array_equal(np.asarray(chunked(x, w)),
                                  np.asarray(mono(x, w)))


@pytest.mark.parametrize("tp", [2, 4, 8])
@pytest.mark.parametrize("bidir", [False, True])
def test_chunked_matmul_reduce_scatter_parity(tp, bidir, eight_devices):
    mesh = _tp_mesh(tp, eight_devices)
    rng = np.random.default_rng(tp + 10)
    m, k, n = tp * 3, 24, 10     # n even for the bidirectional column split
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    specs = dict(mesh=mesh.mesh, axis_names={AXIS_TENSOR},
                 in_specs=(P(None, AXIS_TENSOR), P(AXIS_TENSOR, None)),
                 out_specs=P(AXIS_TENSOR, None), check_vma=False)
    chunked = jax.jit(shard_map(
        lambda a, b: ov.chunked_matmul_reduce_scatter(a, b, AXIS_TENSOR,
                                                      bidirectional=bidir),
        **specs))
    mono = jax.jit(shard_map(
        lambda a, b: ov.matmul_reduce_scatter_monolithic(a, b, AXIS_TENSOR),
        **specs))
    # cross-shard summation order differs from the monolithic psum: fp32
    # last-ulp tolerance (bit-exact is NOT promised for the scatter form)
    np.testing.assert_allclose(np.asarray(chunked(x, w)),
                               np.asarray(mono(x, w)), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(chunked(x, w)),
                               np.asarray(x) @ np.asarray(w),
                               rtol=1e-4, atol=1e-4)


# ------------------------------------------------- GSPMD row-parallel wrapper
@pytest.mark.parametrize("meshcfg,b,t", [
    ({"tensor": 4}, 3, 7),               # m=21 not divisible by tp → pad path
    ({"tensor": 8}, 2, 5),
    ({"data": 2, "tensor": 4}, 4, 6),    # TP×DP: kernel cotangent psum path
    ({"data": 2, "fsdp": 2, "tensor": 2}, 4, 3),
])
def test_row_parallel_dense_forward_and_grads(meshcfg, b, t, eight_devices):
    ndev = int(np.prod(list(meshcfg.values())))
    mesh = MeshSpec(meshcfg, eight_devices[:ndev])
    set_global_mesh(mesh)
    rng = np.random.default_rng(3)
    k, n = 16, 12
    x = jnp.asarray(rng.standard_normal((b, t, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal((n,)), jnp.float32)

    def loss_plain(x, w, bb):
        return jnp.sum((x @ w + bb) ** 2)

    def loss_ov(x, w, bb):
        return jnp.sum(ov.row_parallel_dense_apply(x, w, bb, jnp.float32) ** 2)

    ov.set_overlap_config(ov.OverlapConfig(enabled=True))
    lo, go = jax.jit(jax.value_and_grad(loss_ov, argnums=(0, 1, 2)))(x, w, bias)
    lp, gp = jax.jit(jax.value_and_grad(loss_plain,
                                        argnums=(0, 1, 2)))(x, w, bias)
    np.testing.assert_allclose(float(lo), float(lp), rtol=1e-5)
    for a, b_ in zip(go, gp):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-4)


def test_row_parallel_dense_small_batch_falls_back(eight_devices):
    """m < tp (single-token decode on a wide TP mesh) takes the monolithic
    path and stays correct."""
    mesh = MeshSpec({"tensor": 8}, eight_devices)
    set_global_mesh(mesh)
    ov.set_overlap_config(ov.OverlapConfig(enabled=True))
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((1, 1, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    y = jax.jit(lambda a, b: ov.row_parallel_dense_apply(
        a, b, None, jnp.float32))(x, w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x) @ np.asarray(w),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------- model-level parity
def test_decode_overlap_matches_monolithic_tp(eight_devices):
    """Greedy serving rollouts are identical with comm_overlap on/off at tp=4
    (the engine-level acceptance: overlapped and monolithic TP paths agree)."""
    from deepspeed_tpu.models import gpt2_cfg
    cfg_kw = dict(vocab_size=128, max_seq_len=64, n_embd=32, n_layer=2, n_head=4)
    ids = np.random.default_rng(5).integers(0, 128, size=(2, 8)).astype(np.int32)
    outs, spans = {}, {}
    for enabled in (False, True):
        engine = ds.init_inference(
            model=gpt2_cfg(**cfg_kw),
            config={"dtype": "float32", "max_out_tokens": 64,
                    "tensor_parallel": {"tp_size": 4},
                    "comm_overlap": {"enabled": enabled}})
        collective_spans.reset()
        outs[enabled] = engine.generate(ids, max_new_tokens=6)
        spans[enabled] = collective_spans.summary()
    np.testing.assert_array_equal(outs[False], outs[True])
    # each trace records what its collectives put on the wire, and only the
    # chunked rings count as overlap-scheduled
    for enabled in (False, True):
        assert spans_total_bytes(spans[enabled]) > 0
        assert (spans_overlap_ratio(spans[enabled]) > 0) == enabled


def test_moe_chunked_exchange_bitwise(eight_devices):
    """Capacity-chunked MoE dispatch/combine is bitwise-identical to the
    monolithic exchange on a 4-way expert mesh."""
    from deepspeed_tpu.moe.sharded_moe import moe_dispatch_combine, top1gating
    mesh = MeshSpec({"expert": 4}, eight_devices[:4])
    set_global_mesh(mesh)
    rng = np.random.default_rng(6)
    s, e, m = 32, 4, 16
    x = jnp.asarray(rng.standard_normal((s, m)), jnp.float32)
    logits = jnp.asarray(rng.standard_normal((s, e)), jnp.float32)
    _, combine, dispatch, _ = top1gating(logits, drop_tokens=False, use_rts=False)
    w = jnp.asarray(rng.standard_normal((e, m, m)), jnp.float32)

    def expert_fn(expert_in):
        return jnp.einsum("ecm,emf->ecf", expert_in, w)

    def run():
        return jax.jit(lambda xx: moe_dispatch_combine(
            xx, combine, dispatch, expert_fn))(x)

    ov.set_overlap_config(ov.OverlapConfig(enabled=False))
    base = np.asarray(run())
    ov.set_overlap_config(ov.OverlapConfig(enabled=True, moe_chunks=4))
    chunked = np.asarray(run())
    np.testing.assert_array_equal(base, chunked)


# ------------------------------------------------------ quantized collectives
def test_quantized_allreduce_error_feedback(eight_devices):
    from deepspeed_tpu.comm.compressed import quantized_allreduce
    mesh = MeshSpec({"data": 8}, eight_devices)
    W = 8
    rng = np.random.default_rng(7)
    xs = jnp.asarray(rng.standard_normal((W, 100)), jnp.float32) * 3.0
    err0 = jnp.zeros((W, 100), jnp.float32)

    fn = jax.jit(shard_map(
        lambda x, e: tuple(a[None] for a in
                           quantized_allreduce(x[0], e[0], AXIS_DATA, block=32)),
        mesh=mesh.mesh, axis_names={AXIS_DATA},
        in_specs=(P(AXIS_DATA, None), P(AXIS_DATA, None)),
        out_specs=(P(AXIS_DATA, None), P(AXIS_DATA, None)),
        check_vma=False))
    mean_q, err = fn(xs, err0)
    true_mean = np.asarray(xs).mean(axis=0)
    # every shard holds the same (replicated-by-construction) quantized mean
    mq = np.asarray(mean_q)
    for wq in range(1, W):
        np.testing.assert_array_equal(mq[0], mq[wq])
    # one-shot error bounded by half an int8 step of the largest block
    step = np.abs(np.asarray(xs)).max() / 127.0
    assert np.abs(mq[0] - true_mean).max() <= step

    # error feedback: repeated transmission of a CONSTANT signal accumulates to
    # the true mean — cumulative transmitted ≈ T * signal (1-bit Adam property,
    # shared EF contract with comm.compressed.sign_compress)
    T = 20
    acc = np.zeros(100, np.float32)
    err_t = err0
    for _ in range(T):
        mean_t, err_t = fn(xs, err_t)
        acc += np.asarray(mean_t)[0]
    np.testing.assert_allclose(acc / T, true_mean, atol=2 * step / T + 1e-6)


def _make_engine(quantized, devices, lr=1e-2):
    from deepspeed_tpu.models import GPT2Config, gpt2_model
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    set_global_mesh(None)
    cfg = GPT2Config(vocab_size=128, n_positions=32, n_embd=32, n_layer=2,
                     n_head=4, dropout=0.0, dtype=jnp.float32, scan_layers=True)
    model = gpt2_model(cfg, sample_seq_len=32)
    config = {
        "train_batch_size": 16, "gradient_accumulation_steps": 2,
        "optimizer": {"type": "Adam", "params": {"lr": lr}},
        "zero_optimization": {"stage": 0},
        "comm_overlap": {"enabled": True, "quantized_allreduce": quantized},
        "steps_per_print": 10**9,
    }
    return DeepSpeedEngine(model=model, config=config,
                           mesh_spec=MeshSpec({"data": 8}, devices))


def test_quantized_dp_convergence_smoke(eight_devices):
    """Tiny-model training with int8 EF gradient sync converges like fp32 DP:
    same first-step loss (grads quantize AFTER the loss), and the 8-step loss
    trajectory tracks the full-precision run closely."""
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 128, size=(16, 32), dtype=np.int32)}
    eng_q = _make_engine(True, eight_devices)
    assert eng_q._quantized_dp
    losses_q = [float(eng_q.train_batch(batch)) for _ in range(8)]
    eng_f = _make_engine(False, eight_devices)
    assert not eng_f._quantized_dp
    losses_f = [float(eng_f.train_batch(batch)) for _ in range(8)]
    assert losses_q[0] == pytest.approx(losses_f[0], rel=1e-5)
    assert losses_q[-1] < losses_q[0]                      # it learns
    # trajectory tracks fp32 within 10% of the total improvement
    drop = losses_f[0] - losses_f[-1]
    assert abs(losses_q[-1] - losses_f[-1]) < 0.1 * drop + 1e-3
    # grad norms comparable on the recorded last step
    assert eng_q.get_global_grad_norm() == pytest.approx(
        eng_f.get_global_grad_norm(), rel=0.2)


def test_quantized_dp_regime_gate(eight_devices):
    """Non-plain-DP configs refuse the quantized path loudly (warning) and
    keep the full-precision psum."""
    from deepspeed_tpu.models import GPT2Config, gpt2_model
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    set_global_mesh(None)
    cfg = GPT2Config(vocab_size=128, n_positions=32, n_embd=32, n_layer=2,
                     n_head=4, dropout=0.0, dtype=jnp.float32)
    model = gpt2_model(cfg, sample_seq_len=32)
    config = {
        "train_batch_size": 16, "gradient_accumulation_steps": 2,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1},     # ZeRO shards grads → blocked
        "comm_overlap": {"enabled": True, "quantized_allreduce": True},
        "steps_per_print": 10**9,
    }
    eng = DeepSpeedEngine(model=model, config=config,
                          mesh_spec=MeshSpec({"fsdp": 8}, eight_devices))
    assert not eng._quantized_dp


def test_overlap_config_validation():
    # chunk_bits lifted to {4, 8, 16} in PR 20 (the qring wire widths);
    # anything else is a validation ERROR, not a silent clamp
    for ok in (4, 8, 16):
        assert ov.OverlapConfig(chunk_bits=ok).chunk_bits == ok
    with pytest.raises(ValueError, match="chunk_bits"):
        ov.OverlapConfig(chunk_bits=5)
    with pytest.raises(ValueError, match="chunk_bits"):
        ov.OverlapConfig(chunk_bits=32)
    with pytest.raises(ValueError, match="quant_block"):
        ov.OverlapConfig(quant_block=7)
    with pytest.raises(ValueError, match="unknown comm_overlap keys"):
        ov.resolve_overlap_config({"enabled": True, "chunk_size": 2})
    cfg = ov.resolve_overlap_config({"enabled": True, "bidirectional": False})
    assert cfg.matmul_active and not cfg.quantized_allreduce
