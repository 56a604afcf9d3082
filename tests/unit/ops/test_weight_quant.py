"""Weight-streaming quantized decode: numerics, kernels, engine wiring.

Covers the ``weight_quant`` lane (ISSUE 5): int4 pack/unpack roundtrip,
fused-kernel vs XLA-dequant parity (interpret mode), TP=2 sharded quantized
projections on the virtual CPU mesh, greedy-token parity of quantized engines
vs fp, the quantize-time outlier audit and the loop-invariance HLO pin (no
dequant inside compiled decode bodies on the fallback path).
"""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.models import gpt2_cfg
from deepspeed_tpu.ops.quantizer import (dequantize_grouped, make_quant_node,
                                         pack_int4, quant_dense_apply,
                                         quantize_grouped, quantize_with_audit,
                                         quantized_matmul, quantized_matmul_xla,
                                         unpack_int4)

pytestmark = pytest.mark.weight_quant

TINY = dict(vocab_size=256, max_seq_len=64, n_embd=64, n_layer=2, n_head=4)


@pytest.fixture
def force_fused(monkeypatch):
    """Route engine/model paths through the fused (interpret-mode) kernels on
    the CPU backend."""
    monkeypatch.setenv("DS_TPU_WQ_FORCE_FUSED", "1")


def _tiny_engines(raw_mutator=None, **wq):
    cfg = gpt2_cfg(**TINY)
    e_fp = InferenceEngine(cfg, DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=64))
    raw = jax.tree_util.tree_map(np.asarray, e_fp.params)
    if raw_mutator is not None:
        raw_mutator(raw)
    e_q = InferenceEngine((cfg, raw), DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=64,
        weight_quant={"enabled": True, **wq}))
    return cfg, e_fp, e_q


# ------------------------------------------------------------ int4 packing
def test_int4_pack_unpack_roundtrip():
    rng = np.random.default_rng(0)
    for shape, groups in (((64, 16), 4), ((256, 8), 2), ((3, 32, 8), 4)):
        q = rng.integers(-7, 8, size=shape).astype(np.int8)
        packed = pack_int4(jnp.asarray(q), groups)
        assert packed.shape[-2] == shape[-2] // 2
        out = np.asarray(unpack_int4(packed, groups))
        np.testing.assert_array_equal(out, q)


def test_pack_int4_rejects_odd_group():
    q = jnp.zeros((6, 4), jnp.int8)
    with pytest.raises(ValueError, match="even"):
        pack_int4(q, 2)          # group size 3 — nibble halves can't split


def test_group_size_degradation_warns(caplog):
    """k prime: requested group silently degrading to per-element scales
    bloats the scale tensor — must warn (satellite 2)."""
    from deepspeed_tpu.utils.logging import logger as ds_logger
    w = np.random.default_rng(1).normal(size=(13, 8)).astype(np.float32)
    ds_logger.propagate = True        # the package logger is propagate=False
    try:
        with caplog.at_level(logging.WARNING):
            q, s = quantize_grouped(w, group_size=8, warn_for="test/w")
    finally:
        ds_logger.propagate = False
    assert s.shape[-2] == 13          # degraded to g=1
    assert any("effective group degraded to 1" in r.message
               for r in caplog.records)
    # the audit surfaces the effective group size
    node, info = quantize_with_audit(w, bits=8, group_size=8, threshold=0.5,
                                     name="test/w")
    assert info["group_effective"] == 1 and info["group_requested"] == 8


# ------------------------------------------------------------ kernel parity
@pytest.mark.parametrize("bits,group,shape", [
    (8, 128, (4, 256, 128)),
    (8, 64, (300, 256, 256)),        # prefill GEMM with padded m
    (4, 64, (4, 256, 128)),
    (4, 128, (64, 512, 256)),
])
def test_fused_kernel_matches_xla_dequant(bits, group, shape):
    """Interpret-mode Pallas kernel vs dequantize+XLA-matmul ground truth."""
    m, k, n = shape
    rng = np.random.default_rng(2)
    w = rng.standard_normal((k, n)).astype(np.float32)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    q, s = quantize_grouped(w, group, bits=bits)
    payload = pack_int4(q, s.shape[-2]) if bits == 4 else q
    y_fused = quantized_matmul(x, payload, s, bits=bits, interpret=True)
    y_xla = quantized_matmul_xla(x, payload, s, bits=bits)
    y_ref = x @ dequantize_grouped(q, s)
    np.testing.assert_allclose(np.asarray(y_fused), np.asarray(y_xla),
                               atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(y_xla), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-5)


def test_quant_dense_apply_fused_matches_fallback(force_fused):
    rng = np.random.default_rng(3)
    k, n = 128, 64
    w = rng.standard_normal((k, n)).astype(np.float32)
    x = jnp.asarray(rng.standard_normal((2, 5, k)), jnp.float32)
    for bits in (8, 4):
        q, s = quantize_grouped(w, 64, bits=bits)
        payload = pack_int4(q, s.shape[-2]) if bits == 4 else q
        node = make_quant_node(payload, s, bits)
        y = quant_dense_apply(x, node, None, jnp.float32)
        y_ref = x @ dequantize_grouped(q, s)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=1e-4, rtol=1e-5)


def test_tp2_sharded_quant_projection_parity(eight_devices, force_fused):
    """Column- and row-parallel fused projections shard-map over tensor=2 and
    match the unsharded kernel (satellite 3: TP=2 on the virtual CPU mesh)."""
    from deepspeed_tpu.parallel.mesh import MeshSpec, set_global_mesh
    rng = np.random.default_rng(4)
    k, n = 128, 64
    w = rng.standard_normal((k, n)).astype(np.float32)
    x = jnp.asarray(rng.standard_normal((2, 3, k)), jnp.float32)
    for bits in (8, 4):
        q, s = quantize_grouped(w, 32, bits=bits)
        payload = pack_int4(q, s.shape[-2]) if bits == 4 else q
        node = make_quant_node(payload, s, bits)
        set_global_mesh(None)
        y1 = {p: np.asarray(quant_dense_apply(x, node, None, jnp.float32,
                                              parallel=p))
              for p in ("column", "row")}
        set_global_mesh(MeshSpec({"tensor": 2}, eight_devices[:2]))
        for p in ("column", "row"):
            y2 = np.asarray(quant_dense_apply(x, node, None, jnp.float32,
                                              parallel=p))
            np.testing.assert_allclose(y2, y1[p], atol=2e-5, rtol=1e-5,
                                       err_msg=f"bits={bits} parallel={p}")


# ------------------------------------------------------------ engine wiring
def test_engine_greedy_parity_int8_int4(force_fused):
    """Greedy rollouts of the quantized engines match fp on the tiny model
    (fused kernels active end-to-end), and the int4 payload is packed."""
    cfg, e_fp, e8 = _tiny_engines(bits=8)
    raw = jax.tree_util.tree_map(np.asarray, e_fp.params)
    e4 = InferenceEngine((cfg, raw), DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=64,
        weight_quant={"enabled": True, "bits": 4, "group": 32}))
    q4 = e4.params["layers_0"]["q_proj"]["kernel"]
    assert "__int4_q__" in q4 and q4["__int4_q__"].shape[0] == TINY["n_embd"] // 2

    rng = np.random.default_rng(11)
    ids = rng.integers(0, cfg.vocab_size, size=(2, 8)).astype(np.int32)
    out_fp = e_fp.generate(ids, max_new_tokens=8)
    par8 = (e8.generate(ids, max_new_tokens=8)[:, 8:] == out_fp[:, 8:]).mean()
    par4 = (e4.generate(ids, max_new_tokens=8)[:, 8:] == out_fp[:, 8:]).mean()
    assert par8 >= 0.95, f"int8 greedy parity {par8}"
    assert par4 >= 0.75, f"int4 greedy parity {par4}"
    # lm_head / embeddings stay fp (plain leaves, not quant nodes)
    assert not isinstance(e8.params["wte"], dict)


def test_engine_audit_outlier_exclusion_and_config_exclude():
    """The quantize-time audit keeps outlier-heavy matrices in fp and honours
    ``weight_quant.exclude``; decisions land in ``engine.quant_audit``."""
    def spike(raw):
        kern = raw["layers_0"]["fc_in"]["kernel"].copy()
        kern[0, :16] = 1e4        # outliers wreck their groups' scale grids
        raw["layers_0"]["fc_in"]["kernel"] = kern

    _, _, e = _tiny_engines(raw_mutator=spike, bits=8,
                            exclude=["layers_1/o_proj"])
    by_name = {a["name"]: a for a in e.quant_audit}
    spiked = by_name["layers_0/fc_in/kernel"]
    assert spiked["decision"] == "excluded" and "outlier" in spiked["reason"]
    assert not isinstance(e.params["layers_0"]["fc_in"]["kernel"], dict)
    excl = by_name["layers_1/o_proj/kernel"]
    assert excl["decision"] == "excluded" and "exclude" in excl["reason"]
    assert isinstance(e.params["layers_0"]["q_proj"]["kernel"], dict)
    assert all("group_effective" in a for a in e.quant_audit
               if a["decision"] == "quantized")


@pytest.mark.parametrize("bits, reduction", [(8, 1.8), (4, 3.2)])
def test_engine_audit_monitor_events(bits, reduction):
    class FakeMonitor:
        enabled = True

        def __init__(self):
            self.events = []

        def write_events(self, evs):
            self.events += list(evs)

    _, _, e = _tiny_engines(bits=bits)
    mon = FakeMonitor()
    e.set_monitor(mon)
    tags = {t for t, _, _ in mon.events}
    assert {"inference/weight_quant/bits",
            "inference/weight_quant/matrices_quantized",
            "inference/weight_quant/reduction_vs_bf16"} <= tags
    rep = e.weight_stream_report()
    # modeled bytes a decode step streams, bf16 over quantized + scales
    assert rep["reduction_quantized_nodes"] > reduction
    assert rep["modeled_step_bytes"] > 0


def test_legacy_int8_resolves_to_weight_quant():
    """``dtype="int8"`` drives the same per-site path as ``weight_quant`` with
    8-bit defaults; lm_head is no longer quantized (stays fp with the
    embeddings)."""
    cfg = gpt2_cfg(**TINY)
    e = InferenceEngine(cfg, DeepSpeedInferenceConfig(
        dtype="int8", max_out_tokens=64))
    assert e._wq.enabled and e._wq.bits == 8
    assert isinstance(e.params["layers_0"]["q_proj"]["kernel"], dict)
    assert e.quant_audit and e._quantized


def test_moe_decode_ffn_quant_matches_xla():
    from deepspeed_tpu.ops.moe import moe_decode_ffn_quant, moe_decode_ffn_xla
    rng = np.random.default_rng(5)
    e, d, f, n_tok = 4, 32, 64, 6
    w1 = rng.standard_normal((e, d, f)).astype(np.float32)
    w2 = rng.standard_normal((e, f, d)).astype(np.float32)
    b1 = rng.standard_normal((e, f)).astype(np.float32)
    b2 = rng.standard_normal((e, d)).astype(np.float32)
    x = jnp.asarray(rng.standard_normal((n_tok, d)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, e, size=(n_tok,)), jnp.int32)
    act = jax.nn.gelu
    y_ref = moe_decode_ffn_xla(x, idx, jnp.asarray(w1), jnp.asarray(b1),
                               jnp.asarray(w2), jnp.asarray(b2), act)
    for bits in (8, 4):
        q1, s1 = quantize_grouped(w1, 16, bits=bits)
        q2, s2 = quantize_grouped(w2, 16, bits=bits)
        if bits == 4:
            q1, q2 = pack_int4(q1, s1.shape[-2]), pack_int4(q2, s2.shape[-2])
        n1, n2 = make_quant_node(q1, s1, bits), make_quant_node(q2, s2, bits)
        y = moe_decode_ffn_quant(x, idx, n1, jnp.asarray(b1), n2,
                                 jnp.asarray(b2), act)
        # quantized-weight FFN vs fp reference: bounded by quantization error
        rel = float(jnp.abs(y - y_ref).mean() / jnp.abs(y_ref).mean())
        assert rel < (0.02 if bits == 8 else 0.3), f"bits={bits} rel={rel}"
        # exactness of the gather path itself: vs dequantize-then-gather
        w1d = dequantize_grouped(unpack_int4(q1, s1.shape[-2])
                                 if bits == 4 else q1, s1)
        w2d = dequantize_grouped(unpack_int4(q2, s2.shape[-2])
                                 if bits == 4 else q2, s2)
        y_deq = moe_decode_ffn_xla(x, idx, w1d, jnp.asarray(b1), w2d,
                                   jnp.asarray(b2), act)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_deq),
                                   atol=1e-4, rtol=1e-4)


# ------------------------------------------------- loop-invariance HLO pin
def _decode_loop_hlo(engine, gen_cap=32):
    from deepspeed_tpu.inference.decode_fns import (build_decode_loop,
                                                    make_select_fn)
    from deepspeed_tpu.models.causal_lm import init_cache
    loop = build_decode_loop(engine.module, engine._dequant,
                             make_select_fn(False, 1.0, 0, 1.0), gen_cap,
                             overlap=engine.comm_overlap)
    caches = init_cache(engine.model_config, 2, gen_cap, dtype=engine.dtype)
    tok0 = jnp.zeros((2, 1), jnp.int32)
    lens = jnp.full((2,), 8, jnp.int32)
    return jax.jit(loop).lower(
        engine.params, tok0, caches, lens, np.int32(8), np.int32(-1),
        jax.random.PRNGKey(0)).compile().as_text()


def _while_body_dtypes(txt, needle="s8["):
    """Names of computations reachable from any while body/cond that contain
    ``needle`` (transitively through calls/fusions)."""
    import re
    blocks = dict(re.findall(r"^(%?[\w.\-]+) [^\n]*\{\n(.*?)^\}",
                             txt, re.M | re.S))
    roots = [n for pair in re.findall(
        r"body=(%?[\w.\-]+), condition=(%?[\w.\-]+)", txt) for n in pair]
    roots += [n for pair in re.findall(
        r"condition=(%?[\w.\-]+), body=(%?[\w.\-]+)", txt) for n in pair]
    assert roots, "no while loop found in HLO"
    seen, bad = set(), []
    while roots:
        name = roots.pop()
        if name in seen:
            continue
        seen.add(name)
        body = blocks.get(name) or blocks.get(name.lstrip("%")) or ""
        if needle in body:
            bad.append(name)
        roots += re.findall(
            r"(?:calls=|to_apply=|body=|condition=)(%?[\w.\-]+)", body)
    return bad


_INT8_INVAR = lambda aval: getattr(aval, "dtype", None) == jnp.int8  # noqa: E731


def test_no_dequant_inside_decode_loop_body():
    """Satellite 1 of ISSUE 5, re-pointed (ISSUE 11) at the shared
    ``analysis.assert_loop_invariant`` pass: on the XLA fallback path the
    dequant must be hoisted out of the compiled decode loop — int8 operands
    appear in the module (the params ARE int8) but never inside the loop
    body. Pinned at BOTH levels: the optimized HLO (what actually runs) and
    the jaxpr (the structural hoist in ``decode_fns`` — XLA's own LICM must
    not be what saves us)."""
    from deepspeed_tpu.analysis import (LoopInvarianceError,
                                        assert_loop_invariant)
    _, _, e = _tiny_engines(bits=8)
    txt = _decode_loop_hlo(e)
    assert "s8[" in txt, "quantized params not present at dispatch"
    assert _while_body_dtypes(txt) == []

    from deepspeed_tpu.inference.decode_fns import (build_decode_loop,
                                                    make_select_fn)
    from deepspeed_tpu.models.causal_lm import init_cache
    select = make_select_fn(False, 1.0, 0, 1.0)
    caches = init_cache(e.model_config, 2, 32, dtype=e.dtype)
    args = (e.params, jnp.zeros((2, 1), jnp.int32), caches,
            jnp.full((2,), 8, jnp.int32), np.int32(8), np.int32(-1),
            jax.random.PRNGKey(0))
    loop = build_decode_loop(e.module, e._dequant, select, 32,
                             overlap=e.comm_overlap)
    # require_loop (the default) guards the pin target itself: a refactor
    # that removes the while_loop raises instead of passing vacuously
    assert_loop_invariant(loop, args, invar_predicate=_INT8_INVAR,
                          what="dequant-hoist")
    # negative control: an identity `dequant` pushes the quant nodes into the
    # model, whose CPU fallback dequantizes per-site inside the traced body —
    # the structural inspection must catch that regression shape (XLA LICM
    # may still hoist it in the final HLO, which is why the jaxpr view is
    # the one that pins OUR hoist)
    bad_loop = build_decode_loop(e.module, lambda p: p, select, 32,
                                 overlap=e.comm_overlap)
    with pytest.raises(LoopInvarianceError, match="dequant-hoist"):
        assert_loop_invariant(bad_loop, args, invar_predicate=_INT8_INVAR,
                              what="dequant-hoist")
