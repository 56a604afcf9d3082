"""Phi-4-mini-flash (SambaY) on the normal serving path at a tiny width, the
system against ``benchmarks/chipbench/reference/phi4flash.py`` (plain float32,
every layer at every position, no cache, no ring): the whole forward; prefill
then decode through scheduler, pool, ring and pages with prompts shorter and
longer than the window and a generation that wraps the ring twice; the
prefill that stops early; the paired-row form of differential attention
against the family's four-call form; the selective scan and its one-token
step against the plain recurrence; the flash kernel under a window; the
family constructor's pattern and refusals; the parameter count; slot reuse;
and the planted faults that the benchmark's limits are set against."""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import phi4flash_tiny as pt  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
PUBLISHED = dict(hidden_size=2560, num_hidden_layers=32, vocab_size=200064,
                 num_attention_heads=40, num_key_value_heads=20,
                 intermediate_size=10240, sliding_window=512)


def _reference():
    path = os.path.join(REPO, "benchmarks", "chipbench", "reference", "phi4flash.py")
    spec = importlib.util.spec_from_file_location("chipbench_reference_phi4flash", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _ids(n, seed=0, vocab=512):
    return np.random.default_rng(seed).integers(1, vocab, n).astype(np.int32)


def _engine(dtype="float32", cap=96, **over):
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    return InferenceEngine(pt.tiny_cfg(max_seq_len=cap, **over), DeepSpeedInferenceConfig(
        dtype=dtype, max_out_tokens=cap), seed=5)


@pytest.fixture(scope="module")
def engine():
    return _engine()


def _ref_logits(params, ids, at, **model):
    return REF.next_token_logits(params, {**pt.TINY, **model}, ids, at, vocab_block=256,
                                 pad_to=8)


# ------------------------------------------------------------------- the model
def test_the_forward_agrees_with_the_reference_logit_by_logit(engine):
    ids = _ids(40)
    got = np.asarray(engine.forward(ids[None])[0])
    want = _ref_logits(engine.params, ids, np.arange(40))
    assert float(want.std()) > 0.05
    assert np.abs(got - want).max() < 2e-5
    assert engine.model_config.num_params() == sum(
        a.size for a in jax.tree_util.tree_leaves(engine.params))


def test_the_published_model_has_the_issues_parameters():
    from deepspeed_tpu.models.causal_lm import phi4flash_cfg
    cfg = phi4flash_cfg(max_seq_len=6144, **PUBLISHED)
    assert cfg.num_params() == 3_852_562_944
    assert cfg.layer_pattern == "SFWF" * 8 + "SF*F" + "GFXF" * 7
    assert cfg.layer_keeps.count("kv") == 1 and cfg.layer_keeps.count("state") == 17
    assert cfg.slot_state_layers == ("selective-state-space", "window-attention")
    assert not cfg.kv_every_layer and cfg.prefill_stop == 34
    assert (cfg.mamba1_d_inner, cfg.mamba1_dt_rank, cfg.ssm_state_size,
            cfg.conv_kernel) == (5120, 160, 16, 4)
    assert cfg.cache_row_heads == 2 and cfg.pos_emb == "none"
    assert [cfg.mixer_depth(i) for i in (0, 2, 34, 62)] == [0, 1, 17, 31]


def test_the_builder_lays_eight_layers_out_with_every_kind():
    cfg = pt.tiny_cfg()
    assert cfg.layer_pattern == "SFWFSFWFSF*FGFXF" and cfg.prefill_stop == 10
    assert REF.layer_kinds(pt.TINY) == ["mamba", "window", "mamba", "window", "mamba",
                                        "full", "memory", "cross"]


@pytest.mark.parametrize("key,value", [
    ("mb_per_layer", 1), ("embd_pdrop", 0.1), ("resid_pdrop", 0.1),
    ("hidden_act", "gelu"), ("mlp_bias", True), ("lm_head_bias", True),
    ("mamba_proj_bias", True), ("mamba_conv_bias", False), ("num_hidden_layers", 6)])
def test_the_builder_refuses_by_name_what_it_does_not_build(key, value):
    with pytest.raises(NotImplementedError, match=key.split("_")[0]):
        pt.tiny_cfg(**{key: value})


@pytest.mark.parametrize("pattern,match", [
    ("SFXF", "cross-attention"), ("WF*FGF", "gated-memory")])
def test_a_pattern_whose_reader_has_nothing_to_read_is_refused(pattern, match):
    import dataclasses
    cfg = pt.tiny_cfg()
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(cfg, layer_pattern=pattern, n_layer=len(pattern))


# ------------------------------------------- prefill, ring, pages, the scheduler
def _served(eng, prompts, new, slots=2):
    from deepspeed_tpu.inference.serving.prefix_cache import PrefixCacheConfig
    from deepspeed_tpu.inference.serving.scheduler import (
        ContinuousBatchingScheduler, ServingConfig)
    sched = ContinuousBatchingScheduler(eng, ServingConfig(
        slots=slots, chunk_size=4, max_seq_len=96, max_queue=8, kv_page_size=8,
        prefix_cache=PrefixCacheConfig(enabled=False)))
    handles = [sched.submit(p, max_new_tokens=n) for p, n in zip(prompts, new)]
    sched.run()
    return sched, [list(h.tokens) for h in handles]


def test_serving_through_ring_and_pages_is_the_references_forward(engine):
    """Prompts shorter (5) and longer (40, 21) than the window of 8, three
    requests through two slots, 20 tokens each: the ring wraps twice and a
    slot is reused. Every served token is the argmax of the reference's full
    forward of the sequence as served."""
    prompts = [_ids(n, seed=n) for n in (5, 40, 21)]
    sched, tokens = _served(engine, prompts, [20, 20, 20])
    pool = sched.executor.pool
    assert pool.ring_nbytes == 2 * 2 * (2 * 1 * 8 * 32 * 4) and pool.kv_layers == 1
    assert pool.stats()["ring_bytes"] == pool.ring_nbytes < pool.state_nbytes
    for p, served in zip(prompts, tokens):
        assert len(served) == 20
        ids = np.concatenate([p, np.asarray(served[:-1], np.int32)])
        want = _ref_logits(engine.params, ids, np.arange(p.size - 1, ids.size))
        short = want.max(-1) - want[np.arange(20), served]
        assert short.max() < 1e-5, (p.size, short.max())
        alone = engine.generate(p[None], max_new_tokens=20)[0, p.size:]
        assert served == [int(t) for t in alone]


def test_a_released_slots_next_sequence_reads_nothing_of_the_last(engine):
    a, b = _ids(33, seed=1), _ids(6, seed=2)
    _, after = _served(engine, [a, b], [9, 12], slots=1)    # b takes a's slot
    _, fresh = _served(engine, [b], [12], slots=1)
    assert after[1] == fresh[0]


def test_the_stopped_prefill_gives_the_forwards_last_position(engine):
    """``build_prefill`` runs the layers after the one full layer at the last
    valid position alone; its logits are the all-positions forward's there,
    under right padding too, and what it leaves (ring, pages, state) decodes
    to the forward's next rows."""
    from deepspeed_tpu.inference.decode_fns import build_prefill
    from deepspeed_tpu.models.causal_lm import init_cache
    cfg, module = engine.model_config, engine.module
    ids = np.stack([_ids(40, seed=3), _ids(40, seed=4)])
    lens = jnp.asarray([40, 23])
    full = np.asarray(engine.forward(ids))
    prefill = jax.jit(build_prefill(module, lambda p: p))
    logits, caches = prefill(engine.params, jnp.asarray(ids), init_cache(cfg, 2, 96), lens)
    assert np.abs(np.asarray(logits[0]) - full[0, 39]).max() < 2e-5
    assert np.abs(np.asarray(logits[1]) - full[1, 22]).max() < 2e-5
    text = jax.jit(build_prefill(module, lambda p: p)).lower(
        engine.params, jnp.asarray(ids), init_cache(cfg, 2, 96), lens).as_text()
    # the memory units' and the cross layers' matmuls have ONE row a sequence
    assert "tensor<2x1x128xf32>" in text and "tensor<2x40x128xf32>" in text
    step = jax.jit(lambda v, t, c, n: module.apply(v, t, positions=n[:, None], caches=c,
                                                   cache_lens=n))
    cur = lens
    for i in range(17):                                  # the ring of 8 wraps twice
        tok = jnp.asarray(np.stack([ids[0, :1], ids[1, 23 + i:24 + i]]))
        out, caches = step({"params": engine.params}, tok, caches, cur)
        assert np.abs(np.asarray(out[1, 0]) - full[1, 23 + i]).max() < 2e-5, i
        cur = cur + 1


def test_the_paired_row_form_is_the_familys_four_call_form():
    """``a1 = [attn(q1, k1, v1) ; attn(q1, k1, v2)]``, ``a2 = [attn(q2, k2, v1) ;
    attn(q2, k2, v2)]`` (the family's code: four flash calls a layer) against
    two query heads of 2 d lanes over one row ``[k1 ; k2]`` / ``[v1 ; v2]``."""
    from deepspeed_tpu.models.causal_lm import _band_attention, pair_queries
    from deepspeed_tpu.ops.transformer.attention import xla_attention
    b, t, H, hk, d = 2, 24, 8, 4, 16
    key = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(key[0], (b, t, H, d))
    k = jax.random.normal(key[1], (b, t, hk, d))
    v = jax.random.normal(key[2], (b, t, hk, d))
    got = _band_attention(pair_queries(q), k.reshape(b, t, hk // 2, 2 * d),
                          v.reshape(b, t, hk // 2, 2 * d), None, d ** -0.5)
    got = got.reshape(b, t, H // 2, 2, 2 * d)
    q1, q2 = q[:, :, 0::2], q[:, :, 1::2]                 # (b, t, H/2, d)
    g = (H // 2) // (hk // 2)
    k1, k2 = (jnp.repeat(x, g, axis=2) for x in (k[:, :, 0::2], k[:, :, 1::2]))
    v1, v2 = (jnp.repeat(x, g, axis=2) for x in (v[:, :, 0::2], v[:, :, 1::2]))

    def call(qq, kk, vv):
        return xla_attention(qq, kk, vv, causal=True, softmax_scale=d ** -0.5)

    a1 = jnp.concatenate([call(q1, k1, v1), call(q1, k1, v2)], axis=-1)
    a2 = jnp.concatenate([call(q2, k2, v1), call(q2, k2, v2)], axis=-1)
    assert float(jnp.abs(got[:, :, :, 0] - a1).max()) < 1e-5
    assert float(jnp.abs(got[:, :, :, 1] - a2).max()) < 1e-5


# ------------------------------------------------------------- the selective scan
def _scan_inputs(b, t, c, n, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (b, t, c))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, t, c)) - 2.0)
    A = -jnp.exp(0.5 * jax.random.normal(k[2], (n, c)))
    return x, dt, A, jax.random.normal(k[3], (b, t, n)), jax.random.normal(k[4], (b, t, n)), \
        jnp.linspace(0.5, 1.5, c)


@pytest.mark.parametrize("b,t,c,n", [(2, 37, 256, 4), (1, 300, 1024, 16), (1, 16, 128, 4)])
def test_the_scan_kernel_and_its_step_are_the_plain_recurrence(b, t, c, n):
    from deepspeed_tpu.ops.ssm import (selective_scan, selective_scan_xla, selective_step,
                                       selective_step_xla)
    x, dt, A, B, C, D = _scan_inputs(b, t, c, n)
    state, ys = jnp.zeros((b, n, c)), []
    for i in range(t):
        before = state
        new = jnp.exp(dt[:, i, None, :] * A) * state + B[:, i, :, None] * (
            dt[:, i] * x[:, i])[:, None, :]
        y, state = selective_step_xla(state, x[:, i], dt[:, i], A, B[:, i], C[:, i], D)
        assert float(jnp.abs(state - new).max()) < 1e-6
        if i % 16 == 0:                        # the kernel a sequence, against it
            yk, sk = selective_step(before, x[:, i], dt[:, i], A, B[:, i], C[:, i], D)
            assert float(jnp.abs(yk - y).max()) < 1e-5
            assert float(jnp.abs(sk - state).max()) < 1e-6
        ys.append(y)
    want = jnp.stack(ys, axis=1)
    for fn in (selective_scan, selective_scan_xla):
        y, last = fn(x, dt, A, B, C, D)
        assert float(jnp.abs(y - want).max()) < 2e-5 and y.shape == (b, t, c)
        assert float(jnp.abs(last - state).max()) < 2e-5


def test_padding_does_not_advance_the_scan():
    """``dt = 0`` behind a row's length: the state after the bucket is the
    state after the last valid token, and the valid rows' output is unmoved."""
    from deepspeed_tpu.ops.ssm import selective_scan
    x, dt, A, B, C, D = _scan_inputs(2, 48, 256, 4, seed=1)
    lens = np.asarray([48, 19])
    real = jnp.arange(48)[None, :] < jnp.asarray(lens)[:, None]
    y, state = selective_scan(x, jnp.where(real[..., None], dt, 0.0), A, B, C, D)
    y19, state19 = selective_scan(x[1:, :19], dt[1:, :19], A, B[1:, :19], C[1:, :19], D)
    assert float(jnp.abs(state[1] - state19[0]).max()) < 1e-6
    assert float(jnp.abs(y[1, :19] - y19[0]).max()) < 1e-6


# -------------------------------------------------------------- the windowed flash
@pytest.mark.parametrize("t,block,window", [
    (512, 128, 100), (512, 128, 128), (512, 128, 200), (256, 256, 64), (512, 256, 512),
    (1024, 128, 300)])
def test_flash_under_a_window_is_the_masked_softmax(t, block, window):
    from deepspeed_tpu.ops.attention.flash import flash_attention
    from deepspeed_tpu.ops.transformer.attention import xla_attention
    key = jax.random.split(jax.random.PRNGKey(t + window), 3)
    q, k, v = (jax.random.normal(kk, (1, t, 2, 128)) for kk in key)
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    want = xla_attention(q, k, v, causal=False, softmax_scale=0.125,
                         mask=jnp.asarray((j <= i) & (j > i - window))[None, None])
    got = flash_attention(q, k, v, causal=True, softmax_scale=0.125, block_q=block,
                          block_k=block, window=window)
    assert float(jnp.abs(got - want).max()) < 5e-6


def test_a_window_is_forward_only_and_none_changes_no_call():
    from deepspeed_tpu.ops.attention.flash import flash_attention
    q = jnp.ones((1, 256, 2, 128))
    with pytest.raises(NotImplementedError, match="forward only"):
        jax.grad(lambda x: flash_attention(x, q, q, window=64).sum())(q)
    with pytest.raises(NotImplementedError, match="window"):
        flash_attention(q, q, q, causal=False, window=64)
    with pytest.raises(NotImplementedError, match="window"):
        flash_attention(q, q, q, window=64, alibi_slopes=jnp.ones((2,)))

    def text(**kw):
        return jax.jit(lambda a: flash_attention(a, a, a, **kw)).lower(q).as_text()

    assert text() == text(window=None) and "window" not in text()


# ------------------------------------------------------------- the planted faults
@pytest.mark.parametrize("fault", ["ring_one_short", "lambda_of_next_layer",
                                   "keys_shifted_by_one", "memory_after_gate"])
def test_a_planted_fault_of_a_new_mechanism_moves_the_logits(engine, fault):
    """The controls of the benchmark's limits at a small width: the reference
    with ONE mechanism altered reads whole spreads off the program, where the
    true reference reads rounding."""
    ids = _ids(40, seed=9)
    got = np.asarray(engine.forward(ids[None])[0])
    ref = _reference()
    if fault == "ring_one_short":
        model = {"sliding_window": pt.TINY["sliding_window"] - 1}
    else:
        model = {}
    if fault == "lambda_of_next_layer":
        ref.lambda_init = lambda l, f=ref.lambda_init: f(l + 1)
    if fault == "keys_shifted_by_one":
        plain = ref.diff_attention

        def shifted(u, lp, kv, l, window, model):
            if window is None and "k_proj" not in lp:          # a cross layer
                kv = tuple(jnp.roll(x, 1, axis=0) for x in kv)
            return plain(u, lp, kv, l, window, model)
        ref.diff_attention = shifted
    if fault == "memory_after_gate":
        plain_mamba = ref.mamba

        def gated(u, p, model):
            out, y = plain_mamba(u, p, model)
            c = p["out_proj"].shape[0]
            return out, y * ref.silu((u @ p["in_proj"])[:, c:])
        ref.mamba = gated
    ref._mixer_jit = jax.jit(ref.mixer_layer, static_argnums=(2, 3, 4))
    want = ref.next_token_logits(engine.params, {**pt.TINY, **model}, ids, np.arange(40),
                                 vocab_block=256, pad_to=8)
    err = np.abs(got[-8:] - want[-8:]).max() / want[-8:].std(axis=-1).mean()
    assert err > 0.02, err


def test_a_projection_rounds_to_the_serving_type_by_an_op_the_compiler_keeps():
    """``mamba1.project``: float32 that holds bfloat16 values, the rounding a
    ``reduce_precision`` in the lowered step (an ``astype`` pair the TPU
    compiler may fuse away by the program around it: the serving chunk and
    ``generate``'s loop then round differently); float32 serving adds no op."""
    from deepspeed_tpu.models.mamba1 import project, serving_values
    key = jax.random.split(jax.random.PRNGKey(3), 2)
    a, w = jax.random.normal(key[0], (4, 64)), jax.random.normal(key[1], (64, 32))
    got = project(a, w, jnp.bfloat16)
    assert got.dtype == jnp.float32
    assert bool(jnp.all(got == got.astype(jnp.bfloat16).astype(jnp.float32)))
    plain = (a.astype(jnp.bfloat16) @ w.astype(jnp.bfloat16)).astype(jnp.float32)
    assert float(jnp.abs(got - plain).max()) <= float(jnp.abs(plain).max()) * 2 ** -7
    text = jax.jit(lambda a, w: project(a, w, jnp.bfloat16)).lower(a, w).as_text()
    assert "reduce_precision" in text
    assert "reduce_precision" not in jax.jit(
        lambda a, w: project(a, w, jnp.float32)).lower(a, w).as_text()
    assert serving_values(a, jnp.float32) is a
