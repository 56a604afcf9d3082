"""Granite 4.0's hybrid WITH experts (``granite_hybrid_cfg`` with
``num_local_experts`` > 0: after every mixer a softmax top-k of SwiGLU experts
BESIDE a gated shared expert under one norm, their sum under one residual
multiplier) at a small size on the CPU, seeded random weights, against the
plain float32 reference the benchmark keeps
(``benchmarks/chipbench/reference/granite_moe_hybrid.py``): the expert layer,
the whole forward logit by logit, prefill then decode through the pool and
the scheduler, the share test, planted faults, what the builder builds for
the family's other model and what it refuses, and the stand-in's routers."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.unit import granite_moe_tiny as gm
from tests.unit import granite_tiny as gt
from tests.unit.inference.test_hybrid_model import _served_logits

REF = gm.reference()
TOL = 1e-4          # float32 both sides, in spreads of the reference's logits (~0.3)
# the catalog row's ``config`` (model-configs guide, architectures.jsonl)
PUBLISHED = dict(
    attention_bias=False, attention_multiplier=0.0078125, embedding_multiplier=12,
    hidden_act="silu", hidden_size=4096, intermediate_size=768,
    layer_types=(["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    logits_scaling=16, mamba_chunk_size=256, mamba_conv_bias=True, mamba_d_conv=4,
    mamba_d_head=64, mamba_d_state=128, mamba_expand=2, mamba_n_groups=1,
    mamba_n_heads=128, mamba_proj_bias=False, max_position_embeddings=131072,
    model_type="granitemoehybrid", normalization_function="rmsnorm",
    num_attention_heads=32, num_experts_per_tok=10, num_hidden_layers=40,
    num_key_value_heads=8, num_local_experts=72, position_embedding_type="nope",
    residual_multiplier=0.22, rms_norm_eps=1e-05, rope_scaling=None, rope_theta=10000,
    shared_intermediate_size=1536, tie_word_embeddings=True, vocab_size=100352)


@pytest.fixture(scope="module")
def tiny():
    cfg = gm.config()
    module, params = gm.init(cfg)
    return cfg, module, params


def _logits(cfg, params, ids):
    from deepspeed_tpu.models.causal_lm import CausalLM
    return np.asarray(CausalLM(cfg).apply({"params": params}, jnp.asarray(ids))[0])


def test_the_expert_layer_agrees_with_the_reference(tiny):
    from deepspeed_tpu.models.causal_lm import make_layer
    cfg, _, params = tiny
    assert cfg.layer_kind(1) == "E" and cfg.moe_kind == "gated"
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 21, cfg.n_embd), jnp.float32)
    lp = params["layers_1"]
    (got, _), sown = jax.jit(lambda v, x, p: make_layer(cfg, 1).apply(
        v, x, p, mutable=["stats"]))({"params": lp}, x, jnp.arange(21)[None])
    want = REF.moe_layer(x[0], lp, gm.MODEL)
    change = float(jnp.abs(want - x[0]).max())             # what the layer adds
    assert change > 1e-2
    assert float(jnp.abs(got[0] - want).max()) < 1e-4 * change
    # the two counts the serving chunk adds up: every assignment falls on a held
    # expert here, and the 63 of 21 tokens touch most of the 12
    (stats,) = sown["stats"]["moe_counts"]
    assert int(stats[0]) == 21 * 3 and 6 <= int(stats[1]) <= 12


def test_the_forward_agrees_with_the_reference_logit_by_logit(tiny):
    _, module, params = tiny
    ids = gt.ids(37)
    got = jax.jit(module.apply)({"params": params}, jnp.asarray(ids))[0]
    want = REF.forward(params, gm.MODEL, ids[0])
    assert float(want.std()) > 0.2
    assert float(jnp.abs(got - want).max()) < TOL * float(want.std())
    blocks = REF.next_token_logits(params, gm.MODEL, ids[0], np.arange(30, 37),
                                   vocab_block=100, pad_to=16)
    assert float(np.abs(np.asarray(want[30:37]) - blocks).max()) < TOL * float(want.std())


def test_prefill_then_decode_through_the_pool_is_the_references_forward(tiny):
    """13 tokens prefilled under right padding (routed nowhere) into slot 1 of
    3, then 17 decode steps through the state pool and the pages: logits."""
    cfg, module, params = tiny
    ids = gt.ids(30, seed=3)[0]
    got, pool = _served_logits(cfg, module, params, ids, 13)
    want = REF.forward(params, gm.MODEL, ids)[12:]
    assert got.shape == want.shape == (18, 256)
    assert float(jnp.abs(got - want).max()) < TOL * float(want.std())
    assert [sorted(c) for c in pool.caches] == [["conv", "ssm"], [], ["k", "v"], [],
                                                ["conv", "ssm"], [], ["conv", "ssm"], []]
    assert pool.kv_layers == 1
    assert pool.state_nbytes == 3 * 3 * (8 * 16 * 16 * 4 + 3 * 160 * 4)


def test_the_scheduler_serves_it_and_counts_its_expert_layers():
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.serving.prefix_cache import PrefixCacheConfig
    from deepspeed_tpu.inference.serving.scheduler import (
        ContinuousBatchingScheduler, ServingConfig)
    eng = InferenceEngine(gm.config(), DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=64), seed=3)
    assert eng.model_config.num_params() == sum(
        a.size for a in jax.tree_util.tree_leaves(eng.params))
    prompts = [gt.ids(n, seed=n)[0] for n in (5, 13, 16, 9, 21)]
    sched = ContinuousBatchingScheduler(eng, ServingConfig(
        slots=2, chunk_size=4, max_seq_len=64, max_queue=8, kv_page_size=8,
        prefix_cache=PrefixCacheConfig(enabled=False)))
    handles = [sched.submit(p, max_new_tokens=6 + i) for i, p in enumerate(prompts)]
    sched.run()
    for p, h in zip(prompts, handles):
        alone = eng.generate(p[None], max_new_tokens=len(h.tokens))[0, p.size:]
        assert list(h.tokens) == [int(t) for t in alone], p.size
    want = REF.next_token_logits(eng.params, gm.MODEL, prompts[2], [prompts[2].size - 1],
                                 vocab_block=128, pad_to=16)
    assert int(want[0].argmax()) == handles[2].tokens[0]
    # four expert layers, two slots a step, three experts a token: a step adds
    # 4 x 2 x 3 assignments (idle rows are routed too), all on held experts
    tel = sched.telemetry
    assert tel.moe_assignments > 0 and tel.moe_assignments % (4 * 2 * 3) == 0
    assert 4 <= tel.moe_experts_touched * 24 / tel.moe_assignments <= 4 * 6


def _layer(held, shared_width=96, n=72, k=10):
    from deepspeed_tpu.moe.gated_moe import GatedMoE
    return GatedMoE(d_model=32, n_routed=n, top_k=k, expert_width=16, norm_topk=True,
                    experts_held=held, dtype=jnp.float32, init_std=0.3, out_std=0.3,
                    shared_width=shared_width)


def test_two_shares_and_the_shared_expert_once_add_up_to_the_uncut_reference_layer():
    """THE SHARE TEST for granite-4.0-h-small's layer at its own counts (72
    experts, the top 10): each chip of the 2-way expert-parallel layer routes
    over all 72 and adds its 36 experts' part and the shared expert, which is
    every chip's: the two outputs, the shared expert's term taken off one,
    add up to what the plain reference gives for the whole layer."""
    h = jnp.asarray(np.random.RandomState(5).standard_normal((1, 32, 32)), jnp.float32)
    params = _layer((0, 72)).init(jax.random.PRNGKey(1), h)["params"]
    assert sorted(params) == ["experts_down", "experts_gate", "experts_up", "router",
                              "shared_down", "shared_gate", "shared_up"]

    def share(first, count, **kw):
        p = {k: (v[first:first + count] if k.startswith("experts_") else v)
             for k, v in params.items() if kw.get("shared_width", 96) or "shared" not in k}
        return _layer((first, count), **kw).apply({"params": p}, h)

    whole, stats = share(0, 72)
    (low, s0), (high, s1) = share(0, 36), share(36, 36)
    shared = np.asarray(whole) - np.asarray(share(0, 72, shared_width=0)[0])
    assert np.abs(shared).max() > 0.1
    assert np.abs(np.asarray(low) + np.asarray(high) - shared - np.asarray(whole)).max() < 1e-4
    assert int(s0[0]) + int(s1[0]) == int(stats[0]) == 32 * 10
    assert 0 < int(s0[0]) < 320 and int(s0[1]) <= 36 and int(s1[1]) <= 36
    model = dict(hidden_size=32, num_local_experts=72, num_experts_per_tok=10,
                 residual_multiplier=1.0, rms_norm_eps=1e-5)
    x = h[0]            # the reference norms its input; the layer is given the normed one
    normed = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-5)
    lp = {"norm": {"scale": jnp.ones(32)}, "moe": params}
    want = np.asarray(REF.moe_layer(x, lp, model, expert_block=7) - x)
    parts = [_layer((a, 36)).apply({"params": {
        k: (v[a:a + 36] if k.startswith("experts_") else v)
        for k, v in params.items()}}, normed[None])[0] for a in (0, 36)]
    only_shared = np.asarray(REF.shared_expert(normed, params))
    assert np.abs(np.asarray(parts[0] + parts[1])[0] - only_shared - want).max() < 1e-4
    # and the reference given a share computes that share
    for a, part in zip((0, 36), parts):
        held = {"norm": lp["norm"], "moe": {
            k: (v[a:a + 36] if k.startswith("experts_") else v) for k, v in params.items()}}
        mine = REF.moe_layer(x, held, dict(model, experts_held=[a, 36])) - x
        assert np.abs(np.asarray(part[0]) - np.asarray(mine)).max() < 1e-4


FAULTS = {
    # in the program, by its configuration
    "shared_expert_dropped": dict(moe_shared_width=0),
    "softmax_over_all_not_renormalised": dict(norm_topk_prob=False),
    "one_expert_fewer": dict(experts_per_token=2),
    # in a copy of the reference's layer (the program is right and fails it)
    "shared_expert_under_its_own_norm": "second_norm",
    "residual_multiplier_on_the_experts_only": "moe_only",
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_in_the_expert_layer_fails_the_comparison(tiny, fault, monkeypatch):
    """Each fault reads whole hundredths of a spread at the least where the
    right layer agrees inside ``TOL`` (1e-4): the shared expert left out; the
    shared expert reading a second RMSNorm of its own instead of the layer's
    one; the chosen experts weighted by the softmax over all experts without
    the renormalisation; top k - 1; ``residual_multiplier`` on the routed sum
    only, the shared expert added whole."""
    cfg, _, params = tiny
    ids = gt.ids(37)
    how = FAULTS[fault]
    if isinstance(how, dict):
        got = _logits(dataclasses.replace(cfg, **how), params, ids)
    else:
        got = _logits(cfg, params, ids)
        right = REF.shared_expert
        r = gm.MODEL["residual_multiplier"]
        if how == "second_norm":
            w2 = 1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(9), (cfg.n_embd,))
            planted = lambda b, p: right(REF.rmsnorm(b, w2, 1e-5), p)
        else:
            planted = lambda b, p: right(b, p) / r
        monkeypatch.setattr(REF, "shared_expert", planted)
        # a new function object: jax keeps traces by the function traced
        monkeypatch.setattr(REF, "_routed_jit", jax.jit(
            lambda x, lp, model: REF.normed_and_routed(x, lp, model), static_argnums=2))
    want = np.asarray(REF.forward(params, gm.MODEL, ids[0]))
    assert np.abs(got - want).max() > 0.02 * want.std() >= 200 * TOL * want.std(), fault


def test_a_decode_attention_that_drops_the_multiplier_fails_the_decode_comparison(
        tiny, monkeypatch):
    """The Mosaic ``decode_attention`` (interpret mode here) is where this
    model's ``attention_multiplier`` reaches a kernel on the chip for the first
    time (head 128; the family's other model, at head 64, decodes in XLA). A
    kernel call that falls back to its own ``1 / sqrt(head size)`` agrees on
    the prefill's logits and fails the decode steps."""
    from deepspeed_tpu.models import causal_lm as clm
    cfg, module, params = tiny
    right = clm.decode_attention
    seen = []

    def planted(q, k, v, lens, scale):
        seen.append(scale)
        return right(q, k, v, lens, None)

    monkeypatch.setattr(clm, "decode_attention", planted)
    ids = gt.ids(30, seed=3)[0]
    got, _ = _served_logits(cfg, module, params, ids, 13)
    want = REF.forward(params, gm.MODEL, ids)[12:]
    assert seen and set(seen) == {0.015625}
    assert float(jnp.abs(got[0] - want[0]).max()) < TOL * float(want.std())
    assert float(jnp.abs(got[2:] - want[2:]).max()) > 100 * TOL * float(want.std())


def test_the_builder_builds_both_of_the_familys_models():
    """With experts the published config gives "E" after every mixer; with
    ``num_local_experts`` 0 exactly the configuration it gave before it knew
    of experts (a program is a function of its configuration: equal
    configurations lower equal programs), and the gated layer without a
    shared width holds no such parameter (SDAR's and LFM2's layer)."""
    from deepspeed_tpu.models.causal_lm import CausalLMConfig, granite_hybrid_cfg
    whole = granite_hybrid_cfg(max_seq_len=2048, **PUBLISHED)
    assert whole.layer_pattern == ("ME" * 5 + "*E" + "ME" * 4) * 4
    assert whole.num_params() == 32_207_337_984
    stage = granite_hybrid_cfg(max_seq_len=2048, experts_held=[0, 36],
                               **{**PUBLISHED, "num_hidden_layers": 10,
                                  "layer_types": PUBLISHED["layer_types"][:10]})
    assert stage.layer_pattern == "ME" * 5 + "*E" + "ME" * 4 and stage.n_layer == 20
    assert stage.num_params() == 4_962_732_672 == (
        9 * 102_291_072 + 41_947_136 + 10 * 358_912_000 + 411_041_792 + 4_096)
    assert (stage.moe_kind, stage.moe_router, stage.norm_topk_prob) == \
        ("gated", "softmax", True)
    assert (stage.n_routed_experts, stage.experts_per_token, stage.moe_expert_width,
            stage.moe_shared_width, stage.held_experts) == (72, 10, 768, 1536, (0, 36))
    assert (stage.head_dim, stage.attn_scale, stage.logits_scaling) == (128, 1 / 128, 16.0)
    assert (stage.mamba_num_heads, stage.ssm_n_groups, stage.conv_dim) == (128, 1, 8448)
    tiny = gm.config()
    assert tiny.layer_kinds == gm.PATTERN
    # without experts: field for field what the family's first model was built as
    micro = gt.config()
    assert micro.layer_kinds == gt.PATTERN and micro.moe_shared_width == 0
    assert micro == CausalLMConfig(
        n_embd=64, n_layer=8, layer_pattern=gt.PATTERN, vocab_size=256, n_head=4,
        n_kv_head=2, pos_emb="none", layernorm="rmsnorm", ln_eps=1e-5, qkv_bias=False,
        mlp_bias=False, gated_mlp=True, activation="silu", d_ff=96,
        tie_word_embeddings=True, mamba_num_heads=8, mamba_head_dim=16,
        ssm_state_size=16, ssm_n_groups=1, conv_kernel=4, ssm_chunk_size=8,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=0.015625, logits_scaling=8.0, max_seq_len=64,
        dtype=jnp.float32, init_std=0.3, name="granite-hybrid")
    h = jnp.zeros((1, 4, 32), jnp.float32)
    assert not [k for k in _layer((0, 8), shared_width=0, n=8, k=2).init(
        jax.random.PRNGKey(0), h)["params"] if "shared" in k]


@pytest.mark.parametrize("over,error,match", [
    (dict(position_embedding_type="rope"), NotImplementedError, "position_embedding_type"),
    (dict(mamba_proj_bias=True), NotImplementedError, "mamba_proj_bias"),
    (dict(attention_bias=True), NotImplementedError, "attention_bias"),
    (dict(num_experts_per_tok=13), NotImplementedError, "num_experts_per_tok=13"),
    (dict(num_experts_per_tok=0), NotImplementedError, "1 <= num_experts_per_tok"),
    (dict(intermediate_size=None), NotImplementedError, "intermediate_size=None"),
    (dict(num_hidden_layers=3), ValueError, "layer_types names 4 layers"),
    (dict(experts_held=[8, 6]), ValueError, "no share of 12 experts"),
    (dict(num_local_experts=0, experts_held=[0, 6]), ValueError, "no share of 0 experts"),
])
def test_the_builder_refuses_in_words_what_it_does_not_build(over, error, match):
    with pytest.raises(error, match=match):
        gm.config(**over)


def test_the_stand_in_keeps_its_seeded_routers_and_gets_no_bias(monkeypatch):
    """Granite's router has no selection bias and its stand-in gets none: an
    engine that draws random weights for this configuration never reaches
    the levelling (``level_random_experts`` is for routers that are trained
    with a bias), so every seed's routers are the seeded init's. Measured at
    the published width, seeded routers give every seed the same work
    (``PERF.md`` section 6, PR 53)."""
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.moe import latent_moe
    cfg = gm.config()
    assert cfg.level_random_experts is False
    calls = []
    monkeypatch.setattr(latent_moe, "level_expert_load",
                        lambda *a, **k: calls.append(a))
    conf = DeepSpeedInferenceConfig(dtype="float32", max_out_tokens=64)
    params = InferenceEngine(cfg, conf, seed=5).params
    assert not calls
    for i in (1, 3, 5, 7):
        assert set(params[f"layers_{i}"]["moe"]) == {
            "router", "experts_gate", "experts_up", "experts_down",
            "shared_gate", "shared_up", "shared_down"}
