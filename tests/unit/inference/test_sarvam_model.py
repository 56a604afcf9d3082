"""``sarvam_mla`` (latent attention with one cached row a token for all heads,
absorbed at decode and expanded at prefill; sigmoid-and-bias experts beside a
shared expert) at a small size on the CPU, seeded random weights, against the
plain float32 reference the benchmark keeps
(``benchmarks/chipbench/reference/sarvam_mla.py``): the forward logit by
logit, prefill then decode through the paged pool, absorbed against expanded,
the rotary frequencies and the scale against hand values, the share test, the
cached row, the refusals, the parameter count at the published widths, the
flash kernel at unequal widths, and what D18 left of ``"k" in c``."""

import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.unit import sarvam_tiny as st

REF = st.reference()
TOL = 1e-4          # float32 both sides, in spreads of the reference's logits
# the catalog row's ``config`` (model-configs guide, architectures.jsonl)
PUBLISHED = dict(
    attn_implementation=None, default_theta=10000, first_k_dense_replace=1,
    head_dim=576, hidden_act="silu", hidden_size=4096, intermediate_size=16384,
    kv_lora_rank=512, max_position_embeddings=131072, model_type="sarvam_mla",
    moe_intermediate_size=2048, moe_router_enable_expert_bias=True,
    num_attention_heads=64, num_experts=128, num_experts_per_tok=8,
    num_hidden_layers=32, num_shared_experts=1, q_head_dim=192, qk_nope_head_dim=128,
    qk_rope_head_dim=64, rms_norm_eps=1e-06, rope_scaling=dict(st.ROPE), rope_theta=10000,
    routed_scaling_factor=2.5, tie_word_embeddings=False, use_qk_norm=True,
    v_head_dim=128, vocab_size=262144)


@pytest.fixture(scope="module")
def tiny():
    cfg = st.config()
    module, params = st.init(cfg)
    return cfg, module, params


def _served_logits(cfg, module, params, ids, prompt_len, cap=64, slots=3, slot=1):
    """Prefill ``prompt_len`` tokens (right-padded to a bucket of 32) into
    ``slot`` of a paged pool, then decode the rest one token at a time on the
    chunk's dense view, each appended row copied back into the pages as the
    chunk does: logits at ``prompt_len - 1 ..``, and the pool."""
    from deepspeed_tpu.inference.decode_fns import _copy_back, _dense_view
    from deepspeed_tpu.inference.serving.kv_pool import PagedKVPool
    from deepspeed_tpu.models.causal_lm import init_cache
    pool = PagedKVPool(cfg, slots, cap, page_size=8)
    for _ in range(slot + 1):
        got = pool.acquire(tokens=cap)
    assert got == slot
    pad = np.zeros((1, 32), np.int32)
    pad[0, :prompt_len] = ids[:prompt_len]
    lens0 = jnp.asarray([prompt_len])
    logits, one = jax.jit(lambda v, i, c, n: module.apply(
        v, i, caches=c, cache_lens=jnp.zeros_like(n), seq_lens=n,
        logits_positions=n - 1))({"params": params}, jnp.asarray(pad),
                                 init_cache(cfg, 1, cap), lens0)
    pool.scatter_prefill(slot, one)
    rows = [logits[0, 0]]
    keeps = cfg.layer_keeps

    @jax.jit
    def step(v, toks, caches, table, lens, on):
        dense = _dense_view(keeps, caches, table, cap)
        logits, new = module.apply(v, toks, positions=lens[:, None], caches=dense,
                                   cache_lens=lens)
        return logits, _copy_back(keeps, caches, new, table, lens, lens + on, 1, cap)

    lens = np.zeros(slots, np.int32)
    lens[slot] = prompt_len
    on = jnp.asarray(np.arange(slots) == slot, jnp.int32)
    for i in range(prompt_len, len(ids)):
        toks = np.zeros((slots, 1), np.int32)
        toks[slot, 0] = ids[i]
        logits, pool.caches = step({"params": params}, jnp.asarray(toks), pool.caches,
                                   jnp.asarray(pool.page_table), jnp.asarray(lens.copy()),
                                   on)
        rows.append(logits[slot, 0])
        lens[slot] += 1
    return np.asarray(jnp.stack(rows)), pool


# ------------------------------------------------------------ (1) the reference
def test_the_forward_agrees_with_the_reference_logit_by_logit(tiny):
    cfg, module, params = tiny
    assert cfg.layer_pattern == st.PATTERN and cfg.layer_keeps[0] == "latent"
    ids = st.ids(37)
    got = jax.jit(module.apply)({"params": params}, jnp.asarray(ids))[0]
    want = REF.forward(params, st.MODEL, ids[0])
    assert float(want.std()) > 0.2
    assert float(jnp.abs(got - want).max()) < TOL * float(want.std())
    blocks = REF.next_token_logits(params, st.MODEL, ids[0], np.arange(30, 37), pad_to=16)
    assert float(np.abs(np.asarray(want[30:37]) - blocks).max()) < TOL * float(want.std())


def test_prefill_then_twelve_decode_steps_through_the_pool_agree_with_the_reference(tiny):
    cfg, module, params = tiny
    ids = st.ids(33, seed=3)[0]
    got, _ = _served_logits(cfg, module, params, ids, 21)
    want = np.asarray(REF.forward(params, st.MODEL, ids))[20:]
    assert got.shape == want.shape == (13, 256)
    assert float(np.abs(got - want).max()) < TOL * float(want.std())


@pytest.mark.parametrize("fault", ["no_rotary_term", "scale", "latent_norm", "shared"])
def test_a_planted_fault_of_the_layer_shows_against_the_reference(tiny, fault, monkeypatch):
    """Each fault moves the served logits by far more than the right program's
    rounding: the decode's rotary term dropped (prefill right, decode wrong: only
    prefill-then-decode through the cache shows it), the scale without yarn's
    factor, the latent's norm dropped, the shared expert dropped."""
    from deepspeed_tpu.models import causal_lm as clm
    cfg, module, params = tiny
    ids = st.ids(30, seed=5)[0]
    want = np.asarray(REF.forward(params, st.MODEL, ids))[17:]
    if fault == "no_rotary_term":
        real = clm.latent_decode_attention
        rank = cfg.kv_lora_rank
        monkeypatch.setattr(clm, "latent_decode_attention",
                            lambda q, rows, lens, scale: real(
                                q.at[..., rank:].set(0), rows, lens, scale))
    elif fault == "scale":
        cfg = dataclasses.replace(cfg, rope_yarn=cfg.rope_yarn[:5] + (0.0,))
    elif fault == "latent_norm":
        params = jax.tree_util.tree_map(lambda a: a, params)
        params["layers_2"]["kv_a_norm"]["scale"] = jnp.ones_like(
            params["layers_2"]["kv_a_norm"]["scale"])
    else:
        params = jax.tree_util.tree_map(lambda a: a, params)
        params["layers_3"]["moe"]["shared_down"] = jnp.zeros_like(
            params["layers_3"]["moe"]["shared_down"])
    got, _ = _served_logits(cfg, clm.CausalLM(cfg), params, ids, 18)
    assert float(np.abs(got - want).max()) > 200 * TOL * float(want.std())
    if fault == "no_rotary_term":      # the prefill's logits are right all the same
        assert float(np.abs(got[0] - want[0]).max()) < TOL * float(want.std())


# --------------------------------------------------- (2) absorbed == expanded
@pytest.mark.parametrize("T", [64, 384])
def test_absorbed_decode_is_expanded_attention_on_the_same_rows(T):
    """One query against the cached rows of two sequences: the absorbed form
    over ``[c ; k^r ; 0]`` rows equals softmax(scale (q^n . W_uk c + q^r .
    k^r)) W_uv c, to float32 rounding, in one block (a cap of 64) and over
    several (384: blocks of 64, three walked), and beyond the live rows."""
    from deepspeed_tpu.ops.attention.latent import latent_decode_attention
    key = jax.random.PRNGKey(2)
    b, H, rank, nope, rope, dv, lanes = 2, 4, 32, 16, 8, 16, 128
    ks = jax.random.split(key, 6)
    c = jax.random.normal(ks[0], (b, T, rank))
    k_r = jax.random.normal(ks[1], (b, T, rope))
    q_n = jax.random.normal(ks[2], (b, H, nope))
    q_r = jax.random.normal(ks[3], (b, H, rope))
    w_uk = jax.random.normal(ks[4], (rank, H, nope)) * 0.3
    w_uv = jax.random.normal(ks[5], (rank, H, dv)) * 0.3
    lens = jnp.asarray([T * 3 // 8 + 16, 17])
    scale = 0.2
    with jax.default_matmul_precision("highest"):
        k_n = jnp.einsum("btl,lhn->bthn", c, w_uk)
        v = jnp.einsum("btl,lhv->bthv", c, w_uv)
        s = scale * (jnp.einsum("bhn,bthn->bht", q_n, k_n)
                     + jnp.einsum("bhr,btr->bht", q_r, k_r))
        s = jnp.where(jnp.arange(T)[None, None] < lens[:, None, None], s, -jnp.inf)
        want = jnp.einsum("bht,bthv->bhv", jax.nn.softmax(s, -1), v)
        rows = jnp.concatenate([c, k_r, jnp.zeros((b, T, lanes - rank - rope))], -1)
        # rows past a sequence's length hold anything: they are never seen
        rows = rows.at[1, 17:].set(7.0)[:, None]
        q = jnp.concatenate([jnp.einsum("bhn,lhn->bhl", q_n, w_uk), q_r,
                             jnp.zeros((b, H, lanes - rank - rope))], -1)
        got = jnp.einsum("bhl,lhv->bhv",
                         latent_decode_attention(q, rows, lens, scale)[..., :rank], w_uv)
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(jnp.abs(want).max())
    # a sequence's result does not depend on how far the batch's walk goes
    alone = latent_decode_attention(q[1:], rows[1:], lens[1:], scale)
    both = latent_decode_attention(q, rows, lens, scale)
    assert bool(jnp.all(alone[0] == both[1]))


# ------------------------------------------------------- (3) rotary and scale
def test_the_yarn_frequencies_and_the_scale_against_hand_values():
    from deepspeed_tpu.models.causal_lm import sarvam_mla_cfg
    model = {k: v for k, v in PUBLISHED.items()}
    cfg = sarvam_mla_cfg(max_seq_len=64, **model)
    m = 0.1 * math.log(40) + 1                      # m(40, 1) = 1.36889
    assert abs(m - 1.36889) < 1e-5
    assert abs(cfg.attn_scale - 192 ** -0.5 * m * m) < 1e-9
    assert abs(cfg.attn_scale - 0.135234) < 1e-6
    inv, mscale = cfg.latent_rope()
    assert inv.shape == (32,) and mscale == 1.0
    # the correction dimensions of 32 and 1 rotations in 4096 positions at base
    # 10,000 over 64 lanes: 64 ln(4096 / (r 2 pi)) / (2 ln 10000) = 10.48 and
    # 22.52, so frequencies 0-10 keep base^(-2i/64), 23-31 are divided by 40,
    # and between them the ramp (i - 10) / 13 blends the two
    base = 10000.0 ** (-np.arange(32) / 32.0)
    assert np.allclose(inv[:11], base[:11], rtol=1e-6)
    assert np.allclose(inv[23:], base[23:] / 40, rtol=1e-6)
    for i in (11, 16, 22):
        r = (i - 10) / 13
        assert abs(inv[i] / (base[i] * (1 - r) + base[i] / 40 * r) - 1) < 1e-5
    assert abs(inv[16] - 5.5e-3) < 1e-6             # a hand value: 0.0177828 x 0.30929
    # the reference's own tables, made independently, say the same
    cos, sin, scale = REF.rope_tables(model, 5)
    assert abs(scale - cfg.attn_scale) < 1e-9
    assert np.allclose(cos[3], np.cos(3 * inv), atol=1e-6)
    # plain rotary where the config has no scaling: scale 1 / sqrt(192) alone
    plain = sarvam_mla_cfg(max_seq_len=64, **dict(model, rope_scaling=None))
    assert abs(plain.attn_scale - 192 ** -0.5) < 1e-12
    assert np.allclose(plain.latent_rope()[0], base, rtol=1e-6)


def test_a_rotation_pairs_neighbouring_lanes_as_the_reference_does():
    """The program lays a rotated part out de-interleaved, the reference turns
    the pairs in place: the same numbers, permuted, so every dot product of a
    rotated query with a rotated key agrees."""
    from deepspeed_tpu.ops.attention.latent import rotate_pairs
    cfg = st.config()
    inv, m = cfg.latent_rope()
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 6, 2, 8))
    got = np.asarray(rotate_pairs(x, jnp.arange(6)[None], inv, m))[0]
    cos, sin, _ = REF.rope_tables(st.MODEL, 6)
    want = np.asarray(REF.rotate(x[0], cos, sin))
    assert np.allclose(got[..., :4], want[..., 0::2], atol=1e-6)
    assert np.allclose(got[..., 4:], want[..., 1::2], atol=1e-6)


# ------------------------------------------------------- (4) the share adds up
def test_the_eight_shares_of_an_expert_layer_add_up_to_the_whole_layer(tiny):
    """Expert parallelism's promise at a small size: the terms of the 8 shares
    (one expert each here), with the shared expert counted once, are the uncut
    reference's layer."""
    from deepspeed_tpu.models.causal_lm import make_layer
    cfg, _, params = tiny
    lp = params["layers_3"]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 19, cfg.n_embd), jnp.float32)
    whole = np.asarray(REF.moe_layer(x[0], lp, st.MODEL)) - np.asarray(x[0])
    total = np.zeros_like(whole)
    for first in range(8):
        share = dataclasses.replace(cfg, experts_held=(first, 1))
        mine = dict(lp, moe={k: (v[first:first + 1] if k.startswith("experts_") else v)
                             for k, v in lp["moe"].items()})
        (y, _), _ = jax.jit(lambda v, x, p, share=share: make_layer(share, 3).apply(
            v, x, p, mutable=["stats"]))({"params": mine}, x, jnp.arange(19)[None])
        total += np.asarray(y[0] - x[0])
    b = REF.rmsnorm(x[0], lp["norm"]["scale"], 1e-6)
    shared = np.asarray(REF.shared_expert(b, lp["moe"]["shared_gate"],
                                          lp["moe"]["shared_up"],
                                          lp["moe"]["shared_down"]))
    assert float(np.abs(whole).max()) > 1e-2
    assert float(np.abs(total - 7 * shared - whole).max()) < 1e-4 * float(np.abs(whole).max())


def test_the_eight_vocabulary_slices_are_the_whole_heads_logits(tiny):
    cfg, module, params = tiny
    ids = st.ids(12, seed=8)
    whole = np.asarray(module.apply({"params": params}, jnp.asarray(ids))[0])
    parts = []
    for i in range(8):
        rows = slice(32 * i, 32 * (i + 1))
        part = dict(params, lm_head={"kernel": params["lm_head"]["kernel"][:, rows]})
        parts.append(np.asarray(REF.forward(part, st.MODEL, ids[0])))
    assert float(np.abs(np.concatenate(parts, -1) - whole).max()) < TOL * float(whole.std())


# ----------------------------------------------------------- (5) the cached row
def test_the_cached_row_is_five_lane_tiles_with_zero_lanes_and_no_values():
    from deepspeed_tpu.models.causal_lm import LAYER_KINDS, init_cache, sarvam_mla_cfg
    from deepspeed_tpu.ops.paged_attention import latent_row_lanes
    assert latent_row_lanes(576) == 640 and latent_row_lanes(40) == 128
    assert LAYER_KINDS["L"].keeps == "latent"
    big = sarvam_mla_cfg(max_seq_len=64, **PUBLISHED)
    assert big.latent_row_width == 576
    shapes = jax.eval_shape(lambda: init_cache(big, 2, 48, kv_shape=(9, 64, 16, 128)))
    assert shapes[0].keys() == {"k"} and shapes[0]["k"].shape == (9, 1, 16, 640)
    assert shapes[1] == {}
    dense = jax.eval_shape(lambda: init_cache(big, 2, 48))
    assert dense[0]["k"].shape == (2, 1, 48, 640)


def test_the_pool_keeps_one_row_a_token_and_its_zero_lanes_stay_zero(tiny):
    cfg, module, params = tiny
    ids = st.ids(33, seed=3)[0]
    _, pool = _served_logits(cfg, module, params, ids, 21)
    assert pool.keeps == cfg.layer_keeps and pool.kv_layers == 3
    assert pool.stats()["latent_row_bytes"] == 128 * 4         # float32 here
    assert pool.page_nbytes == 3 * 8 * 128 * 4
    table = pool.page_table[1]
    for c in (pool.caches[0], pool.caches[2], pool.caches[4]):
        assert c.keys() == {"k"} and c["k"].shape == (pool.total_pages, 1, 8, 128)
        rows = np.asarray(c["k"])[table, 0].reshape(-1, 128)[:32]
        assert np.abs(rows[:, :40]).min(axis=1).max() > 0       # 32 written rows
        assert not rows[:, 40:].any()                           # lanes 40-127 zero
    assert pool.caches[1] == {} and pool.caches[3] == {}


def test_the_telemetry_says_a_latent_pool_from_a_per_head_one(tiny):
    from deepspeed_tpu.inference.serving.kv_pool import PagedKVPool
    from deepspeed_tpu.observability.schema import TAGS
    from tests.unit import granite_tiny as gt
    assert "serving/kv_latent_row_bytes" in TAGS
    assert PagedKVPool(gt.config(), 2, 32, page_size=8).stats()["latent_row_bytes"] == 0
    assert PagedKVPool(tiny[0], 2, 32, page_size=8).stats()["latent_row_bytes"] == 512


# -------------------------------------------------------------- (6) refusals
@pytest.fixture(scope="module")
def engine():
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    cfg = st.config(max_seq_len=48, greedy_decode_rows=2)
    return InferenceEngine(cfg, DeepSpeedInferenceConfig(dtype="float32",
                                                         max_out_tokens=48), seed=1)


@pytest.mark.parametrize("what", ["prefix_cache", "speculate"])
def test_the_scheduler_refuses_prefix_hits_and_speculation_over_latent_rows(engine, what):
    from deepspeed_tpu.inference.serving.prefix_cache import PrefixCacheConfig
    from deepspeed_tpu.inference.serving.scheduler import (
        ContinuousBatchingScheduler, ServingConfig)
    kw = dict(slots=2, chunk_size=4, max_seq_len=48, kv_page_size=8,
              prefix_cache=PrefixCacheConfig(enabled=what == "prefix_cache"),
              speculate=what == "speculate")
    with pytest.raises(ValueError, match="latent-attention layers keep one latent row"):
        ContinuousBatchingScheduler(engine, ServingConfig(**kw))


def test_the_scheduler_serves_what_generate_gives(engine):
    from deepspeed_tpu.inference.serving.prefix_cache import PrefixCacheConfig
    from deepspeed_tpu.inference.serving.scheduler import (
        ContinuousBatchingScheduler, ServingConfig)
    sched = ContinuousBatchingScheduler(engine, ServingConfig(
        slots=2, chunk_size=4, max_seq_len=48, kv_page_size=8,
        prefix_cache=PrefixCacheConfig(enabled=False)))
    prompts = [st.ids(n, seed=n)[0] for n in (9, 17, 5)]
    handles = [sched.submit(p, max_new_tokens=m) for p, m in zip(prompts, (7, 10, 6))]
    sched.run()
    for p, h in zip(prompts, handles):
        want = np.asarray(engine.generate(p[None], max_new_tokens=len(h.tokens)))[0, p.size:]
        assert list(h.tokens) == [int(t) for t in want]
    assert sched.executor.pool.stats()["latent_row_bytes"] == 512


def test_the_rows_the_plans_lay_out_are_counted_beside_what_is_held(engine):
    """``serving/moe_plan_rows_total`` (PR 58): a program's static plan rows
    x its expert layers and forwards, counted on the host; the programs' own
    ``moe_assignments`` over it is the live share of what the plans lay out."""
    from deepspeed_tpu.inference.serving.prefix_cache import PrefixCacheConfig
    from deepspeed_tpu.inference.serving.scheduler import (
        ContinuousBatchingScheduler, ServingConfig)
    from deepspeed_tpu.observability.metrics import get_registry
    from deepspeed_tpu.observability.schema import TAGS
    from deepspeed_tpu.ops.moe.grouped_ffn import plan_rows
    assert "serving/moe_plan_rows_total" in TAGS
    sched = ContinuousBatchingScheduler(engine, ServingConfig(
        slots=2, chunk_size=4, max_seq_len=48, kv_page_size=8,
        prefix_cache=PrefixCacheConfig(enabled=False)))
    cfg = engine.model_config
    layers, k, held = (cfg.layer_kinds.count("E"), cfg.experts_per_token,
                       cfg.held_experts[1])
    assert layers == 2 and held == 8
    ex, tel = sched.executor, sched.telemetry
    assert ex.moe_plan_rows(16) == layers * plan_rows(16 * k, held,
                                                      cfg.n_routed_experts)
    sched.submit(st.ids(9, seed=9)[0], max_new_tokens=6)
    sched.step()                                    # the prefill and one chunk
    prefill = ex.moe_plan_rows(ex.bucket_for(9))
    chunk = 4 * ex.moe_plan_rows(2)                 # 4 forwards of 2 slots
    assert tel.moe_plan_rows == prefill + chunk
    sched.run()
    assert tel.moe_plan_rows > prefill + chunk
    assert (tel.moe_plan_rows - prefill) % chunk == 0
    assert 0 < tel.moe_assignments < tel.moe_plan_rows
    assert "moe_plan_rows_total" in get_registry().prometheus_text()


def test_a_prefill_says_the_height_of_the_tiles_it_was_built_with(engine, monkeypatch):
    """``moe_tile_rows`` (PR 60) beside ``moe_assignments`` on
    ``serving.prefill``, and on the ``setup.program`` phase of every program
    with expert layers: ``grouped_ffn.tile_rows`` of the program's static
    assignments and the router's width, which the expert layers take from
    their router matrix."""
    from deepspeed_tpu.inference.serving.prefix_cache import PrefixCacheConfig
    from deepspeed_tpu.inference.serving.scheduler import (
        ContinuousBatchingScheduler, ServingConfig)
    from deepspeed_tpu.observability import get_tracer
    from deepspeed_tpu.observability.schema import SPANS
    from deepspeed_tpu.ops.moe import grouped_ffn as g
    assert "moe_tile_rows" in SPANS["serving.prefill"][2]
    assert "moe_tile_rows" in SPANS["setup.program"][2]
    cfg = engine.model_config
    k, experts = cfg.experts_per_token, cfg.n_routed_experts
    seen = []
    whole = g.tile_rows
    monkeypatch.setattr(g, "tile_rows", lambda a, e: seen.append((a, e)) or whole(a, e))
    tracer = get_tracer()
    tracer.reset()
    tracer.enable()
    try:
        sched = ContinuousBatchingScheduler(engine, ServingConfig(
            slots=2, chunk_size=4, max_seq_len=48, kv_page_size=8,
            prefix_cache=PrefixCacheConfig(enabled=False)))
        sched.submit(st.ids(19, seed=4)[0], max_new_tokens=5)
        sched.run()
        spans, phases = list(tracer.spans), tracer.phases
    finally:
        tracer.disable()
        tracer.reset()
    ex = sched.executor
    (prefill,) = [s["attrs"] for s in spans if s["name"] == "serving.prefill"]
    assert prefill["bucket"] == 32 and prefill["moe_assignments"] > 0
    assert prefill["moe_tile_rows"] == ex.moe_tile_rows(32) == whole(32 * k, experts) == 16
    built = {p["attrs"]["program"]: p["attrs"]["moe_tile_rows"] for p in phases
             if p["name"] == "setup.program"}
    assert built == {"prefill": 16, "decode_chunk": 16}
    # a forward this long with this router would be built with the tall tiles
    assert ex.moe_tile_rows(2048) == 128 and 2048 * k / experts >= g.TALL_TILES_FROM
    # the layers traced with the router's width, read off their router matrix
    assert (32 * k, experts) in seen and (2 * k, experts) in seen
    assert {e for _, e in seen} == {experts}


def test_a_latent_layer_refuses_a_prefill_at_a_cache_offset(tiny):
    from deepspeed_tpu.models.causal_lm import init_cache
    cfg, module, params = tiny
    with pytest.raises(NotImplementedError, match="no prefill at a cache offset"):
        module.apply({"params": params}, jnp.zeros((1, 4), jnp.int32),
                     caches=init_cache(cfg, 1, 16), cache_lens=jnp.asarray([3]),
                     prefix_fill=True)


@pytest.mark.parametrize("over,named", [
    (dict(q_lora_rank=1536), "q_lora_rank"),
    (dict(n_group=8), "n_group"),
    (dict(rope_scaling=dict(st.ROPE, type="linear")), "rope_scaling.type"),
    (dict(use_qk_norm=False), "use_qk_norm"),
    (dict(head_dim=512), "head_dim"),
    (dict(hidden_act="gelu"), "hidden_act"),
])
def test_the_builder_refuses_what_it_does_not_build(over, named):
    from deepspeed_tpu.models.causal_lm import sarvam_mla_cfg
    with pytest.raises(NotImplementedError, match=re.escape(named)):
        sarvam_mla_cfg(max_seq_len=64, **{**PUBLISHED, **over})


# ---------------------------------------------------------- (7) the parameters
def test_the_cut_holds_4_225_311_616_parameters_by_both_counts():
    """Shapes only: nothing is allocated at the published widths."""
    import json
    from deepspeed_tpu.models.causal_lm import CausalLM, sarvam_mla_cfg
    from benchmarks.chipbench import sarvam_shapes as sh
    with open(os.path.join(st.REPO, "benchmarks", "chipbench", "configs",
                           "sarvam-105b.json")) as f:
        model = json.load(f)["model"]
    cfg = sarvam_mla_cfg(max_seq_len=6144, **model)
    assert cfg.layer_pattern == "LF" + "LE" * 7 and cfg.held_experts == (0, 16)
    assert cfg.num_params() == sh.params(model) == 4_225_311_616
    tree = jax.eval_shape(lambda: CausalLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    assert sum(math.prod(a.shape) for a in jax.tree_util.tree_leaves(tree)) \
        == 4_225_311_616
    assert sh.attention_params(model) == 94_634_496
    whole = sarvam_mla_cfg(max_seq_len=64, **PUBLISHED)
    assert whole.num_params() == sh.params(dict(PUBLISHED)) == 106_031_767_424


# --------------------------------------- the flash kernel at unequal widths
def test_the_flash_forward_takes_queries_and_keys_wider_than_values():
    from deepspeed_tpu.ops.attention.flash import flash_attention
    from deepspeed_tpu.ops.transformer.attention import xla_attention
    key = jax.random.PRNGKey(0)
    q, k = (jax.random.normal(jax.random.fold_in(key, i), (1, 256, 2, 192)) for i in (0, 1))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 256, 2, 128))
    pad = ((0, 0),) * 3 + ((0, 64),)
    want = xla_attention(q, k, v, causal=True, softmax_scale=0.1)
    for blocks in ({}, dict(block_q=128, block_k=128)):         # one kv block, and two
        got = flash_attention(jnp.pad(q, pad), jnp.pad(k, pad), v, causal=True,
                              softmax_scale=0.1, **blocks)
        assert got.shape == (1, 256, 2, 128)
        assert float(jnp.abs(got - want).max()) < 1e-5
    with pytest.raises(NotImplementedError, match="whole 128-lane tiles"):
        flash_attention(q, k, v, causal=True)


# ------------------------------------------------------------------ (10) D18
def test_no_serve_program_asks_a_cache_for_its_keys_any_more():
    """D18: what a layer keeps is ``LAYER_KINDS``' answer
    (``CausalLMConfig.layer_keeps``), not whether its cache has a ``"k"``."""
    for rel in ("deepspeed_tpu/inference/decode_fns.py",
                "deepspeed_tpu/inference/serving/kv_pool.py"):
        with open(os.path.join(st.REPO, rel)) as f:
            text = f.read()
        assert '"k" in c' not in text and '"k" not in c' not in text, rel


def test_the_dense_view_and_the_copy_back_go_by_what_a_layer_keeps():
    """A cache that happens to hold a ``"k"`` is left alone where its layer keeps
    a state or nothing; a latent layer's one array is gathered and written back."""
    from deepspeed_tpu.inference.decode_fns import _copy_back, _dense_view
    pages = {"k": jnp.arange(5 * 1 * 4 * 128, dtype=jnp.float32).reshape(5, 1, 4, 128)}
    kv = {"k": jnp.ones((5, 2, 4, 8)), "v": 2 * jnp.ones((5, 2, 4, 8))}
    odd = {"k": jnp.zeros((3, 7))}
    table = jnp.asarray([[1, 2], [3, 4], [0, 0]], jnp.int32)
    keeps = ("latent", "state", "kv", "nothing")
    view = _dense_view(keeps, [pages, odd, kv, {}], table, 6)
    assert view[0]["k"].shape == (3, 1, 6, 128) and view[0].keys() == {"k"}
    assert view[1] is odd and view[3] == {}
    assert view[2]["k"].shape == view[2]["v"].shape == (3, 2, 6, 8)
    assert bool(jnp.all(view[0]["k"][1, 0, :4] == pages["k"][3, 0]))
    lens = jnp.asarray([2, 5, 0], jnp.int32)
    new = [{"k": view[0]["k"] + 1000.0}, odd, view[2], {}]
    out = _copy_back(keeps, [pages, odd, kv, {}], new, table, lens,
                     lens + jnp.asarray([1, 1, 0]), 1, 6)
    assert out[1] is odd and out[3] == {}
    changed = np.argwhere(np.asarray(out[0]["k"] != pages["k"]).any(axis=(1, 3)))
    assert sorted(map(tuple, changed)) == [(1, 2), (4, 1)]      # (page, row): rows 2 and 5


# ------------------------------------------- the expert kernel cut over its width
@pytest.mark.parametrize("gated", [True, False])
def test_an_expert_cut_over_its_width_adds_up_to_the_whole_expert(gated, monkeypatch):
    """``grouped_ffn`` called once a block of the experts' width (sarvam's 4096 x
    2048 experts take two: three whole blocks, double-buffered, pass the kernel's
    VMEM) gives what one call on whole matrices gives."""
    from deepspeed_tpu.ops.moe import grouped_ffn as g
    assert g.width_blocks(4096, 2048, 3, 2, 16) == 2 \
        and g.width_blocks(4096, 2048, 3, 2, 128) == 2 \
        and g.width_blocks(4096, 768, 3, 2, 128) == 1
    key = jax.random.PRNGKey(3)
    e, l, f, tm, tiles = 3, 128, 512, 16, 5
    x = jax.random.normal(key, (tiles * tm, l))
    w1, wg = (jax.random.normal(jax.random.fold_in(key, i), (e, l, f)) * 0.1 for i in (1, 2))
    w2 = jax.random.normal(jax.random.fold_in(key, 3), (e, f, l)) * 0.1
    te = jnp.asarray([0, 0, 2, 1, 1], jnp.int32)
    tv = jnp.asarray([1, 1, 1, 1, 0], jnp.int32)
    kw = dict(act=jax.nn.silu, tm=tm, w_gate=wg if gated else None)
    mats = 3 if gated else 2
    assert g.width_blocks(l, f, mats, 4, tm) == 1
    whole = g.grouped_ffn(x, te, tv, w1, w2, **kw)
    want = g.grouped_ffn_xla(x, te, tv, w1, w2, jax.nn.silu, tm, wg if gated else None)
    live = slice(0, 4 * tm)                              # the invalid tile is not written
    assert float(jnp.abs(whole[live] - want[live]).max()) < 1e-4
    # a VMEM that holds two experts' blocks only at a quarter of their width
    monkeypatch.setattr(g, "VMEM_LIMIT_BYTES",
                        2 * mats * l * (f // 4) * 4 + g.tile_bytes(tm, l, f // 4, 4))
    assert g.width_blocks(l, f, mats, 4, tm) == 4
    cut = g.grouped_ffn(x, te, tv, w1, w2, **kw)
    assert float(jnp.abs(cut[live] - whole[live]).max()) \
        < 1e-4 * float(jnp.abs(whole[live]).max())


# ------------------------------------------- the stand-in's routers behind a margin
HOMES = dict(hidden_size=256, num_hidden_layers=4, vocab_size=1024, num_attention_heads=4,
             kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             intermediate_size=512, moe_intermediate_size=64, num_experts=32,
             num_experts_per_tok=4, experts_held=[0, 8])


def _homes_engine(seed, dtype, **over):
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models.causal_lm import sarvam_mla_cfg
    model = {**st.MODEL, **HOMES, **over}
    eng = InferenceEngine(sarvam_mla_cfg(max_seq_len=64, **model),
                          DeepSpeedInferenceConfig(dtype=dtype, max_out_tokens=64),
                          seed=seed)
    return eng, {**st.MODEL, **HOMES}


def _choices(params, model, ids):
    """The reference's dense routing weights (tokens, held experts) of every
    expert layer, on the stream the reference itself computes."""
    x = jnp.asarray(params["wte"][jnp.asarray(ids)], jnp.float32)
    frozen, out = REF._Frozen(model), []
    for i in range(model["num_hidden_layers"]):
        x = REF.attention_layer(x, params[f"layers_{2 * i}"], frozen)
        lp = params[f"layers_{2 * i + 1}"]
        if "moe" in lp:
            out.append(np.asarray(REF.moe_route(x, lp, frozen)[1]))
            x = REF.moe_layer(x, lp, frozen)
        else:
            x = REF.ffn_layer(x, lp, frozen)
    return out, np.asarray(x)


def test_home_routers_give_every_token_its_homes_and_keep_its_code():
    """``home_random_routers``: a token id's code (+std on its homes' lanes, -std
    on the others, 4 homes of 32) reaches every router as the embedding gave it
    (no matrix writes lanes 0-31), each layer's router turns it into other
    homes (a permutation a layer), the chosen weights are all 2.5 / 4, loads
    over the vocabulary are level to the draw, and the seeded bias stays."""
    eng, model = _homes_engine(3, "float32", home_random_routers=True)
    p, n, k = eng.params, 32, 4
    code = np.asarray(p["wte"][:, :n], np.float32)
    assert np.allclose(np.abs(code), 0.02) and ((code > 0).sum(axis=1) == k).all()
    load = (code > 0).sum(axis=0)
    assert load.sum() == 1024 * k and load.max() < 1.35 * load.mean()
    ids = st.ids(48, seed=5, vocab=1024)[0]
    routed, x = _choices(p, model, ids)
    assert np.array_equal(x[:, :n], code[ids])              # nothing wrote there
    emb = np.asarray(p["wte"], np.float32)[ids]
    assert float(np.abs(x[:, n:] - emb[:, n:]).mean()) > 0.005   # every other lane moved
    sets = []
    for i, dense in zip((1, 2, 3), routed):
        moe = p[f"layers_{2 * i + 1}"]["moe"]
        rows = np.asarray(moe["router"][:n], np.float32)
        assert sorted(rows.argmax(axis=1)) == list(range(n)) and (rows.sum(axis=1) == 1024).all()
        assert float(np.abs(np.asarray(moe["router_bias"])).max()) > 0   # as seeded
        homes = (code[ids] > 0) @ (rows > 0)                # (tokens, experts) 0/1
        assert np.allclose(dense, 2.5 / k * homes[:, :8], atol=1e-6)
        sets.append(homes)
    assert not np.array_equal(sets[0], sets[1])             # other homes a layer


@pytest.mark.parametrize("homes", [False, True])
def test_a_hundredth_of_noise_moves_seeded_choices_and_none_behind_the_margin(homes):
    """Why the stand-in has the margin: the reference's own router on a stream
    and on the same stream with a hundredth of noise on every lane (what bf16
    leaves on a residual stream after a few layers), 192 tokens x 3 expert
    layers. As seeded, the 4th and 5th of 32 scores lie so close that the
    noise moves dozens of choices; behind the margin it moves none."""
    eng, model = _homes_engine(1, "float32", **({"home_random_routers": True} if homes else {}))
    ids = st.ids(192, seed=9, vocab=1024)[0]
    frozen, moved = REF._Frozen(model), 0
    x = jnp.asarray(eng.params["wte"][jnp.asarray(ids)], jnp.float32)
    for i in range(model["num_hidden_layers"]):
        x = REF.attention_layer(x, eng.params[f"layers_{2 * i}"], frozen)
        lp = eng.params[f"layers_{2 * i + 1}"]
        if "moe" not in lp:
            x = REF.ffn_layer(x, lp, frozen)
            continue
        noisy = x * (1 + 0.01 * jax.random.normal(jax.random.PRNGKey(i), x.shape))
        whole = {**model, "experts_held": [0, 32]}          # every expert's weight
        a = np.asarray(REF.moe_route(x, lp, REF._Frozen(whole))[1]) > 0
        b = np.asarray(REF.moe_route(noisy, lp, REF._Frozen(whole))[1]) > 0
        assert (a.sum(axis=1) == 4).all()
        moved += int((a != b).any(axis=1).sum())
        x = REF.moe_layer(x, lp, frozen)
    assert moved == 0 if homes else moved > 20, moved


def test_behind_the_margin_bf16_reads_alike_on_every_seed_and_float8_apart():
    """The comparison the benchmark's ``correct`` makes (largest logit error
    over the last 8 positions, in spreads of the reference's logits), four
    seeds, top 4 of 32 with 8 held: the bf16 program's readings stay together
    (no seed's is its router's), and the reference on matrices kept to
    float8's 3 mantissa bits, range kept, reads several times the largest."""
    from tests.unit.chipbench.test_chipbench_hybrid import rounded_matrices
    program, coarse = [], []
    for seed in range(4):
        eng, model = _homes_engine(seed, "bfloat16", home_random_routers=True)
        ids = st.ids(64, seed=seed, vocab=1024)[0]
        at = np.arange(56, 64)
        want = REF.next_token_logits(eng.params, model, ids, at)
        spread = float(want.std(axis=-1).mean())
        got = np.asarray(eng.forward(ids[None])[0, -8:], np.float32)
        program.append(float(np.abs(got - want).max()) / spread)
        low = REF.next_token_logits(rounded_matrices(eng.params, "float8_e4m3fn"),
                                    model, ids, at)
        coarse.append(float(np.abs(low - want).max()) / spread)
    assert max(program) < 2 * min(program), program
    assert min(coarse) > 3 * max(program), (program, coarse)
