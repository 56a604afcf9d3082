"""LFM2's mixture-of-experts model (``models/causal_lm.py: lfm2_moe_cfg``:
gated short convolutions and attention, a dense feed-forward and then experts
behind a sigmoid router with an expert bias) at a small size on the CPU,
seeded random weights, against the plain float32 reference the benchmark keeps
(``benchmarks/chipbench/reference/lfm2_moe.py``): each kind of layer, the whole
forward logit by logit, the convolution's state under right padding and token
by token, prefill then decode through the pool against the reference's full
forward, slot recycling, and what the scheduler refuses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.unit import lfm2_tiny as lt
from tests.unit.inference.test_hybrid_model import _prefill, _served_logits

REF = lt.reference()
TOL = 2e-5          # float32 both sides; logits spread ~2


@pytest.fixture(scope="module")
def tiny():
    cfg = lt.config()
    module, params = lt.init(cfg)
    return cfg, module, params


@pytest.mark.parametrize("kind,index", [("C", 0), ("F", 1), ("*", 4), ("E", 5)])
def test_each_kind_of_layer_agrees_with_the_reference(tiny, kind, index):
    from deepspeed_tpu.models.causal_lm import make_layer
    cfg, _, params = tiny
    assert cfg.layer_kind(index) == kind and cfg.head_dim == 16
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 21, cfg.n_embd), jnp.float32)
    pos = jnp.arange(21)[None]
    lp = params[f"layers_{index}"]
    got, _ = jax.jit(lambda v, x, pos: make_layer(cfg, index).apply(
        v, x, pos, mutable=["stats"])[0])({"params": lp}, x, pos)
    fn = {"C": REF.conv_layer, "*": REF.attention_layer, "F": REF.ffn_layer,
          "E": REF.moe_layer}[kind]
    want = fn(x[0], lp, REF._Frozen(lt.MODEL))
    change = float(jnp.abs(want - x[0]).max())             # what the mixer adds
    assert change > 1e-2
    assert float(jnp.abs(got[0] - want).max()) < 1e-4 * change


def test_the_forward_agrees_with_the_reference_logit_by_logit(tiny):
    _, module, params = tiny
    ids = lt.ids(37)
    got = jax.jit(module.apply)({"params": params}, jnp.asarray(ids))[0]
    want = REF.forward(params, lt.MODEL, ids[0])
    assert float(want.std()) > 0.5
    assert float(jnp.abs(got - want).max()) < TOL * float(want.std())
    blocks = REF.next_token_logits(params, lt.MODEL, ids[0], np.arange(30, 37),
                                   vocab_block=100, pad_to=16)
    assert float(np.abs(np.asarray(want[30:37]) - blocks).max()) < TOL * float(want.std())


@pytest.mark.parametrize("dropped", ["expert_bias", "gate_C", "tied_head", "qk_norm",
                                     "silu_in_the_convolution"])
def test_a_model_that_drops_a_term_disagrees_by_whole_spreads(tiny, dropped, monkeypatch):
    """Each term of section A matters to the comparison: the program without
    it (or with the SiLU that Mamba's convolution has and LFM2's has not) lies
    far off the reference."""
    from deepspeed_tpu.models import short_conv
    from deepspeed_tpu.models.causal_lm import CausalLM
    _, _, params = tiny
    cfg = lt.config()
    params = jax.tree_util.tree_map(lambda a: a, params)
    if dropped == "expert_bias":
        for lp in params.values():
            if isinstance(lp, dict) and "moe" in lp:
                lp["moe"]["router_bias"] = jnp.zeros_like(lp["moe"]["router_bias"])
    if dropped == "qk_norm":
        cfg.qk_norm = False
    if dropped == "tied_head":
        cfg.tie_word_embeddings = False
        params["lm_head"] = {"kernel": jax.random.normal(
            jax.random.PRNGKey(5), (64, 256)) * 0.3}
    if dropped == "gate_C":
        whole = short_conv.short_conv
        monkeypatch.setattr(short_conv, "short_conv",
                            lambda v, w, s=None: (jnp.ones_like(v), whole(v, w, s)[1]))
    if dropped == "silu_in_the_convolution":
        whole = short_conv.short_conv
        monkeypatch.setattr(short_conv, "short_conv", lambda v, w, s=None: (
            jax.nn.silu(whole(v, w, s)[0]), whole(v, w, s)[1]))
    ids = lt.ids(24)
    got = np.asarray(CausalLM(cfg).apply({"params": params}, ids)[0])
    want = np.asarray(REF.forward(tiny[2], lt.MODEL, ids[0]))
    assert np.abs(got - want).max() > 0.3 * want.std(), dropped


def test_the_builder_lays_the_published_keys_out_as_pairs_of_mixer_layers(tiny):
    from deepspeed_tpu.models.causal_lm import (LAYER_KINDS, PATTERN_KINDS, init_cache,
                                                lfm2_moe_cfg)
    cfg, _, params = tiny
    assert cfg.layer_kinds == lt.PATTERN and cfg.n_layer == 8
    assert cfg.num_params() == sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert cfg.tie_word_embeddings and "lm_head" not in params
    assert (cfg.moe_kind, cfg.moe_router, cfg.moe_topk_eps) == ("gated", "sigmoid_bias", 1e-6)
    caches = init_cache(cfg, 2, 16)
    assert [sorted(c) for c in caches] == [["conv"], [], ["conv"], [], ["k", "v"], [],
                                           ["conv"], []]
    assert caches[0]["conv"].shape == (2, 2, 64) and caches[4]["k"].shape == (2, 2, 16, 16)
    assert cfg.slot_state_layers == ("short-convolution",) and not cfg.kv_every_layer
    assert set(PATTERN_KINDS) == set(LAYER_KINDS) - {"A"} >= set("*LMCEF")   # PR 59 added rows
    # the published 24 layers, and the 12 the benchmark's stage keeps
    published = dict(lt.MODEL, num_hidden_layers=24, layer_types=(
        ["conv", "conv", "full_attention", "conv"] * 4 + ["conv", "conv",
        "full_attention", "conv", "conv", "full_attention", "conv", "conv"]))
    whole = lfm2_moe_cfg(max_seq_len=64, **published)
    assert whole.layer_pattern == "CFCF*ECE" + "CECE*ECE" * 4 + "CE*ECECE"
    stage = lfm2_moe_cfg(max_seq_len=64, **dict(published, num_hidden_layers=12))
    assert stage.layer_pattern == "CFCF*ECECECE*ECECECE*ECE" == whole.layer_pattern[:24]
    assert (whole.layer_pattern.count("C"), whole.layer_pattern.count("*")) == (18, 6)
    with pytest.raises(NotImplementedError, match="convolution bias"):
        lt.config(conv_bias=True)
    with pytest.raises(ValueError, match="layer_types names 6"):
        lt.config(num_hidden_layers=7)


def test_a_padded_prompt_leaves_the_state_of_its_true_length(tiny):
    """A right-padded prompt: every conv layer's state is the last two
    products ``B * u`` before the TRUE length, whatever the padding holds."""
    from deepspeed_tpu.models.causal_lm import init_cache
    cfg, module, params = tiny
    ids = lt.ids(11)
    lens = jnp.asarray([11])
    outs = []
    for bucket in (11, 16, 32):
        pad = np.zeros((1, bucket), np.int32)
        pad[0, :11] = ids[0]
        pad[0, 11:] = 77                      # padding of any content
        logits, caches = _prefill(module)(
            {"params": params}, jnp.asarray(pad), init_cache(cfg, 1, 48), lens)
        outs.append((logits, caches[0]["conv"], caches[2]["conv"], caches[6]["conv"]))
    for other in outs[1:]:
        for a, b in zip(outs[0], other):
            assert float(jnp.abs(a - b).max()) < TOL
    assert float(jnp.abs(outs[0][1]).max()) > 1e-3
    # a prompt shorter than the window: zeros before position 0
    _, caches = _prefill(module)({"params": params}, jnp.asarray(pad),
                                 init_cache(cfg, 1, 48), jnp.asarray([1]))
    assert float(jnp.abs(caches[0]["conv"][0, 0]).max()) == 0.0
    assert float(jnp.abs(caches[0]["conv"][0, 1]).max()) > 0.0


def test_token_by_token_is_the_sequence_form():
    from deepspeed_tpu.models.short_conv import ShortConvMixer
    mixer = ShortConvMixer(d_model=32, conv_kernel=3, dtype=jnp.float32, init_std=0.3,
                           out_std=0.3)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 32))
    params = mixer.init(jax.random.PRNGKey(0), x)["params"]
    whole, nothing = mixer.apply({"params": params}, x)
    assert nothing is None
    state = {"conv": jnp.zeros((2, 2, 32))}
    for i in range(9):
        step, state = mixer.apply({"params": params}, x[:, i:i + 1], cache=state)
        assert float(jnp.abs(step[:, 0] - whole[:, i]).max()) < 1e-5
    # and the state a prefill leaves is the one the steps arrived at
    _, left = mixer.apply({"params": params}, x, cache={"conv": jnp.zeros((2, 2, 32))},
                          seq_lens=jnp.asarray([9, 9]))
    assert float(jnp.abs(left["conv"] - state["conv"]).max()) < 1e-6


def test_prefill_then_decode_through_the_pool_is_the_references_forward(tiny):
    cfg, module, params = tiny
    ids = lt.ids(30, seed=3)[0]
    got, pool = _served_logits(cfg, module, params, ids, 13)
    want = REF.forward(params, lt.MODEL, ids)[12:]
    assert got.shape == want.shape
    assert float(jnp.abs(got - want).max()) < TOL * float(want.std())
    assert [sorted(c) for c in pool.caches] == [["conv"], [], ["conv"], [], ["k", "v"],
                                                [], ["conv"], []]
    # the conv state lives in the per-slot state pool, beside one layer's pages
    assert pool.kv_layers == 1 and pool.state_nbytes == 3 * 3 * 2 * 64 * 4


def test_a_released_slot_is_cleared_and_a_recycled_one_leaks_nothing(tiny):
    cfg, module, params = tiny
    ids = lt.ids(20, seed=5)[0]
    _, pool = _served_logits(cfg, module, params, ids, 9)
    assert float(jnp.abs(pool.caches[0]["conv"][1]).max()) > 0
    pool.release(1)
    for c in pool.caches:
        if "conv" in c:
            assert float(jnp.abs(c["conv"][1]).max()) == 0.0
    # a slot that is given out again is written whole by the next prefill:
    # what the rows of other requests leave in it in between reaches no one
    from deepspeed_tpu.models.causal_lm import init_cache
    dirty = [{k: jnp.full_like(v, 9.0) for k, v in c.items()} if "conv" in c else c
             for c in pool.caches]
    pool.caches = dirty
    slot = pool.acquire(tokens=32)
    pad = np.zeros((1, 16), np.int32)
    pad[0, :9] = ids[:9]
    _, one = _prefill(module)({"params": params}, jnp.asarray(pad),
                              init_cache(cfg, 1, 64), jnp.asarray([9]))
    pool.scatter_prefill(slot, one)
    for c, o in zip(pool.caches, one):
        if "conv" in c:
            assert float(jnp.abs(c["conv"][slot] - o["conv"][0]).max()) == 0.0


def _engine(**over):
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    return InferenceEngine(lt.config(**over), DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=64), seed=3)


def test_the_scheduler_serves_it_and_a_recycled_slot_gives_the_same_tokens():
    from deepspeed_tpu.inference.serving.prefix_cache import PrefixCacheConfig
    from deepspeed_tpu.inference.serving.scheduler import (
        ContinuousBatchingScheduler, ServingConfig)
    from deepspeed_tpu.observability.trace import get_tracer
    eng = _engine(level_random_experts=True)
    assert eng.model_config.num_params() == sum(
        a.size for a in jax.tree_util.tree_leaves(eng.params))
    prompts = [lt.ids(n, seed=n)[0] for n in (5, 13, 16, 9, 21)]
    tracer = get_tracer()
    tracer.reset()
    tracer.enable()
    try:
        sched = ContinuousBatchingScheduler(eng, ServingConfig(
            slots=2, chunk_size=4, max_seq_len=64, max_queue=8, kv_page_size=8,
            prefix_cache=PrefixCacheConfig(enabled=False)))
        # five requests through two slots: every slot is recycled
        handles = [sched.submit(p, max_new_tokens=6 + i) for i, p in enumerate(prompts)]
        sched.run()
        spans = list(tracer.spans)
    finally:
        tracer.disable()
        tracer.reset()
    for p, h in zip(prompts, handles):
        alone = eng.generate(p[None], max_new_tokens=len(h.tokens))[0, p.size:]
        assert list(h.tokens) == [int(t) for t in alone], p.size
    # the first token of a request is the argmax of the reference's logits
    want = REF.next_token_logits(eng.params, lt.MODEL, prompts[2], [prompts[2].size - 1],
                                 vocab_block=128, pad_to=16)
    assert int(want[0].argmax()) == handles[2].tokens[0]
    # the expert layers' counts ride the spans; the pool's phase counts the conv state
    chunks = [s["attrs"] for s in spans if s["name"] == "serving.decode_chunk"]
    assert chunks and all(c["moe_experts_touched"] <= c["moe_assignments"] for c in chunks)
    assert all("moe_assignments" in s["attrs"] for s in spans
               if s["name"] == "serving.prefill")
    (pool,) = [s["attrs"] for s in spans if s["name"] == "setup.kv_pool"]
    assert pool["state_bytes"] == sched.executor.pool.state_nbytes == 2 * 3 * 2 * 64 * 4
    assert 0 < sched.telemetry.moe_experts_touched <= sched.telemetry.moe_assignments


@pytest.mark.parametrize("bad,match", [
    (dict(prefix_cache="on"), "prefix_cache.enabled"),
    (dict(speculate=True), "speculate"),
])
def test_the_scheduler_refuses_what_a_convolutions_state_cannot_do(bad, match):
    """The refusal asks the configuration which layers keep a per-slot state
    (``LAYER_KINDS``), not for a letter: a model with "C" layers and no "M"."""
    from deepspeed_tpu.inference.serving.prefix_cache import PrefixCacheConfig
    from deepspeed_tpu.inference.serving.scheduler import (
        ContinuousBatchingScheduler, ServingConfig)
    kw = dict(slots=2, max_seq_len=64)
    if "prefix_cache" in bad:
        kw["prefix_cache"] = PrefixCacheConfig(enabled=True)
    else:
        kw.update(bad, prefix_cache=PrefixCacheConfig(enabled=False))
    with pytest.raises(ValueError, match=match) as err:
        ContinuousBatchingScheduler(_engine(), ServingConfig(**kw))
    assert all(word in str(err.value)
               for word in ("short-convolution", "per-slot state", "snapshots"))


def test_a_short_convolution_refuses_a_prefill_at_an_offset(tiny):
    from deepspeed_tpu.models.causal_lm import init_cache
    cfg, module, params = tiny
    with pytest.raises(NotImplementedError, match="short-convolution.*cache offset"):
        module.apply({"params": params}, jnp.zeros((1, 4), jnp.int32),
                     caches=init_cache(cfg, 1, 16), cache_lens=jnp.asarray([4]),
                     prefix_fill=True)


# --------------------------------------------- rows of two KV heads at head 64 (PR 42)
HEAD64 = {2: dict(hidden_size=256, num_attention_heads=4, num_key_value_heads=2),
          8: dict(hidden_size=512, num_attention_heads=8, num_key_value_heads=8)}


@pytest.mark.parametrize("hk", [2, 8])
def test_at_head_64_the_pool_keeps_two_heads_a_row_and_serves_the_same(hk):
    """LFM2's attention at its published head size, 64 (the tiny model's is
    16, whose two KV heads fill no row): the pool's pages and ``init_cache``'s
    cache hold two KV heads in a 128-lane row, prefill then decode through
    the pool stays inside this file's limit against the reference's whole
    forward, and the scheduler gives ``engine.generate``'s tokens."""
    from deepspeed_tpu.inference.serving.prefix_cache import PrefixCacheConfig
    from deepspeed_tpu.inference.serving.scheduler import (
        ContinuousBatchingScheduler, ServingConfig)
    from deepspeed_tpu.models.causal_lm import init_cache
    over = HEAD64[hk]
    cfg = lt.config(**over)
    assert cfg.head_dim == 64 and cfg.kv_heads == hk
    module, params = lt.init(cfg)
    ids = lt.ids(30, seed=3)[0]
    got, pool = _served_logits(cfg, module, params, ids, 13)
    want = REF.forward(params, {**lt.MODEL, **over}, ids)[12:]
    assert got.shape == want.shape
    assert float(jnp.abs(got - want).max()) < TOL * float(want.std())
    assert pool.heads_per_row == 2
    (pages,) = [c for c in pool.caches if "k" in c]
    assert pages["k"].shape[1:] == (hk // 2, 8, 128)
    (cache,) = [c for c in init_cache(cfg, 3, 64) if "k" in c]
    assert cache["k"].shape == (3, hk // 2, 64, 128)
    eng = _engine(level_random_experts=True, **over)
    sched = ContinuousBatchingScheduler(eng, ServingConfig(
        slots=2, chunk_size=4, max_seq_len=64, max_queue=8, kv_page_size=8,
        prefix_cache=PrefixCacheConfig(enabled=False)))
    prompts = [lt.ids(n, seed=n)[0] for n in (5, 16, 9)]
    handles = [sched.submit(p, max_new_tokens=6 + i) for i, p in enumerate(prompts)]
    sched.run()
    for p, h in zip(prompts, handles):
        alone = eng.generate(p[None], max_new_tokens=len(h.tokens))[0, p.size:]
        assert list(h.tokens) == [int(t) for t in alone], p.size
    want = REF.next_token_logits(eng.params, {**lt.MODEL, **over}, prompts[1],
                                 [prompts[1].size - 1], vocab_block=128, pad_to=16)
    assert int(want[0].argmax()) == handles[1].tokens[0]


@pytest.mark.parametrize("speculate", [False, True], ids=["prefix-hit", "verify-round"])
def test_a_gpt2_shaped_head_64_model_through_a_prefix_hit_and_a_verify_round(speculate):
    """GPT-2's shape (every head its own keys, 64 wide: rows of two heads,
    ``g`` = 1 a head): a prefill at an offset over bound prefix pages and the
    speculative verify round both read the dense view in rows with packed
    queries, and give ``engine.generate``'s tokens."""
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.serving.prefix_cache import PrefixCacheConfig
    from deepspeed_tpu.inference.serving.scheduler import (
        ContinuousBatchingScheduler, ServingConfig)
    from deepspeed_tpu.models.causal_lm import gpt2_cfg
    eng = InferenceEngine(
        gpt2_cfg(vocab_size=96, max_seq_len=64, n_embd=256, n_layer=2, n_head=4,
                 dtype=jnp.float32),
        DeepSpeedInferenceConfig(dtype="float32", max_out_tokens=48), seed=5)
    sched = ContinuousBatchingScheduler(eng, ServingConfig(
        slots=2, chunk_size=3, max_seq_len=48, kv_page_size=8, speculate=speculate,
        spec_k=4, prefix_cache=PrefixCacheConfig(
            min_hit_tokens=4, min_insert_tokens=4, insert_on="prefill")))
    assert sched.executor.pool.heads_per_row == 2
    rng = np.random.default_rng(7)
    shared = rng.integers(0, 96, size=13).astype(np.int32)     # ends inside a page: a COW
    unit = rng.integers(0, 96, size=3).astype(np.int32)
    prompts = [np.concatenate([shared, np.tile(unit, 3)]),
               np.concatenate([shared, np.tile(unit, 2), unit[:2]])]
    hs = []
    for p in prompts:
        hs.append(sched.submit(p, max_new_tokens=9))
        sched.run()
    assert hs[0].prefix_hit_tokens == 0 and hs[1].prefix_hit_tokens > 0
    for p, h in zip(prompts, hs):
        alone = np.asarray(eng.generate(p[None], max_new_tokens=9))[0, p.size:]
        np.testing.assert_array_equal(h.result(), alone)
    if speculate:
        assert sched.telemetry.snapshot()["spec_accepted"] > 0
