"""Granite 4.0's hybrid without experts (``models/causal_lm.py:
granite_hybrid_cfg``: Mamba-2 with one state group and attention without
positions, a gated feed-forward after every mixer, four scalar multipliers, a
tied head) at a small size on the CPU, seeded random weights, against the
plain float32 reference the benchmark keeps
(``benchmarks/chipbench/reference/granite_hybrid.py``): each kind of layer, the
whole forward logit by logit, each multiplier and each attention path, prefill
under right padding then decode through the pool and the scheduler, slot
recycling under its span, and what the builder and the scheduler refuse."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.unit import granite_tiny as gt
from tests.unit.inference.test_hybrid_model import _decode, _prefill, _served_logits

REF = gt.reference()
TOL = 1e-4          # float32 both sides, in spreads of the reference's logits (~0.3)
# the catalog row's ``config`` (model-configs guide, architectures.jsonl)
PUBLISHED = dict(
    attention_bias=False, attention_multiplier=0.015625, embedding_multiplier=12,
    hidden_act="silu", hidden_size=2048, intermediate_size=8192,
    layer_types=(["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    logits_scaling=8, mamba_chunk_size=256, mamba_conv_bias=True, mamba_d_conv=4,
    mamba_d_head=64, mamba_d_state=128, mamba_expand=2, mamba_n_groups=1,
    mamba_n_heads=64, mamba_proj_bias=False, max_position_embeddings=131072,
    model_type="granitemoehybrid", normalization_function="rmsnorm",
    num_attention_heads=32, num_experts_per_tok=0, num_hidden_layers=40,
    num_key_value_heads=8, num_local_experts=0, position_embedding_type="nope",
    residual_multiplier=0.22, rms_norm_eps=1e-05, rope_scaling=None, rope_theta=10000,
    shared_intermediate_size=8192, tie_word_embeddings=True, vocab_size=100352)


@pytest.fixture(scope="module")
def tiny():
    cfg = gt.config()
    module, params = gt.init(cfg)
    return cfg, module, params


@pytest.mark.parametrize("kind,index,groups", [("M", 0, 1), ("M", 0, 2), ("F", 1, 1),
                                               ("*", 2, 1)])
def test_each_kind_of_layer_agrees_with_the_reference(kind, index, groups):
    """``groups`` 1 is the published Mamba-2: ONE B and ONE C a token serve all
    8 heads (the reference broadcasts them by hand) and the gated norm runs
    over the whole inner width; 2 shows the same code at another grouping."""
    from deepspeed_tpu.models.causal_lm import make_layer
    cfg = gt.config(mamba_n_groups=groups)
    _, params = gt.init(cfg)
    model = {**gt.MODEL, "mamba_n_groups": groups}
    assert cfg.layer_kind(index) == kind and cfg.ssm_n_groups == groups
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 21, cfg.n_embd), jnp.float32)
    pos = jnp.arange(21)[None]
    lp = params[f"layers_{index}"]
    got, _ = jax.jit(make_layer(cfg, index).apply)({"params": lp}, x, pos)
    frozen = REF._Frozen(model)
    want = REF.mlp_layer(x[0], lp, frozen) if kind == "F" else REF.mixer_layer(
        x[0], lp, {"M": "mamba", "*": "attention"}[kind], frozen)
    change = float(jnp.abs(want - x[0]).max())             # what the layer adds
    assert change > 1e-2
    assert float(jnp.abs(got[0] - want).max()) < 1e-4 * change


def test_the_forward_agrees_with_the_reference_logit_by_logit(tiny):
    _, module, params = tiny
    ids = gt.ids(37)
    got = jax.jit(module.apply)({"params": params}, jnp.asarray(ids))[0]
    want = REF.forward(params, gt.MODEL, ids[0])
    assert float(want.std()) > 0.2
    assert float(jnp.abs(got - want).max()) < TOL * float(want.std())
    blocks = REF.next_token_logits(params, gt.MODEL, ids[0], np.arange(30, 37),
                                   vocab_block=100, pad_to=16)
    assert float(np.abs(np.asarray(want[30:37]) - blocks).max()) < TOL * float(want.std())


@pytest.mark.parametrize("dropped", sorted(gt.DEFAULTS))
def test_a_model_that_drops_a_multiplier_fails_the_comparison(tiny, dropped):
    """Each of the four scalars set to what every other family has (1, 1,
    ``1 / sqrt(head size)``, 1) makes the comparison FAIL, by a thousand times
    its tolerance at the least (the attention scale, one mixer in four here,
    reads 0.3 spreads; the other three whole spreads): none can be lost inside
    a tolerance."""
    from deepspeed_tpu.models.causal_lm import CausalLM
    _, _, params = tiny
    cfg = gt.config(**{dropped: gt.DEFAULTS[dropped]})
    ids = gt.ids(37)
    got = np.asarray(CausalLM(cfg).apply({"params": params}, ids)[0])
    want = np.asarray(REF.forward(params, gt.MODEL, ids[0]))
    least = 0.1 if dropped == "attention_multiplier" else 3.0
    assert np.abs(got - want).max() > least * want.std() >= 1000 * TOL * want.std()


def _attention_only(**over):
    """Attention in every published layer: the one model all four cache modes
    can run (a Mamba layer refuses a prefill at an offset)."""
    model = {**gt.MODEL, "layer_types": ["attention"] * 4, **over}
    cfg = gt.config(max_seq_len=512, **{k: model[k] for k in ("layer_types", *over)})
    return cfg, model


def _paths(cfg, module, params, ids):
    """The logits at the last 8 positions of ``ids`` (1, t), by the path the
    test names, and the positions they are at."""
    from deepspeed_tpu.models.causal_lm import init_cache
    t = ids.shape[1]
    v = {"params": params}

    def whole():
        return module.apply(v, jnp.asarray(ids))[0, -8:]

    def cached():
        # prefill all but the last 8, then one token a step on the dense
        # cache (``decode_attention``)
        lens = jnp.asarray([t - 8])
        logits, caches = _prefill(module)(v, jnp.asarray(ids[:, :t - 8]),
                                          init_cache(cfg, 1, 64), lens)
        rows, step = [], _decode(module)
        for i in range(t - 8, t):
            logits, caches = step(v, jnp.asarray(ids[:, i:i + 1]), caches, lens)
            rows.append(logits[0, 0])
            lens = lens + 1
        return jnp.stack(rows)

    def at_an_offset():
        # the first t - 8 tokens are in the cache; the last 8 are a suffix
        lens = jnp.asarray([t - 8])
        _, caches = _prefill(module)(v, jnp.asarray(ids[:, :t - 8]),
                                     init_cache(cfg, 1, 64), lens)
        logits, _ = module.apply(
            v, jnp.asarray(ids[:, t - 8:]), positions=lens[:, None] + jnp.arange(8)[None],
            caches=caches, cache_lens=lens, prefix_fill=True,
            logits_positions=jnp.arange(8)[None])
        return logits[0]

    return {"xla_prefill": whole, "flash_prefill": whole, "decode": cached,
            "prefill_at_an_offset": at_an_offset}


@pytest.mark.parametrize("path,t", [("xla_prefill", 24), ("flash_prefill", 256),
                                    ("decode", 24), ("prefill_at_an_offset", 24)])
def test_the_attention_multiplier_reaches_every_attention_path(path, t):
    """The configuration's ONE scale (``CausalLMConfig.attn_scale``) is what
    the XLA products, the flash kernel, the decode kernel
    and the prefill at an offset multiply the scores by: each path agrees with
    the reference at the published kind of value, and a model left at ``1 /
    sqrt(head size)`` is told apart on that very path."""
    from deepspeed_tpu.models.causal_lm import CausalLM
    from deepspeed_tpu.ops.transformer.attention import flash_eligible
    assert flash_eligible(t) == (path == "flash_prefill")
    cfg, model = _attention_only()
    assert cfg.attn_scale == 0.015625 != cfg.head_dim ** -0.5
    module, params = gt.init(cfg)
    ids = gt.ids(t, seed=4)
    want = np.asarray(REF.forward(params, model, ids[0]))[-8:]
    got = np.asarray(_paths(cfg, module, params, ids)[path]())
    assert np.abs(got - want).max() < TOL * want.std(), path
    usual, _ = _attention_only(attention_multiplier=gt.DEFAULTS["attention_multiplier"])
    off = np.asarray(_paths(usual, CausalLM(usual), params, ids)[path]())
    assert np.abs(off - want).max() > 0.05 * want.std(), path


def test_every_attention_function_takes_the_scale_it_is_handed():
    """The three XLA paths of ``causal_lm.py`` that once wrote ``1 /
    sqrt(d)`` themselves use the scale they are handed, and the model's
    private helpers have NO default for it: a caller that forgets the
    argument fails instead of serving Granite at ``1 / sqrt(d)``. Only the
    flash-alibi kernel's reference keeps the kernel's default."""
    import inspect
    from deepspeed_tpu.models import causal_lm as clm
    from deepspeed_tpu.ops.attention.decode import (decode_attention_live,
                                                    decode_attention_xla)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 8, 4, 128))
    k = jax.random.normal(ks[1], (1, 8, 4, 128))
    val = jax.random.normal(ks[2], (1, 8, 4, 128))
    slopes = jnp.asarray(clm.alibi_slopes(4))
    hm = lambda a: a.transpose(0, 2, 1, 3)                 # (b, h, T, d) cache rows
    lens = jnp.asarray([8])
    calls = {
        "alibi": lambda q, s: clm._alibi_attention_xla(q, k, val, slopes, s),
        "prefix": lambda q, s: clm._prefix_attention_xla(
            q, hm(k), hm(val), jnp.asarray([0]), None, s),
        "decode_alibi": lambda q, s: decode_attention_live(
            q[:, -1], hm(k), hm(val), lens, s, slopes),
        "decode_alibi_plain": lambda q, s: decode_attention_xla(
            q[:, -1], hm(k), hm(val), lens, s, slopes),
    }
    for name, call in calls.items():
        for s in (128 ** -0.5, 1.0 / 128):
            assert float(jnp.abs(call(q, s) - call(q * s, 1.0)).max()) < 1e-5, name
        assert float(jnp.abs(call(q, 1.0 / 128) - call(q, 128 ** -0.5)).max()) > 1e-2, name
    usual = calls["alibi"](q, None)
    assert float(jnp.abs(calls["alibi"](q, 128 ** -0.5) - usual).max()) < 1e-6
    for fn in (clm._bias_attention, clm._prefix_attention_xla, clm._sharded_decode):
        assert inspect.signature(fn).parameters["scale"].default is inspect.Parameter.empty
    assert not hasattr(clm, "_softmax_scale")


def test_the_builder_lays_the_published_keys_out_as_pairs_of_mixer_layers(tiny):
    from deepspeed_tpu.models.causal_lm import CausalLMConfig, granite_hybrid_cfg, init_cache
    cfg, _, params = tiny
    assert cfg.layer_kinds == gt.PATTERN and cfg.n_layer == 8
    assert cfg.num_params() == sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert cfg.tie_word_embeddings and "lm_head" not in params
    caches = init_cache(cfg, 2, 16)
    assert [sorted(c) for c in caches] == [["conv", "ssm"], [], ["k", "v"], [],
                                           ["conv", "ssm"], [], ["conv", "ssm"], []]
    assert caches[0]["ssm"].shape == (2, 8, 16, 16) and caches[0]["ssm"].dtype == jnp.float32
    assert caches[0]["conv"].shape == (2, 3, 128 + 2 * 16)
    assert cfg.slot_state_layers == ("state-space",) and not cfg.kv_every_layer
    # the published model, every key of the catalog row's config passed as it is
    whole = granite_hybrid_cfg(max_seq_len=2048, **PUBLISHED)
    assert len(whole.layer_pattern) == 80 and whole.layer_pattern[1::2] == "F" * 40
    assert [i for i, k in enumerate(whole.layer_pattern) if k == "*"] == [10, 30, 50, 70]
    assert whole.layer_pattern.count("M") == 36
    assert whole.num_params() == 3_191_396_096
    assert 36 * 25_849_280 + 4 * 10_487_808 + 40 * 50_333_696 + 100_352 * 2_048 + 2_048 \
        == 3_191_396_096
    assert (whole.head_dim, whole.kv_heads, whole.pos_emb) == (64, 8, "none")
    assert (whole.ssm_n_groups, whole.ssm_chunk_size, whole.conv_dim) == (1, 256, 4352)
    assert (whole.embedding_multiplier, whole.residual_multiplier, whole.attn_scale,
            whole.logits_scaling) == (12.0, 0.22, 0.015625, 8.0)
    # every other family has the four at 1, 1, 1 / sqrt(d), 1
    plain = CausalLMConfig(n_embd=256, n_head=4)
    assert (plain.embedding_multiplier, plain.residual_multiplier,
            plain.attention_multiplier, plain.logits_scaling) == (1.0, 1.0, None, 1.0)
    assert plain.attn_scale == 64 ** -0.5
    with pytest.raises(ValueError, match="classic layer"):
        CausalLMConfig(residual_multiplier=0.22)
    with pytest.raises(ValueError, match="layer_types names 4 layers"):
        gt.config(num_hidden_layers=5)


@pytest.mark.parametrize("key,value", [("num_local_experts", 8),
                                       ("position_embedding_type", "rope"),
                                       ("mamba_proj_bias", True),
                                       ("attention_bias", True)])
def test_the_builder_refuses_what_it_does_not_build(key, value):
    with pytest.raises(NotImplementedError, match=key):
        gt.config(**{key: value})


def test_padding_does_not_advance_the_recurrence(tiny):
    """A right-padded prompt: the state is the one after the last real token
    and the window holds the last 3 real inputs, whatever the padding."""
    from deepspeed_tpu.models.causal_lm import init_cache
    cfg, module, params = tiny
    ids = gt.ids(11)
    lens = jnp.asarray([11])
    outs = []
    for bucket in (11, 16, 32):
        pad = np.zeros((1, bucket), np.int32)
        pad[0, :11] = ids[0]
        pad[0, 11:] = 77                      # padding of any content
        logits, caches = _prefill(module)(
            {"params": params}, jnp.asarray(pad), init_cache(cfg, 1, 48), lens)
        outs.append((logits, caches[0]["ssm"], caches[0]["conv"], caches[6]["ssm"]))
    for other in outs[1:]:
        for a, b in zip(outs[0], other):
            assert float(jnp.abs(a - b).max()) < 1e-5
    assert float(jnp.abs(outs[0][1]).max()) > 1e-3


def test_prefill_then_decode_through_the_pool_is_the_references_forward(tiny):
    """13 tokens prefilled under right padding into slot 1 of 3, then 17
    decode steps through the pool: logits, not tokens."""
    cfg, module, params = tiny
    ids = gt.ids(30, seed=3)[0]
    got, pool = _served_logits(cfg, module, params, ids, 13)
    want = REF.forward(params, gt.MODEL, ids)[12:]
    assert got.shape == want.shape == (18, 256)
    assert float(jnp.abs(got - want).max()) < TOL * float(want.std())
    assert [sorted(c) for c in pool.caches] == [["conv", "ssm"], [], ["k", "v"], [],
                                                ["conv", "ssm"], [], ["conv", "ssm"], []]
    # three slots of three Mamba layers' state and windows, beside one layer's pages
    assert pool.kv_layers == 1
    assert pool.state_nbytes == 3 * 3 * (8 * 16 * 16 * 4 + 3 * 160 * 4)


@pytest.mark.parametrize("fault", ["state_left_unchanged", "a_neighbours_state"])
def test_a_planted_fault_of_the_state_update_fails_the_decode_comparison(
        tiny, fault, monkeypatch):
    """What the cell's served-token limit is read against on the chip (PERF.md
    section 6, PR 44: a one-token update that returns the state it was given),
    here logit by logit at the small width: the decode comparison that the
    right update passes inside ``TOL`` reads whole hundredths of a spread when
    the update leaves the state as it was, or hands back the next slot's."""
    from deepspeed_tpu.models import mamba2
    cfg, module, params = tiny
    right = mamba2.ssm_step

    def planted(state, *rest):
        y, new = right(state, *rest)
        return (y, state) if fault == "state_left_unchanged" else (
            y, jnp.roll(new, 1, axis=0))

    monkeypatch.setattr(mamba2, "ssm_step", planted)
    ids = gt.ids(30, seed=3)[0]
    got, _ = _served_logits(cfg, module, params, ids, 13)
    want = REF.forward(params, gt.MODEL, ids)[12:]
    # the prefill's logits do not pass through the one-token update
    assert float(jnp.abs(got[0] - want[0]).max()) < TOL * float(want.std())
    assert float(jnp.abs(got[2:] - want[2:]).max()) > 100 * TOL * float(want.std())


def test_the_output_projections_scale_is_the_configurations():
    """``out_init_std``: the matrices that write to the residual stream
    (``o_proj``, the mixer's ``out_proj``, ``fc_out``) are drawn at it; left
    out, at ``init_std / sqrt(2 n_layer)`` as every other family's are. The
    cell's configuration sets it (0.04), because under a tied head and an
    embedding multiplier of 12 the smaller scale leaves a random stand-in
    repeating its input token whatever its mixers do."""
    import json
    import os
    from deepspeed_tpu.models.causal_lm import CausalLM
    with open(os.path.join(gt.REPO, "benchmarks", "chipbench", "configs",
                           "granite-4.0-h-micro.json")) as f:
        doc = json.load(f)
    assert doc["model"]["out_init_std"] == 0.04 and "init_std" not in doc["model"]

    def stds(**over):
        cfg = gt.config(hidden_size=128, shared_intermediate_size=256, mamba_n_heads=16,
                        init_std=0.02, **over)
        p = CausalLM(cfg).init({"params": jax.random.PRNGKey(0)},
                               jnp.zeros((1, 8), jnp.int32))["params"]
        return cfg, {
            "out_proj": float(p["layers_0"]["mamba"]["out_proj"].std()),
            "fc_out": float(p["layers_1"]["fc_out"]["kernel"].std()),
            "o_proj": float(p["layers_2"]["o_proj"]["kernel"].std()),
            "in_proj": float(p["layers_0"]["mamba"]["in_proj"].std()),
            "up_proj": float(p["layers_1"]["up_proj"]["kernel"].std()),
            "wte": float(p["wte"].std())}

    cfg, usual = stds()
    assert cfg.out_init_std is None and cfg.out_std == 0.02 / 16 ** 0.5
    cfg, stated = stds(out_init_std=0.04)
    assert cfg.out_std == 0.04
    for name in ("out_proj", "fc_out", "o_proj"):
        assert abs(usual[name] / 0.005 - 1) < 0.05, (name, usual[name])
        assert abs(stated[name] / 0.04 - 1) < 0.05, (name, stated[name])
    for name in ("in_proj", "up_proj", "wte"):
        assert abs(usual[name] / 0.02 - 1) < 0.05 and usual[name] == stated[name], name


def _engine(dtype="float32", **over):
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    return InferenceEngine(gt.config(**over), DeepSpeedInferenceConfig(
        dtype=dtype, max_out_tokens=64), seed=3)


def test_the_scheduler_serves_it_and_clears_a_released_slot_under_its_span():
    from deepspeed_tpu.inference.serving.prefix_cache import PrefixCacheConfig
    from deepspeed_tpu.inference.serving.scheduler import (
        ContinuousBatchingScheduler, ServingConfig)
    from deepspeed_tpu.observability import schema
    from deepspeed_tpu.observability.trace import get_tracer
    eng = _engine()
    assert eng.model_config.num_params() == sum(
        a.size for a in jax.tree_util.tree_leaves(eng.params))
    prompts = [gt.ids(n, seed=n)[0] for n in (5, 13, 16, 9, 21)]
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
    want = REF.next_token_logits(eng.params, gt.MODEL, prompts[2], [prompts[2].size - 1],
                                 vocab_block=128, pad_to=16)
    assert int(want[0].argmax()) == handles[2].tokens[0]
    pool = sched.executor.pool
    (phase,) = [s["attrs"] for s in spans if s["name"] == "setup.kv_pool"]
    a_slot = 3 * (8 * 16 * 16 * 4 + 3 * 160 * 4)
    assert phase["state_bytes"] == pool.state_nbytes == 2 * a_slot
    # every release of a slot clears its state under a span of its own
    cleared = [s for s in spans if s["name"] == "serving.clear_state"]
    assert len(cleared) == 5 and {s["attrs"]["slot"] for s in cleared} == {0, 1}
    assert all(s["attrs"]["state_bytes"] == a_slot for s in cleared)
    assert schema.SPANS["serving.clear_state"][2] == ("slot", "state_bytes")
    harvests = [s for s in spans if s["name"] == "serving.harvest"]
    for s in cleared:
        assert any(h["ts"] <= s["ts"] and s["ts"] + s["dur"] <= h["ts"] + h["dur"]
                   for h in harvests)
    # cleared at the release itself (a chunk that runs on for the other slot
    # steps the idle row again; the next admission writes it whole)
    slot = pool.acquire(tokens=16)
    pool.caches = [{k: jnp.full_like(a, 9.0) for k, a in c.items()} if "ssm" in c else c
                   for c in pool.caches]
    pool.release(slot)
    for c in pool.caches:
        if "ssm" in c:
            assert float(jnp.abs(c["ssm"][slot]).max()) == 0.0 == float(
                jnp.abs(c["conv"][slot]).max())
            assert float(jnp.abs(c["ssm"][1 - slot]).min()) == 9.0


@pytest.mark.parametrize("bad,match", [
    (dict(prefix_cache="on"), "prefix_cache.enabled"),
    (dict(speculate=True), "speculate"),
])
def test_the_scheduler_refuses_what_a_recurrent_state_cannot_do(bad, match):
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
               for word in ("state-space", "per-slot state", "snapshots"))


def test_generates_decode_loop_updates_the_caches_it_is_given_in_place():
    """``engine.generate`` at ``greedy_decode_rows`` holds ONE copy of its
    caches: the loop returns them, so the donated argument's buffers are the
    loop's carry (at 64 rows of the published model's state a second copy is
    6 GB, more than the chip has beside the weights)."""
    from deepspeed_tpu.models.causal_lm import init_cache
    eng = _engine(greedy_decode_rows=4)
    prompt = gt.ids(13, seed=13)
    out = eng.generate(prompt, max_new_tokens=7)
    assert np.array_equal(out, _engine().generate(prompt, max_new_tokens=7))
    _, loop = eng._loop_fns(False, 1.0, 0, 1.0, 64)
    caches = init_cache(eng.model_config, 4, 64, dtype=eng.dtype)
    leaves = len(jax.tree_util.tree_leaves(caches))
    text = loop.lower(eng.params, jnp.zeros((4, 1), jnp.int32), caches,
                      jnp.full((4,), 13, jnp.int32), np.int32(7), np.int32(-1),
                      jax.random.PRNGKey(0)).as_text()
    assert text.count("tf.aliasing_output") == leaves == 3 * 2 + 2


def test_a_pool_is_dropped_before_its_replacement_is_built(monkeypatch):
    """``reset_pool`` (every ``evict_all``: the harness's parity check, a
    failed dispatch) lets the old pool's arrays go BEFORE it builds the new
    one: at the published sizes two pools of 5.97 GB do not fit beside 6.38 GB
    of weights ("Attempting to allocate 128.00M ... 80.51M free", the chip,
    PR 44)."""
    from deepspeed_tpu.inference.serving.executor import ChunkedDecodeExecutor
    ex = ChunkedDecodeExecutor(_engine(), slots=2, cap=64, chunk_size=4, kv_page_size=8)
    old = ex.pool
    build = ex._build_pool
    seen = []

    def watched():
        seen.append(ex.pool)
        return build()

    monkeypatch.setattr(ex, "_build_pool", watched)
    ex.reset_pool()
    assert seen == [None] and ex.pool is not old and ex.pool.free_slots == 2
