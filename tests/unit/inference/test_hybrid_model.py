"""The hybrid of state-space, attention and expert layers at a small size on
the CPU, seeded random weights, against the plain float32 reference
(``benchmarks/chipbench/reference/nemotron_h.py``): each mixer, the chunked
scan against the token-by-token recurrence (also under right padding), prefill
then decode through the pools against the reference's full forward logit by
logit, slot recycling, and what the scheduler refuses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.unit import hybrid_tiny as ht

REF = ht.reference()
TOL = 2e-5          # float32 both sides; logits spread ~0.3


@pytest.fixture(scope="module")
def tiny():
    cfg = ht.config()
    module, params = ht.init(cfg)
    return cfg, module, params


@pytest.mark.parametrize("kind,index", [("M", 0), ("E", 1), ("*", 3)])
def test_each_mixer_agrees_with_the_reference(tiny, kind, index):
    from deepspeed_tpu.models.causal_lm import make_layer
    cfg, _, params = tiny
    assert cfg.layer_kind(index) == kind
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 21, cfg.n_embd), jnp.float32)
    pos = jnp.arange(21)[None]
    lp = params[f"layers_{index}"]
    got, _ = jax.jit(make_layer(cfg, index).apply)({"params": lp}, x, pos)
    fn = {"M": REF.mamba_layer, "*": REF.attention_layer, "E": REF.moe_layer}[kind]
    want = fn(x[0], lp, REF._Frozen(ht.MODEL))
    change = float(jnp.abs(want - x[0]).max())             # what the mixer adds
    assert change > 1e-3
    assert float(jnp.abs(got[0] - want).max()) < 1e-3 * change


def test_the_forward_agrees_with_the_reference_logit_by_logit(tiny):
    _, module, params = tiny
    ids = ht.ids(37)
    got = jax.jit(module.apply)({"params": params}, jnp.asarray(ids))[0]
    want = REF.forward(params, ht.MODEL, ids[0])
    assert float(jnp.abs(got - want).max()) < TOL
    blocks = REF.next_token_logits(params, ht.MODEL, ids[0], np.arange(30, 37),
                                   vocab_block=100, pad_to=16)
    assert float(np.abs(np.asarray(want[30:37]) - blocks).max()) < TOL


@pytest.mark.parametrize("t,chunk", [(24, 8), (19, 8), (5, 8), (40, 16)])
def test_chunked_scan_is_the_token_by_token_recurrence(t, chunk):
    from deepspeed_tpu.models.mamba2 import ssd_chunked
    from deepspeed_tpu.ops.ssm import ssm_step_xla
    h, p, n, g = 8, 4, 16, 2
    ks = jax.random.split(jax.random.PRNGKey(t), 5)
    x = jax.random.normal(ks[0], (2, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, t, h)) - 2.0)
    B = jax.random.normal(ks[2], (2, t, g, n))
    C = jax.random.normal(ks[3], (2, t, g, n))
    A, D = -jnp.linspace(1.0, 8.0, h), jnp.linspace(0.5, 1.5, h)
    y, last = jax.jit(ssd_chunked, static_argnums=6)(x, dt, A, B, C, D, chunk)
    state = jnp.zeros((2, h, p, n))
    step = jax.jit(ssm_step_xla)
    for i in range(t):
        yi, state = step(state, x[:, i], dt[:, i], A, B[:, i], C[:, i], D)
        assert float(jnp.abs(yi - y[:, i]).max()) < 1e-4
    assert float(jnp.abs(state - last).max()) < 1e-4


def test_padding_does_not_advance_the_recurrence(tiny):
    """A right-padded prompt: the state is the one after the last real token
    and the conv state holds the last 3 real inputs, whatever the padding."""
    from deepspeed_tpu.models.causal_lm import init_cache
    cfg, module, params = tiny
    ids = ht.ids(11)
    lens = jnp.asarray([11])
    outs = []
    for bucket in (11, 16, 32):
        pad = np.zeros((1, bucket), np.int32)
        pad[0, :11] = ids[0]
        pad[0, 11:] = 77                      # padding of any content
        logits, caches = _prefill(module)(
            {"params": params}, jnp.asarray(pad), init_cache(cfg, 1, 48), lens)
        outs.append((logits, caches[0]["ssm"], caches[0]["conv"], caches[2]["ssm"]))
    for other in outs[1:]:
        for a, b in zip(outs[0], other):
            assert float(jnp.abs(a - b).max()) < TOL
    assert float(jnp.abs(outs[0][1]).max()) > 1e-3


def _prefill(module):
    return jax.jit(lambda variables, ids, caches, lens: module.apply(
        variables, ids, caches=caches, cache_lens=jnp.zeros_like(lens),
        logits_positions=lens - 1, seq_lens=lens))


def _decode(module):
    return jax.jit(lambda variables, toks, caches, lens: module.apply(
        variables, toks, positions=lens[:, None], caches=caches, cache_lens=lens))


def _served_logits(cfg, module, params, ids, prompt_len):
    """Prefill ``prompt_len`` tokens (right-padded to a bucket) into slot 1 of
    a pool of 3, then decode the rest one token at a time through the pool."""
    from deepspeed_tpu.inference.serving.kv_pool import PagedKVPool
    from deepspeed_tpu.models.causal_lm import init_cache
    from deepspeed_tpu.ops.paged_attention import (gather_kv_dense,
                                                   write_view_rows)
    cap, slots, slot = 64, 3, 1
    pool = PagedKVPool(cfg, slots, cap, page_size=8)
    for _ in range(slot + 1):
        got = pool.acquire(tokens=cap)
    assert got == slot
    bucket = 32
    pad = np.zeros((1, bucket), np.int32)
    pad[0, :prompt_len] = ids[:prompt_len]
    lens0 = jnp.asarray([prompt_len])
    logits, one = _prefill(module)({"params": params}, jnp.asarray(pad),
                                   init_cache(cfg, 1, cap), lens0)
    pool.scatter_prefill(slot, one)
    rows = [logits[0, 0]]
    decode = _decode(module)
    lens = np.zeros(slots, np.int32)
    lens[slot] = prompt_len
    for i in range(prompt_len, len(ids)):
        toks = np.zeros((slots, 1), np.int32)
        toks[slot, 0] = ids[i]
        # never written again: the step may still be running when the loop goes
        # on (on the CPU a jax array made from a numpy array can alias it)
        toks_d, lens_d = jnp.asarray(toks), jnp.asarray(lens)
        table = jnp.asarray(pool.page_table)
        # the chunk's route: attention over the dense view
        caches = [dict(zip(("k", "v"), gather_kv_dense(
            c["k"], c["v"], table, cap))) if "k" in c else c
            for c in pool.caches]
        logits, new = decode({"params": params}, toks_d, caches, lens_d)
        # mirror the appended row back, as the chunk does: the slot's one
        # new row of the view into its page, a state layer's carry as it is
        paged = [j for j, c in enumerate(pool.caches) if "k" in c]
        written = write_view_rows(
            [pool.caches[j] for j in paged], [new[j] for j in paged], table,
            lens_d, jnp.asarray(np.arange(slots) == slot, jnp.int32), 1, cap)
        out = list(new)
        for j, pages in zip(paged, written):
            out[j] = pages
        pool.caches = out
        rows.append(logits[slot, 0])
        lens = lens + (np.arange(slots) == slot).astype(np.int32)   # a NEW array
    return jnp.stack(rows), pool


def test_prefill_then_decode_through_the_pool_is_the_references_forward(tiny):
    cfg, module, params = tiny
    ids = ht.ids(30, seed=3)[0]
    got, pool = _served_logits(cfg, module, params, ids, 13)
    want = REF.forward(params, ht.MODEL, ids)[12:]
    assert got.shape == want.shape
    assert float(jnp.abs(got - want).max()) < TOL
    kinds = ["ssm" in c for c in pool.caches]
    assert kinds == [True, False, True, False, False]
    assert pool.caches[1] == {} and set(pool.caches[3]) == {"k", "v"}
    assert pool.kv_layers == 1 and pool.state_nbytes == sum(
        int(a.nbytes) for c in pool.caches if "ssm" in c for a in c.values())


def test_a_released_slot_is_cleared_and_a_recycled_one_leaks_nothing(tiny):
    cfg, module, params = tiny
    ids = ht.ids(20, seed=5)[0]
    _, pool = _served_logits(cfg, module, params, ids, 9)
    assert float(jnp.abs(pool.caches[0]["ssm"][1]).max()) > 0
    pool.release(1)
    for c in pool.caches:
        if "ssm" in c:
            assert float(jnp.abs(c["ssm"][1]).max()) == 0.0
            assert float(jnp.abs(c["conv"][1].astype(jnp.float32)).max()) == 0.0


def test_the_scheduler_serves_it_and_a_recycled_slot_gives_the_same_tokens():
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.serving.prefix_cache import PrefixCacheConfig
    from deepspeed_tpu.inference.serving.scheduler import (
        ContinuousBatchingScheduler, ServingConfig)
    cfg = ht.config()
    eng = InferenceEngine(cfg, DeepSpeedInferenceConfig(dtype="float32",
                                                        max_out_tokens=64), seed=3)
    assert eng.model_config.num_params() == sum(
        a.size for a in jax.tree_util.tree_leaves(eng.params))
    prompts = [ht.ids(n, seed=n)[0] for n in (5, 13, 16, 9, 21)]
    sched = ContinuousBatchingScheduler(eng, ServingConfig(
        slots=2, chunk_size=4, max_seq_len=64, max_queue=8, kv_page_size=8,
        prefix_cache=PrefixCacheConfig(enabled=False)))
    # five requests through two slots: every slot is recycled
    handles = [sched.submit(p, max_new_tokens=6 + i) for i, p in enumerate(prompts)]
    sched.run()
    for p, h in zip(prompts, handles):
        alone = eng.generate(p[None], max_new_tokens=len(h.tokens))[0, p.size:]
        assert list(h.tokens) == [int(t) for t in alone], p.size
    assert sched.telemetry.moe_assignments > 0
    assert 0 < sched.telemetry.moe_experts_touched <= sched.telemetry.moe_assignments
    first = int(np.argmax(eng.forward(prompts[2][None])[0, -1]))
    assert first == handles[2].tokens[0]
    # the first token of a request is the argmax of the reference's logits
    want = REF.next_token_logits(eng.params, ht.MODEL, prompts[2], [prompts[2].size - 1],
                                 vocab_block=128, pad_to=16)
    assert int(want[0].argmax()) == first


def test_greedy_decode_rows_adds_rows_that_change_no_token():
    """A configuration may fix the rows a greedy ``generate`` decodes at (on
    the chip a matmul row rounds differently by the matmul's row count; here
    on the CPU it does not, so the tokens must be the same): with or without
    an EOS, and sampling keeps its own batch."""
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    conf = DeepSpeedInferenceConfig(dtype="float32", max_out_tokens=64)
    plain = InferenceEngine(ht.config(), conf, seed=3)
    rows = InferenceEngine(ht.config(greedy_decode_rows=4), conf, seed=3)
    prompt = ht.ids(13, seed=13)
    before = plain.generate(prompt, max_new_tokens=7)
    assert np.array_equal(rows.generate(prompt, max_new_tokens=7), before)
    eos = int(before[0, prompt.shape[1] + 2])
    cut = rows.generate(prompt, max_new_tokens=7, eos_token_id=eos)
    assert cut.shape[1] == prompt.shape[1] + 3 and np.array_equal(
        cut, before[:, :cut.shape[1]])
    sampled = rows.generate(np.concatenate([prompt] * 3), max_new_tokens=4,
                            do_sample=True, seed=5)
    assert sampled.shape == (3, prompt.shape[1] + 4)


@pytest.mark.parametrize("pattern,why", [
    ("MEM*E", ("state", "snapshots")),      # a recurrent state
    ("*E*E", ("expert layers", "keys and values")),   # no M: a layer with no cache
])
@pytest.mark.parametrize("bad,match", [
    (dict(prefix_cache="on"), "prefix_cache.enabled"),
    (dict(speculate=True), "speculate"),
])
def test_the_scheduler_refuses_what_a_layer_without_keys_and_values_cannot_do(
        bad, match, pattern, why):
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.serving.prefix_cache import PrefixCacheConfig
    from deepspeed_tpu.inference.serving.scheduler import (
        ContinuousBatchingScheduler, ServingConfig)
    eng = InferenceEngine(ht.config(hybrid_override_pattern=pattern),
                          DeepSpeedInferenceConfig(dtype="float32", max_out_tokens=64),
                          seed=1)
    kw = dict(slots=2, max_seq_len=64)
    if "prefix_cache" in bad:
        kw["prefix_cache"] = PrefixCacheConfig(enabled=True)
    else:
        kw.update(bad, prefix_cache=PrefixCacheConfig(enabled=False))
    with pytest.raises(ValueError, match=match) as err:
        ContinuousBatchingScheduler(eng, ServingConfig(**kw))
    assert all(word in str(err.value) for word in why)


def test_a_pattern_without_state_space_layers_is_served_like_any_other():
    """``*E*E``: pages for the attention layers, nothing for the expert
    layers, no state pool; each request's tokens are ``generate``'s."""
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.serving.prefix_cache import PrefixCacheConfig
    from deepspeed_tpu.inference.serving.scheduler import (
        ContinuousBatchingScheduler, ServingConfig)
    eng = InferenceEngine(ht.config(hybrid_override_pattern="*E*E"),
                          DeepSpeedInferenceConfig(dtype="float32", max_out_tokens=64),
                          seed=2)
    prompts = [ht.ids(n, seed=n)[0] for n in (7, 16, 11)]
    sched = ContinuousBatchingScheduler(eng, ServingConfig(
        slots=2, chunk_size=4, max_seq_len=64, max_queue=8, kv_page_size=8,
        prefix_cache=PrefixCacheConfig(enabled=False)))
    assert not sched.executor.kv_every_layer
    assert [sorted(c) for c in sched.executor.pool.caches] == \
        [["k", "v"], [], ["k", "v"], []]
    handles = [sched.submit(p, max_new_tokens=5 + i) for i, p in enumerate(prompts)]
    sched.run()
    for p, h in zip(prompts, handles):
        alone = eng.generate(p[None], max_new_tokens=len(h.tokens))[0, p.size:]
        assert list(h.tokens) == [int(t) for t in alone], p.size


def test_a_state_space_layer_refuses_a_prefill_at_an_offset(tiny):
    from deepspeed_tpu.models.causal_lm import init_cache
    cfg, module, params = tiny
    with pytest.raises(NotImplementedError, match="cache offset"):
        module.apply({"params": params}, jnp.zeros((1, 4), jnp.int32),
                     caches=init_cache(cfg, 1, 16), cache_lens=jnp.asarray([4]),
                     prefix_fill=True)


def test_the_pattern_decides_the_layers_and_the_caches():
    from deepspeed_tpu.models.causal_lm import CausalLMConfig, bloom_cfg, init_cache
    cfg = ht.config()
    assert cfg.layer_kinds == "MEM*E" and cfg.held_experts == (4, 8)
    caches = init_cache(cfg, 2, 16)
    assert [sorted(c) for c in caches] == [["conv", "ssm"], [], ["conv", "ssm"],
                                           ["k", "v"], []]
    assert caches[0]["ssm"].dtype == jnp.float32 and caches[0]["ssm"].shape == (2, 8, 8, 16)
    assert caches[0]["conv"].shape == (2, 3, 8 * 8 + 2 * 2 * 16)
    assert caches[3]["k"].shape == (2, 2, 16, 16)
    classic = bloom_cfg(n_layer=2, n_embd=32, n_head=2, vocab_size=64)
    assert classic.layer_kinds == "AA"
    assert [sorted(c) for c in init_cache(classic, 1, 8)] == [["k", "v"]] * 2
    with pytest.raises(ValueError, match="layer_pattern"):
        CausalLMConfig(n_layer=3, layer_pattern="MX*")
    with pytest.raises(ValueError, match="no share"):
        ht.config(experts_held=[12, 8])
