"""SDAR's mixture-of-experts model (``models/causal_lm.py: sdar_moe_cfg``)
and its generation by diffusion over blocks, at a small size on the CPU,
against the plain float32 reference the benchmark keeps
(``benchmarks/chipbench/reference/sdar_moe.py``): the layer's terms one by one,
the expert shares of an expert-parallel layer, prefill and block steps through
the paged pool under the three unmasking strategies, ``engine.forward`` and
``engine.generate``, what the scheduler refuses, and the spans and counters."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
MASK = 500
MODEL = dict(hidden_size=64, num_hidden_layers=2, vocab_size=512,
             num_attention_heads=4, num_key_value_heads=2, head_dim=32,
             num_experts=8, num_experts_per_tok=2, moe_intermediate_size=48,
             norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=1e6,
             gen_block_length=4, gen_denoising_steps=4, gen_remasking="sequential",
             gen_confidence_threshold=0.9, mask_token_id=MASK)
STRATEGIES = ("sequential", "low_confidence_static", "low_confidence_dynamic")


def _reference():
    path = os.path.join(REPO, "benchmarks", "chipbench", "reference", "sdar_moe.py")
    spec = importlib.util.spec_from_file_location("chipbench_reference_sdar_moe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()


def _cfg(**over):
    from deepspeed_tpu.models.causal_lm import sdar_moe_cfg
    return sdar_moe_cfg(max_seq_len=96, init_std=0.3, dtype=jnp.float32,
                        **{**MODEL, **over})


def _init(cfg, seed=0):
    """Seeded parameters; the learned norms of q and k away from one, so that
    a layer that drops them shows."""
    from deepspeed_tpu.models.causal_lm import CausalLM
    module = CausalLM(cfg)
    params = module.init({"params": jax.random.PRNGKey(seed)},
                         jnp.zeros((1, 8), jnp.int32))["params"]
    params = jax.tree_util.tree_map(lambda a: a, params)
    key = jax.random.PRNGKey(seed + 100)
    for name, lp in params.items():
        if isinstance(lp, dict) and "q_norm" in lp:
            for i, n in enumerate(("q_norm", "k_norm")):
                lp[n]["scale"] = 1.0 + 0.5 * jax.random.normal(
                    jax.random.fold_in(key, hash(name) % 1000 + i), lp[n]["scale"].shape)
    return module, params


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(1, MASK, size=n).astype(np.int32)


def _reference_logits(params, model, ids):
    x = ref.hidden(params, model, ids)
    return np.asarray(ref.head(x, params["ln_f"], params["lm_head"]["kernel"],
                               ref._Frozen(model)))


# ------------------------------------------------------------------ the model
def test_the_whole_model_agrees_with_the_reference():
    module, params = _init(_cfg())
    ids = _ids(24)
    got = np.asarray(module.apply({"params": params}, ids[None])[0])
    want = _reference_logits(params, MODEL, ids)
    assert np.abs(got - want).max() < 2e-4 * want.std()


def test_one_layer_agrees_with_the_reference():
    """A published layer is the pair of mixer layers "*E"."""
    from deepspeed_tpu.models.causal_lm import make_layer
    cfg = _cfg()
    _, params = _init(cfg)
    x = np.random.default_rng(1).normal(size=(1, 12, 64)).astype(np.float32)
    pos = jnp.arange(12)[None]
    y, _ = make_layer(cfg, 0).apply({"params": params["layers_0"]}, jnp.asarray(x), pos)
    y, _ = make_layer(cfg, 1).apply({"params": params["layers_1"]}, y, pos,
                                    mutable=["stats"])[0]
    model = ref._Frozen(MODEL)
    want = ref.attention_layer(jnp.asarray(x[0]), params["layers_0"],
                               jnp.asarray(ref.block_mask(12, 4)), model)
    want = ref.moe_layer(want, params["layers_1"], model)
    assert np.abs(np.asarray(y[0]) - np.asarray(want)).max() < 1e-4


@pytest.mark.parametrize("dropped", ["qk_norm", "renormalisation", "expert_gate",
                                     "block_causal_mask", "rotation"])
def test_a_layer_that_drops_a_term_disagrees_by_whole_spreads(dropped, monkeypatch):
    """Each term of the layer matters to the comparison: the program built
    without it lies whole spreads of the logits off the reference."""
    over = {"qk_norm": {}, "renormalisation": {"norm_topk_prob": False},
            "expert_gate": {}, "block_causal_mask": {"gen_block_length": 0},
            "rotation": {}}[dropped]
    cfg = _cfg(**over)
    if dropped == "block_causal_mask":
        cfg.gen_block_length = 0                   # plainly causal
    _, params = _init(_cfg())
    if dropped == "qk_norm":
        cfg.qk_norm = False
    if dropped == "rotation":
        cfg.pos_emb = "none"
    if dropped == "expert_gate":
        from deepspeed_tpu.moe import gated_moe
        whole = gated_moe.grouped_experts
        monkeypatch.setattr(gated_moe, "grouped_experts",
                            lambda *a: whole(*a[:-1], None))
    from deepspeed_tpu.models.causal_lm import CausalLM
    ids = _ids(24)
    got = np.asarray(CausalLM(cfg).apply({"params": params}, ids[None])[0])
    want = _reference_logits(params, MODEL, ids)
    assert np.abs(got - want).max() > 0.5 * want.std(), dropped


def test_the_shares_of_four_chips_add_up_to_the_uncut_layer():
    """``experts_held`` tells a program its share; the partial sums of four
    shares of two experts each are the whole layer's, which the reference
    (every expert on every token) gives."""
    from deepspeed_tpu.moe.gated_moe import GatedMoE
    _, params = _init(_cfg())
    moe = params["layers_1"]["moe"]
    h = jnp.asarray(np.random.default_rng(2).normal(size=(1, 16, 64)), jnp.float32)

    def share(first, count):
        p = {"router": moe["router"],
             **{k: moe[k][first:first + count]
                for k in ("experts_gate", "experts_up", "experts_down")}}
        layer = GatedMoE(d_model=64, n_routed=8, top_k=2, expert_width=48,
                         norm_topk=True, experts_held=(first, count),
                         dtype=jnp.float32, init_std=0.3, out_std=0.3)
        return layer.apply({"params": p}, h)

    whole, stats = share(0, 8)
    parts = [share(a, 2) for a in (0, 2, 4, 6)]
    assert np.abs(sum(np.asarray(o) for o, _ in parts) - np.asarray(whole)).max() < 1e-5
    assert sum(int(s[0]) for _, s in parts) == int(stats[0]) == 16 * 2
    # and the whole is what the reference gives with every expert on every token
    lp = {"norm": {"scale": jnp.ones(64)}, "moe": moe}
    x = h[0] * jax.lax.rsqrt(jnp.mean(h[0] * h[0], axis=-1, keepdims=True) + 1e-6)
    want = ref.moe_layer(h[0], lp, ref._Frozen(MODEL)) - h[0]
    got, _ = GatedMoE(d_model=64, n_routed=8, top_k=2, expert_width=48, norm_topk=True,
                      experts_held=(0, 8), dtype=jnp.float32, init_std=0.3,
                      out_std=0.3).apply({"params": moe}, x[None])
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < 1e-4


# ----------------------------------------------------------- served generation
@pytest.fixture(scope="module")
def engine():
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    return InferenceEngine(_cfg(), DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=96), seed=3)


def _use(engine, strategy, threshold=0.9):
    """The same weights under another unmasking order: the model's own
    setting, read when a program is traced."""
    engine.model_config.gen_remasking = strategy
    engine.model_config.gen_confidence_threshold = threshold
    engine._fns.clear()
    return dict(MODEL, gen_remasking=strategy, gen_confidence_threshold=threshold)


def _agree(got, want, records, prompt_len, what, tol=1e-4):
    """Token for token, but for a choice the reference itself makes by a
    hair (a lead under ``tol`` spreads in a forward up to the one that
    unmasked the first differing position): there another rounding may
    rightly choose otherwise, and what follows differs with it."""
    got, want = list(map(int, got)), list(map(int, want))
    if got == want:
        return
    first = next(i for i, (a, b) in enumerate(zip(got, want)) if a != b)
    upto = 1 + next(i for i, r in enumerate(records) if prompt_len + first in r[0])
    assert min(min(abs(r[1]), r[2]) for r in records[:upto]) < tol, \
        (what, first, got, want)


REQUESTS = [(8, 9), (9, 5), (10, 12), (11, 7), (17, 3), (6, 1), (12, 8)]


@pytest.mark.parametrize("strategy,threshold", [
    ("sequential", 0.9), ("low_confidence_static", 0.9),
    ("low_confidence_dynamic", 0.9), ("low_confidence_dynamic", 0.02)])
def test_prefill_and_block_steps_through_the_pool_give_the_references_tokens(
        engine, strategy, threshold):
    """Seven requests over three slots (slots recycled), prompts with ``P %
    4`` = 0..3, outputs that are no multiple of 4, one prompt that holds the
    mask token: what the scheduler serves is what the plain block loop gives,
    and so is ``engine.generate``. At threshold 0.02 the dynamic strategy
    unmasks several positions in one forward."""
    from deepspeed_tpu.inference.serving.scheduler import (
        ContinuousBatchingScheduler, RequestState, ServingConfig)
    model = _use(engine, strategy, threshold)
    sched = ContinuousBatchingScheduler(engine, ServingConfig(
        slots=3, chunk_size=10, max_seq_len=96, kv_page_size=16))
    sent = []
    for i, (P, n) in enumerate(REQUESTS):
        prompt = _ids(P, seed=10 + i)
        if P == 10:
            prompt[3] = MASK               # the mask token as an ordinary prompt token
        sent.append((prompt, n, sched.submit(prompt, max_new_tokens=n)))
    sched.run()
    several = 0
    for prompt, n, h in sent:
        want, records = ref.generate(engine.params, model, prompt, n, strategy)
        several += sum(len(r[0]) > 1 for r in records)
        assert h.state == RequestState.FINISHED and len(h.tokens) == n
        assert h.first_token_at is not None and h.first_token_at >= h.arrival
        _agree(h.tokens, want, records, len(prompt), (strategy, len(prompt), n))
        out = engine.generate(prompt[None], max_new_tokens=n)
        assert out.shape == (1, len(prompt) + n)
        _agree(out[0, len(prompt):], want, records, len(prompt),
               ("generate", len(prompt), n))
    assert (several > 0) == (threshold < 0.5)
    assert sched.executor.pool.free_slots == 3


def test_generate_pads_to_the_serving_rows_and_stops_at_an_eos(engine):
    """``greedy_decode_rows`` rows hold nothing and are cut off; a row that
    meets its EOS inside a block ends there and is padded with it."""
    model = _use(engine, "sequential")
    prompts = np.stack([_ids(9, seed=40), _ids(9, seed=41)])
    plain = engine.generate(prompts, max_new_tokens=10)
    engine.model_config.greedy_decode_rows = 4
    engine._fns.clear()
    padded = engine.generate(prompts, max_new_tokens=10)
    engine.model_config.greedy_decode_rows = None
    assert np.array_equal(plain, padded)
    for row in range(2):
        want, _ = ref.generate(engine.params, model, prompts[row], 10)
        assert list(plain[row, 9:]) == list(want)
    eos = int(plain[0, 9 + 5])                       # the sixth generated token
    first = list(plain[0, 9:]).index(eos)
    out = engine.generate(prompts[:1], max_new_tokens=10, eos_token_id=eos)[0, 9:]
    assert list(out[:first + 1]) == list(plain[0, 9:9 + first + 1])
    assert all(int(t) == eos for t in out[first:])


def test_forward_is_what_each_next_token_is_chosen_from(engine):
    """``engine.forward`` keeps its contract: row ``p`` is what token ``p +
    1`` is chosen from given ``ids[:p + 1]``: the logits AT ``p + 1`` with
    the rest of its block masked, one forward over a clean copy beside four
    masked copies."""
    _use(engine, "sequential")
    for n in (13, 16):
        ids = _ids(n, seed=n)
        ids[5] = MASK
        got = np.asarray(engine.forward(ids[None])[0])
        want = ref.next_token_logits(engine.params, MODEL, ids, np.arange(n))
        assert got.shape == want.shape == (n, 512)
        assert np.abs(got - want).max() < 2e-4 * want.std()


def test_the_scheduler_refuses_what_generation_by_blocks_cannot_do(engine):
    from deepspeed_tpu.inference.serving.prefix_cache import PrefixCacheConfig
    from deepspeed_tpu.inference.serving.scheduler import (
        ContinuousBatchingScheduler, ServingConfig)
    _use(engine, "sequential")
    with pytest.raises(ValueError, match="speculate with this model.*blocks of 4"):
        ContinuousBatchingScheduler(engine, ServingConfig(
            slots=2, max_seq_len=96, speculate=True))
    with pytest.raises(ValueError, match="block length must divide the page size"):
        ContinuousBatchingScheduler(engine, ServingConfig(
            slots=2, max_seq_len=96, kv_page_size=6,
            prefix_cache=PrefixCacheConfig(enabled=True)))
    with pytest.raises(ValueError, match="must divide the cap .* and the page size"):
        ContinuousBatchingScheduler(engine, ServingConfig(
            slots=2, max_seq_len=96, kv_page_size=6))
    with pytest.raises(ValueError, match="prefix_cache.enabled with this model"):
        ContinuousBatchingScheduler(engine, ServingConfig(
            slots=2, max_seq_len=96, prefix_cache=PrefixCacheConfig(enabled=True)))


@pytest.mark.parametrize("bad", [dict(gen_denoising_steps=3), dict(gen_block_length=1),
                                 dict(gen_remasking="random"),
                                 dict(mask_token_id=512)])
def test_the_configuration_refuses_a_generation_it_cannot_run(bad):
    with pytest.raises(ValueError):
        _cfg(**bad)


def test_the_chunk_span_and_the_counters_say_what_the_blocks_did(engine):
    """A chunk's span carries ``forwards``, ``block_length``,
    ``blocks_committed``, ``positions_unmasked`` and ``blocks_merged`` (the
    commits that opened their next block in the same forward) beside the
    expert counts, a prefill's ``blocks_committed``; the registry the four
    totals."""
    from deepspeed_tpu.inference.serving.scheduler import (
        ContinuousBatchingScheduler, ServingConfig)
    from deepspeed_tpu.observability.metrics import get_registry
    from deepspeed_tpu.observability.trace import get_tracer
    _use(engine, "sequential")
    tracer = get_tracer()
    tracer.reset()
    tracer.enable()
    try:
        sched = ContinuousBatchingScheduler(engine, ServingConfig(
            slots=2, chunk_size=10, max_seq_len=96))
        h = sched.submit(_ids(10, seed=50), max_new_tokens=8)   # 2 whole blocks + 2
        sched.run()
        spans = list(tracer.spans)
    finally:
        tracer.disable()
        tracer.reset()
    assert len(h.tokens) == 8
    chunks = [s["attrs"] for s in spans if s["name"] == "serving.decode_chunk"]
    (prefill,) = [s["attrs"] for s in spans if s["name"] == "serving.prefill"]
    assert prefill["blocks_committed"] == 2
    assert all(c["forwards"] == 10 and c["block_length"] == 4 for c in chunks)
    # blocks [8, 12): 2 open + 2 denoised; [12, 16) and [16, 20): 4 each, cut to 8 tokens
    assert sum(c["blocks_committed"] for c in chunks) == 3
    assert sum(c["positions_unmasked"] for c in chunks) == 10
    # the first two commits open the next block; the third ends the request
    # and the block behind it is thrown away, uncounted
    assert sum(c["blocks_merged"] for c in chunks) == 2
    assert sum(c["tokens_kept"] for c in chunks) == 8
    assert all("moe_experts_touched" in c for c in chunks)
    t = sched.telemetry
    assert (t.block_forwards, t.blocks_committed, t.positions_unmasked,
            t.blocks_merged) == (10 * len(chunks), 3, 10, 2)
    snap = get_registry().snapshot()
    for name in ("serving/block_forwards_total", "serving/blocks_committed_total",
                 "serving/positions_unmasked_total", "serving/blocks_merged_total"):
        assert name in snap


# ------------------------------------- a commit in the forward that opens the next block
def _serve(engine, requests, **serving):
    """``requests``: ``(prompt, n, eos or None)``. Returns the scheduler, the
    handles and, by request id, the rows its pages held when it finished (a
    layer that keeps keys and values: ``(k, v)``, each ``(kv heads, rows,
    d)``), read before another request can be given the pages (a request that
    ran for more than one step)."""
    from deepspeed_tpu.inference.serving.scheduler import (
        ContinuousBatchingScheduler, ServingConfig)
    from deepspeed_tpu.ops.paged_attention import pages_to_dense
    sched = ContinuousBatchingScheduler(engine, ServingConfig(
        **{**dict(slots=2, chunk_size=10, max_seq_len=96, kv_page_size=16), **serving}))
    handles = [sched.submit(p, max_new_tokens=n, eos_token_id=eos)
               for p, n, eos in requests]
    tables, rows = {}, {}
    for _ in range(200):
        for slot, h in enumerate(sched._slot_req):
            if h is not None:
                tables[h.id] = jnp.asarray(sched.executor.pool.page_table[slot].copy())
        more = sched.step()
        for h in handles:
            if h.state.name == "FINISHED" and h.id in tables and h.id not in rows:
                rows[h.id] = [tuple(np.asarray(pages_to_dense(c[key], tables[h.id]))
                                    for key in ("k", "v"))
                              for c in sched.executor.pool.caches if "k" in c]
        if not more:
            break
    assert all(h.state.name == "FINISHED" for h in handles)
    return sched, handles, rows


def test_a_block_of_four_costs_four_forwards_in_steady_state(engine):
    """The commit of a block rides the forward that opens the next one: 40
    tokens are 10 blocks of 4 denoising forwards and one last commit, where a
    forward of its own a commit made them 50; a serving chunk always runs
    its 10 forwards, so there the count is tokens kept a forward a slot."""
    _use(engine, "sequential")
    prompt = _ids(8, seed=60)
    out = engine.generate(prompt[None], max_new_tokens=40)
    assert out.shape == (1, 48)
    assert 40 <= engine.block_forwards <= 42
    from deepspeed_tpu.observability.trace import get_tracer
    tracer = get_tracer()
    tracer.reset()
    tracer.enable()
    try:
        _, (h,), _ = _serve(engine, [(prompt, 80, None)], slots=1)
        spans = list(tracer.spans)
    finally:
        tracer.disable()
        tracer.reset()
    assert list(h.tokens[:40]) == list(out[0, 8:])
    chunks = [s["attrs"] for s in spans if s["name"] == "serving.decode_chunk"]
    steady = chunks[:-1]                 # the last chunk holds the last commit alone
    kept = sum(c["tokens_kept"] for c in steady)
    run = sum(c["forwards"] * c["active_slots"] for c in steady)
    assert len(steady) == 8 and kept / run >= 0.95, (kept, run)
    assert sum(c["blocks_merged"] for c in chunks) == \
        sum(c["blocks_committed"] for c in chunks) - 1 == 19


def _reference_kv(params, model, ids):
    """The keys and values the reference's layers form over ``ids``, its own
    parts in its own order: a layer ``(k, v)``, each ``(t, kv heads, d)``."""
    m = ref._Frozen(model)
    nk, hd = int(m["num_key_value_heads"]), int(m["head_dim"])
    eps, base = ref._eps(m), float(m["rope_theta"])
    mask = jnp.asarray(ref.block_mask(len(ids), int(m["gen_block_length"])))
    x = jnp.asarray(params["wte"][jnp.asarray(ids)], jnp.float32)
    out = []
    for i in range(int(m["num_hidden_layers"])):
        lp = ref._f32(params[f"layers_{2 * i}"])
        with jax.default_matmul_precision(ref.HI):
            hn = ref.rmsnorm(x, lp["norm"]["scale"], eps)
            k = (hn @ lp["k_proj"]["kernel"]).reshape(len(ids), nk, hd)
            v = (hn @ lp["v_proj"]["kernel"]).reshape(len(ids), nk, hd)
            out.append((ref.rotate(ref.rmsnorm(k, lp["k_norm"]["scale"], eps), base), v))
        x = ref.attention_layer(x, lp, mask, m)
        x = ref.moe_layer(x, params[f"layers_{2 * i + 1}"], m)
    return out


def test_a_last_block_that_ends_at_the_cap_leaves_the_references_rows_in_the_pages(
        engine):
    """A forward writes two blocks at a slot's length and the append clamps a
    write that would pass the end of the view: the request whose last block
    ends exactly at ``max_seq_len`` (its last commit writes past the cap, and
    it idles at the cap while its neighbour runs on) must leave every row of
    its pages as the keys and values of its tokens."""
    model = _use(engine, "sequential")
    cap = 32
    requests = [(_ids(9, seed=70), cap - 9, None), (_ids(6, seed=71), 22, None),
                (_ids(12, seed=72), cap - 12, None)]
    _, handles, rows = _serve(engine, requests, max_seq_len=cap)
    for (prompt, n, _), h in zip(requests, handles):
        want, records = ref.generate(engine.params, model, prompt, n)
        _agree(h.tokens, want, records, len(prompt), ("at the cap", len(prompt), n))
        seq = np.concatenate([prompt, np.asarray(h.tokens, np.int32)])
        whole = len(seq) // 4 * 4         # the committed blocks
        for got, want_kv in zip(rows[h.id], _reference_kv(engine.params, model, seq)):
            for g, w in zip(got, want_kv):
                np.testing.assert_allclose(
                    g[:, :whole], np.asarray(w).transpose(1, 0, 2)[:, :whole],
                    atol=2e-4, err_msg=f"prompt {len(prompt)}")


@pytest.mark.parametrize("tail", [1, 2, 3])
def test_an_eos_inside_a_merged_commit_discards_the_opened_block(engine, tail):
    """A prompt's tail of 1, 2, 3 tokens opens the first block (``skip``); a
    request that meets its EOS in a block ends at that block's commit, and the
    block the same forward opened behind it is thrown away: the reference's
    tokens up to the EOS, and no position of the opened block counted."""
    model = _use(engine, "sequential")
    prompt = _ids(8 + tail, seed=80 + tail)
    want, records = ref.generate(engine.params, model, prompt, 12)
    want = list(map(int, want))
    at = next(i for i in range(4 - tail + 1, 12) if want[i] not in want[:i])
    eos = want[at]                       # inside the second or a later block
    sched, (h,), _ = _serve(engine, [(prompt, 12, eos)])
    _agree(h.tokens, want[:at + 1], records, len(prompt), ("eos", tail))
    assert len(h.tokens) == at + 1 and h.finish_reason == "eos"
    blocks = -(-(tail + at + 1) // 4)    # committed by the decode, the EOS's included
    t = sched.telemetry
    assert (t.blocks_committed, t.blocks_merged) == (blocks, blocks - 1)
    assert t.positions_unmasked == 4 * blocks - tail
    out = engine.generate(prompt[None], max_new_tokens=12, eos_token_id=eos)[0]
    assert list(out[len(prompt):len(prompt) + at + 1]) == list(h.tokens)
    assert all(int(x) == eos for x in out[len(prompt) + at:])
