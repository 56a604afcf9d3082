"""Kernels of the serving path compiled at their real widths for a DESCRIBED
v5e chip (none is attached here): what interpret mode cannot show — a slice
not aligned to the tiling, more VMEM than a kernel may use. Nothing runs, so
nothing here is a result or a time. One file, one fixture: only the worker
that is given this file loads the TPU's compiler."""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("tokens", [32, 64, 512])
def test_the_grouped_expert_kernel_compiles_at_the_published_widths(
        one_chip, tokens, monkeypatch):
    from deepspeed_tpu.moe.latent_moe import relu2
    from deepspeed_tpu.ops.moe import grouped_ffn as g
    monkeypatch.setattr(g, "_interpret", lambda: False)     # compile, do not interpret
    held, latent, width, k = 128, 1024, 2688, 22
    tm = g.tile_rows(tokens * k, 512)
    tiles = tokens * k // tm + held

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(functools.partial(g.grouped_ffn, act=relu2, tm=tm)).lower(
        sds((tiles * tm, latent), jnp.bfloat16), sds((tiles,), jnp.int32),
        sds((tiles,), jnp.int32), sds((held, latent, width), jnp.bfloat16),
        sds((held, width, latent), jnp.bfloat16)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "moe_grouped_ffn" in text
    # the plan lowers for the chip too: no sort, and no scatter at a decode
    # step's sizes; the 512-token bucket's 11,264 assignments count by prefix
    # sums and ONE scatter since PR 58, because the chip read that plan at
    # 0.144 ms where the dense comparisons take 0.413 (``plan_by_prefix_sums``)
    plan = jax.jit(functools.partial(g.dispatch_plan, first=0, count=held, tm=tm)).lower(
        sds((tokens, k), jnp.int32)).compile().as_text()
    assert " sort(" not in plan
    assert plan.count(" scatter(") == g.plan_by_prefix_sums(tokens * k) == (tokens == 512)


def _bloom_layers():
    from deepspeed_tpu.models.causal_lm import bloom_cfg
    return bloom_cfg(n_layer=2, n_embd=4096, n_head=32, vocab_size=250880,
                     dtype=jnp.bfloat16)


def _hybrid_attention_layer(pattern="*"):
    from deepspeed_tpu.models.causal_lm import nemotron_h_cfg
    return nemotron_h_cfg(
        hidden_size=4096, hybrid_override_pattern=pattern, vocab_size=131072,
        num_attention_heads=32, num_key_value_heads=2, head_dim=128,
        mamba_num_heads=128, mamba_head_dim=64, ssm_state_size=128, n_groups=8,
        conv_kernel=4, chunk_size=128, n_routed_experts=512,
        num_experts_per_tok=22, moe_intermediate_size=2688,
        moe_shared_expert_intermediate_size=5376, moe_latent_size=1024,
        routed_scaling_factor=5, dtype=jnp.bfloat16)


def _granite_attention_layer(layer_types=("attention",)):
    from deepspeed_tpu.models.causal_lm import granite_hybrid_cfg
    return granite_hybrid_cfg(
        hidden_size=2048, num_hidden_layers=len(layer_types),
        layer_types=list(layer_types),
        vocab_size=100352, num_attention_heads=32, num_key_value_heads=8,
        shared_intermediate_size=8192, mamba_n_heads=64, mamba_d_head=64,
        mamba_d_state=128, mamba_n_groups=1, mamba_d_conv=4, mamba_chunk_size=256,
        embedding_multiplier=12, residual_multiplier=0.22,
        attention_multiplier=0.015625, logits_scaling=8, dtype=jnp.bfloat16)


def _neox_layers():
    """``chip_smoke.py``'s rotary model (``NEOX_4L``), 2 of its 4 layers: 32
    key heads of 128 with ONE query row each."""
    from deepspeed_tpu.models.causal_lm import gptneox_cfg
    return gptneox_cfg(vocab_size=50432, max_seq_len=576, n_embd=4096,
                       n_layer=2, n_head=32, dtype=jnp.bfloat16)


def _abstract_model(cfg, slots, cap, pages, page, sds):
    """A module with its parameters and its pool's caches as shapes on the
    described chip (the pool's rows as ``heads_per_row`` lays them out)."""
    from deepspeed_tpu.models.causal_lm import CausalLM, init_cache
    module = CausalLM(cfg)
    params = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    params = jax.tree_util.tree_map(lambda x: sds(x.shape, cfg.dtype), params)
    r = cfg.cache_row_heads
    kv_shape = (pages, cfg.kv_heads // r, page, r * cfg.head_dim)
    caches = jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype), jax.eval_shape(lambda: init_cache(
            cfg, slots, cap, kv_shape=kv_shape)))
    return module, params, caches, kv_shape


def _pool_relayouts(text, kv_shape):
    """The compiled lines that scatter into, or copy, an array of the pages'
    shape: what an ``.at[page, :, row, :].set`` into the pool compiles to (the
    whole pool transposed for the scatter and back, every call)."""
    shape = " = bf16[" + ",".join(str(n) for n in kv_shape) + "]"
    return [line.strip()[:160] for line in text.splitlines()
            if shape in line and (" scatter(" in line or " copy(" in line)]


@pytest.mark.parametrize("make_cfg,slots,cap,pages,kernels,live", [
    (_bloom_layers, 2, 576, 73, 0, 2),            # bloom-7b1's cells, 2 of 30 layers
    (_hybrid_attention_layer, 32, 2048, 4097, 1, 0),  # the hybrid's one attention layer
    (_granite_attention_layer, 64, 2048, 8193, 0, 1),  # Granite's, two d 64 heads a row
    (_neox_layers, 4, 576, 145, 2, 0),            # chip_smoke's rotary model, a full pool
    (_neox_layers, 4, 576, 73, 2, 0),             # and an oversubscribed one: the same chunk
], ids=["bloom-7b1", "nemotron-h-attention", "granite-attention", "neox-mha",
        "neox-mha-oversubscribed"])
def test_the_decode_chunk_holds_no_loop_but_its_own(
        one_chip, make_cfg, slots, cap, pages, kernels, live, monkeypatch):
    """The dense-view decode chunk at the cells' shapes: a step appends its
    K/V rows without a loop over the slots (a scatter the TPU compiler
    expands into a serial ``while`` of trip count = slots, twice a layer a
    step), so the chunk's own ``while`` is the only one but for the walk over
    live blocks of XLA's decode attention, one a layer where a layer takes it
    (ALiBi, heads of 64: ``decode_attention_live``), which converts a block of
    the view to float32 and never the view; BLOOM's chunk holds
    no Mosaic kernel, the hybrid's attention layer its ``decode_attention``,
    a rotary model of 128-wide heads one a layer, whether or not its pool
    holds a row for every slot's whole cap (the regime a gather-by-page-index
    kernel had until PR 47). The chunk's rows go back into the pages as slab
    writes: no scatter, and no copy of a pages-shaped array
    (``write_view_rows``)."""
    from deepspeed_tpu.inference.decode_fns import (build_paged_decode_chunk,
                                                    make_slot_select_fn)
    from deepspeed_tpu.ops.attention import decode
    monkeypatch.setattr(decode, "_interpret", lambda: False)
    cfg, page, chunk = make_cfg(), 16, 8

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    module, params, caches, kv_shape = _abstract_model(cfg, slots, cap, pages, page, sds)
    fn = build_paged_decode_chunk(module, lambda p: p,
                                  make_slot_select_fn(False, 1.0, 0, 1.0),
                                  chunk, kv_cap=cap)
    text = jax.jit(fn, donate_argnums=(2,)).lower(
        params, sds((slots, 1)), caches, sds((slots, cap // page)), sds((slots,)),
        sds((slots,), jnp.bool_), sds((slots,)), sds((slots,)), sds((slots,)),
        sds((slots,)), sds((2,), jnp.uint32)).compile().as_text()
    loops = [line for line in text.splitlines() if " while(" in line]
    walks = [line for line in loops
             if "ds.attn.core/jit(decode_attention_live)/while" in line]
    assert len(loops) == 1 + live == 1 + len(walks), [
        line.split(" = ")[0].strip() for line in loops]
    assert sum('op_name="jit(decode_chunk)/while"' in line for line in loops) == 1
    rows = cfg.head_dim * (128 // cfg.head_dim if cfg.head_dim < 128 else 1)
    assert f"f32[{slots},{cfg.kv_heads * cfg.head_dim // rows},{cap},{rows}]" not in text
    assert text.count("tpu_custom_call") == kernels
    assert not kernels or "decode_attention" in text
    assert " scatter(" not in text and _pool_relayouts(text, kv_shape) == []


@pytest.mark.parametrize("bucket", [8, 64])
def test_the_suffix_prefill_writes_its_rows_without_relaying_the_pool(
        one_chip, bucket, monkeypatch):
    """The prefix-hit prefill at BLOOM's shapes (the pool of 73 pages, cap
    576, the smallest and the largest suffix bucket; 2 of 30 layers): the
    suffix's rows go into the slot's pages as slab writes, so the program
    scatters into and copies no pages-shaped array (the forward's scatter of
    the suffix's rows into the one-slot VIEW stays)."""
    from deepspeed_tpu.inference.decode_fns import (build_prefix_prefill,
                                                    make_slot_select_fn)
    from deepspeed_tpu.inference.serving.executor import PRE_COLS, _suffix_prefill
    from deepspeed_tpu.ops.attention import decode
    monkeypatch.setattr(decode, "_interpret", lambda: False)
    cfg, cap, pages, page = _bloom_layers(), 576, 73, 16

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    module, params, caches, kv_shape = _abstract_model(cfg, 2, cap, pages, page, sds)
    fn = _suffix_prefill(build_prefix_prefill(module, lambda p: p),
                         make_slot_select_fn(False, 1.0, 0, 1.0), cap)
    text = jax.jit(fn, donate_argnums=(1,)).lower(
        params, caches, sds((1, bucket)), sds((PRE_COLS + cap // page,)),
        sds((2,), jnp.uint32)).compile().as_text()
    assert _pool_relayouts(text, kv_shape) == []


@pytest.mark.parametrize("make_cfg,slots,cap,pages,bucket", [
    (_bloom_layers, 2, 576, 73, 64),        # chat's largest bucket, 2 of 30 layers
    (_bloom_layers, 2, 576, 73, 512),       # docqa's miss: the document's bucket
    (functools.partial(_granite_attention_layer, ("mamba", "attention")),
     64, 2048, 8193, 1024),                 # Granite: a Mamba and an attention layer
    (functools.partial(_hybrid_attention_layer, "M*"), 32, 2048, 4097, 256),
], ids=["bloom-7b1-64", "bloom-7b1-512", "granite-1024", "nemotron-h-256"])
def test_the_miss_prefill_writes_its_results_over_the_cache_it_is_handed(
        one_chip, make_cfg, slots, cap, pages, bucket, monkeypatch):
    """The cache-miss prefill at the cells' shapes: the stand-alone prefill,
    handed the last miss's batch-1 cache donated, aliases every leaf of it to
    a result (keys and values of ``cap`` rows, a Mamba layer's window and
    state), so that the program allocates none of its 60-80 result arrays: on
    the chip their allocation held the device idle 3-4 ms an admission."""
    from deepspeed_tpu.analysis.donation import _alias_param_positions, _flat_args_info
    from deepspeed_tpu.inference.decode_fns import build_prefill, make_slot_select_fn
    from deepspeed_tpu.inference.serving.executor import _prefill
    from deepspeed_tpu.models.causal_lm import init_cache
    from deepspeed_tpu.ops.attention import decode
    monkeypatch.setattr(decode, "_interpret", lambda: False)
    cfg, page = make_cfg(), 16

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    module, params, _, _ = _abstract_model(cfg, slots, cap, pages, page, sds)
    one = jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda: init_cache(cfg, 1, cap, dtype=cfg.dtype)))
    fn = _prefill(build_prefill(module, lambda p: p),
                  make_slot_select_fn(False, 1.0, 0, 1.0), cfg, cap, cfg.dtype)
    lowered = jax.jit(fn, donate_argnums=(1,), keep_unused=True).lower(
        params, one, sds((1, bucket)), sds((2,)), sds((2,), jnp.uint32))
    text = lowered.compile().as_text()
    donated = [i for i, (_, info) in enumerate(_flat_args_info(lowered)) if info.donated]
    assert len(donated) == len(jax.tree_util.tree_leaves(one)) > 0
    assert len(_alias_param_positions(text)) == len(donated)


@pytest.mark.parametrize("tokens", [128, 512])
def test_the_gated_expert_kernel_compiles_at_sdars_published_widths(
        one_chip, tokens, monkeypatch):
    """``moe_grouped_ffn`` in its gated form (three weight blocks a tile) at
    SDAR-30B-A3B's widths: a block forward of 32 slots x 4 rows and a
    512-token prefill, top 8 of 128 experts of 2048 x 768."""
    from deepspeed_tpu.ops.moe import grouped_ffn as g
    monkeypatch.setattr(g, "_interpret", lambda: False)
    held, d, width, k = 128, 2048, 768, 8
    tm = g.tile_rows(tokens * k, 128)
    tiles = tokens * k // tm + held

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    up = sds((held, d, width), jnp.bfloat16)
    text = jax.jit(functools.partial(g.grouped_ffn, act=jax.nn.silu, tm=tm)).lower(
        sds((tiles * tm, d), jnp.bfloat16), sds((tiles,), jnp.int32),
        sds((tiles,), jnp.int32), up, sds((held, width, d), jnp.bfloat16),
        w_gate=up).compile().as_text()
    assert "tpu_custom_call" in text and "moe_grouped_ffn" in text


@pytest.mark.parametrize("tokens", [32, 512])
def test_the_gated_expert_kernel_compiles_at_lfm2s_published_widths(
        one_chip, tokens, monkeypatch):
    """``moe_grouped_ffn`` in its gated form at LFM2-8B-A1B's widths, the
    cell ``lfm2-8b-a1b.conv32``'s kernel shape ``(32, 2048, 1792)``, tiles of
    16 rows: a decode step of 32 slots and a 512-token prefill, top 4 of 32
    experts. An expert is 22.02 MB, 2.3 x SDAR's: its three whole-matrix
    blocks, double-buffered, are 44 MB of the kernel's 64 MiB of VMEM."""
    from deepspeed_tpu.ops.moe import grouped_ffn as g
    monkeypatch.setattr(g, "_interpret", lambda: False)
    held, d, width, k = 32, 2048, 1792, 4
    tm = g.tile_rows(tokens * k, 32)
    assert tm == 16
    tiles = tokens * k // tm + held
    assert 2 * 3 * d * width * 2 < g.VMEM_LIMIT_BYTES

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    up = sds((held, d, width), jnp.bfloat16)
    text = jax.jit(functools.partial(g.grouped_ffn, act=jax.nn.silu, tm=tm)).lower(
        sds((tiles * tm, d), jnp.bfloat16), sds((tiles,), jnp.int32),
        sds((tiles,), jnp.int32), up, sds((held, width, d), jnp.bfloat16),
        w_gate=up).compile().as_text()
    assert "tpu_custom_call" in text and "moe_grouped_ffn" in text


@pytest.mark.parametrize("tokens", [32, 512])
def test_the_gated_expert_kernel_compiles_at_granite_smalls_published_widths(
        one_chip, tokens, monkeypatch):
    """``moe_grouped_ffn`` in its gated form at granite-4.0-h-small's widths,
    the cell ``granite-4.0-h-small.conv32``'s kernel shape ``(36, 4096, 768)``
    (the chip's 36 of 72 experts), the top 10: a decode step of 32 slots is
    320 assignments, 56 tiles of 16 rows; a 512-token prefill takes tiles of
    32. The fourth expert size on the chip (9.4, 11.0, 22.0 and this 18.9
    MB): three whole-matrix blocks, double-buffered, are 37.7 MB of the
    kernel's 64 MiB of VMEM."""
    from deepspeed_tpu.ops.moe import grouped_ffn as g
    monkeypatch.setattr(g, "_interpret", lambda: False)
    held, d, width, k = 36, 4096, 768, 10
    tm = g.tile_rows(tokens * k, 72)
    assert tm == (16 if tokens == 32 else 32)
    tiles = tokens * k // tm + held
    assert (tokens, tiles) in ((32, 56), (512, 196))
    assert 2 * 3 * d * width * 2 == 37_748_736 < g.VMEM_LIMIT_BYTES

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    up = sds((held, d, width), jnp.bfloat16)
    text = jax.jit(functools.partial(g.grouped_ffn, act=jax.nn.silu, tm=tm)).lower(
        sds((tiles * tm, d), jnp.bfloat16), sds((tiles,), jnp.int32),
        sds((tiles,), jnp.int32), up, sds((held, width, d), jnp.bfloat16),
        w_gate=up).compile().as_text()
    assert "tpu_custom_call" in text and "moe_grouped_ffn" in text


def test_sdars_block_chunk_holds_its_two_kernels_and_no_loop_but_its_own(
        one_chip, monkeypatch):
    """The decode chunk of generation by diffusion over blocks at the cell's
    shapes (32 slots x cap 2048, 10 forwards; 2 of the 8 layers): every
    forward's two blocks of 4 rows a slot go through ONE ``decode_attention``
    a layer (the 8 queries of a key head's 8 query heads as two runs of 32
    rows of one group, a length a run) and the gated ``moe_grouped_ffn``; the
    blocks' rows are appended and the committed blocks copied back to their
    pages without a loop over the slots."""
    from deepspeed_tpu.inference.decode_fns import (block_view_rows,
                                                    build_block_decode_chunk,
                                                    make_slot_select_fn)
    from deepspeed_tpu.models.causal_lm import sdar_moe_cfg
    from deepspeed_tpu.ops.attention import decode
    from deepspeed_tpu.ops.moe import grouped_ffn as g
    monkeypatch.setattr(decode, "_interpret", lambda: False)
    monkeypatch.setattr(g, "_interpret", lambda: False)
    cfg = sdar_moe_cfg(hidden_size=2048, num_hidden_layers=2, vocab_size=151936,
                       num_attention_heads=32, num_key_value_heads=4, head_dim=128,
                       num_experts=128, num_experts_per_tok=8,
                       moe_intermediate_size=768, dtype=jnp.bfloat16)
    slots, cap, pages, page, forwards, B = 32, 2048, 4097, 16, 10, 4

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    module, params, caches, kv_shape = _abstract_model(cfg, slots, cap, pages, page, sds)
    fn = build_block_decode_chunk(module, lambda p: p,
                                  make_slot_select_fn(False, 1.0, 0, 1.0),
                                  forwards, kv_cap=cap, with_stats=True)
    text = jax.jit(fn, donate_argnums=(4,)).lower(
        params, sds((slots, B)), sds((slots, B), jnp.bool_), sds((slots,)), caches,
        sds((slots, cap // page)), sds((slots,)), sds((slots,), jnp.bool_),
        sds((slots,)), sds((slots,)), sds((slots,)), sds((slots,)),
        sds((2,), jnp.uint32)).compile().as_text()
    loops = [line for line in text.splitlines() if " while(" in line]
    assert len(loops) == 1, [line.split(" = ")[0].strip() for line in loops]
    assert text.count("tpu_custom_call") == 4       # two kernels a layer
    assert "decode_attention" in text and "moe_grouped_ffn" in text
    # the committed blocks' slabs come out of the view by dynamic_slice: no
    # gather re-lays the VIEW out either
    view = (slots, kv_shape[1], block_view_rows(cfg, cap), kv_shape[3])
    assert _pool_relayouts(text, kv_shape) == [] and _pool_relayouts(text, view) == []


def _flash_cases():
    """name -> (function of the flash module, operand shapes, dtype): the three
    kernels at the cells' shapes, and the widest block the layout makes."""
    def grad(fn):
        return jax.grad(lambda *a: fn(*a).astype(jnp.float32).sum(),
                        argnums=tuple(range(3)))

    return {
        # gpt2-125m.seq1k: c_attn's output as ONE operand, two heads of 64 a block
        "gpt2-fused-gradient": (lambda f: jax.grad(lambda x: f.flash_attention_qkv(
            x, 12).astype(jnp.float32).sum()), [(24, 1024, 3 * 768)], jnp.bfloat16),
        # the same under c_attn's bias, as Block runs it
        "gpt2-fused-bias-gradient": (lambda f: jax.grad(
            lambda x, c: f.flash_attention_qkv(x + c, 12).astype(jnp.float32).sum(),
            argnums=(0, 1)), [(24, 1024, 3 * 768), (3 * 768,)], jnp.bfloat16),
        # configs/gpt2-1.3b-zero3.json unsharded: 16 heads of 128, one a block
        "gpt2-1.3b-fused-bias-gradient": (lambda f: jax.grad(
            lambda x, c: f.flash_attention_qkv(x + c, 16).astype(jnp.float32).sum(),
            argnums=(0, 1)), [(4, 1024, 3 * 2048), (3 * 2048,)], jnp.bfloat16),
        "gpt2-split-gradient": (lambda f: grad(f.flash_attention_local),
                                [(24, 1024, 12, 64)] * 3, jnp.bfloat16),
        # bloom-7b1.docqa's 512-bucket prefill: one head of 128 a block, alibi
        "bloom-prefill": (lambda f: functools.partial(
            f.flash_attention_local, alibi_slopes=jnp.arange(32.0) / 32),
            [(1, 512, 32, 128)] * 3, jnp.bfloat16),
        # sdar-30b-a3b-chat.conv32's prefill: the block-causal mask
        "sdar-prefill": (lambda f: functools.partial(f.flash_attention_local, mask_block=4),
                         [(1, 512, 32, 128)] * 3, jnp.bfloat16),
        # lfm2-8b-a1b.conv32's and granite-4.0-h-micro.conv64's: two heads of 64 a block,
        # Granite's scale
        "d64-prefill": (lambda f: functools.partial(f.flash_attention_local,
                                                    softmax_scale=1 / 64),
                        [(1, 512, 32, 64)] * 3, jnp.bfloat16),
        # four float32 heads a block over several kv blocks: past the default 16 MiB
        # of scoped VMEM (19.6), inside the limit the kernels ask for
        "four-f32-heads-a-block": (lambda f: grad(f.flash_attention_local),
                                   [(2, 2048, 4, 32)] * 3, jnp.float32),
        # heads the lane tiles cannot address: the folded (b*h, t, d) call
        "odd-head-count": (lambda f: grad(f.flash_attention_local),
                           [(2, 1024, 3, 64)] * 3, jnp.bfloat16),
    }


@pytest.mark.parametrize("case", sorted(_flash_cases()))
def test_the_flash_kernels_compile_reading_heads_in_place(one_chip, case, monkeypatch):
    """Forward and both backward kernels with the head picked in their index maps:
    Mosaic takes the lane-group blocks of (b, t, h*d) and of the fused (b, t, 3*h*d),
    and the compiled program moves no head (no transpose) where the layout is flat."""
    from deepspeed_tpu.ops.attention import flash
    monkeypatch.setattr(flash, "_interpret", lambda: False)
    make, shapes, dtype = _flash_cases()[case]
    text = jax.jit(make(flash)).lower(*(
        jax.ShapeDtypeStruct(s, dtype, sharding=one_chip) for s in shapes)).compile().as_text()
    kernels = 3 if "gradient" in case or "heads" in case or "odd" in case else 1
    assert text.count("tpu_custom_call") == kernels and "flash_fwd" in text
    assert case == "odd-head-count" or " transpose(" not in text


def _gpt2_125m_gradient(one_chip, monkeypatch, unrolled=None):
    """``gpt2-125m.seq1k``'s forward + backward (the engine's loss, ``dots`` remat,
    micro 24 x seq 1024) compiled for the described chip; ``unrolled`` stands in for
    ``models/gpt2.py: unroll_layer_loop``'s answer where a test wants the other one."""
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.ops.attention import flash
    monkeypatch.setattr(flash, "_interpret", lambda: False)
    if unrolled is not None:
        monkeypatch.setattr(gpt2, "unroll_layer_loop", lambda: unrolled)
    model = gpt2.gpt2_model(gpt2.GPT2Config(
        vocab_size=50304, dtype=jnp.bfloat16, attention_impl="flash", remat=True,
        remat_policy="dots"))
    params = jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, jnp.bfloat16, sharding=one_chip),
        jax.eval_shape(model.init_fn, jax.random.PRNGKey(0)))
    batch = {"input_ids": jax.ShapeDtypeStruct((24, 1024), jnp.int32, sharding=one_chip)}
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    compiled = jax.jit(jax.value_and_grad(model.loss_fn)).lower(params, batch, rng).compile()
    text = compiled.as_text()
    stack_writes = re.findall(r"= \w+\[12,24,1024,[^\]]*\]\S* dynamic-update-slice\(", text)
    return text, stack_writes, compiled.memory_analysis().temp_size_in_bytes


def test_the_125m_gradient_holds_no_loop_and_no_stack_of_saved_activations(
        one_chip, monkeypatch):
    """Where the parameters are whole on the device the layers are lowered unrolled:
    no ``while``, so no ``(12, 24, 1024, ...)`` stack that every saved activation is
    written into and sliced (and, for the kernels, copied) out of; a kernel call a
    layer where the loop's body held one; less temporary memory than the loop."""
    text, stack_writes, temp = _gpt2_125m_gradient(one_chip, monkeypatch)
    assert " while(" not in text and stack_writes == []
    assert text.count("tpu_custom_call") == 36
    assert all(text.count(kernel) >= 12
               for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"))
    # delta is made inside flash_bwd_dq: no float32 do * o re-laid for a reduce_sum;
    # and dq | dk | dv are joined inside their consumers, not written out by three
    # in-place updates of a (24, 1024, 2304) buffer a layer
    assert "f32[24,1024,768]{1,2,0" not in text
    assert not re.findall(r"bf16\[24,1024,2304\]\S* dynamic-update-slice\(", text)
    looped, looped_writes, looped_temp = _gpt2_125m_gradient(one_chip, monkeypatch, unrolled=False)
    assert looped.count(" while(") == 2 and len(looped_writes) >= 5
    assert looped.count("tpu_custom_call") == 3
    assert temp < looped_temp


def _pallas_calls(fn, shapes, dtype):
    """(name, grid, operands, results, block sizes of each, aliases, which grid axes
    are ``parallel`` and which ``arbitrary``) of every ``pallas_call`` ``fn`` traces
    to: what a kernel is called WITH, as the trace has it and no compiler has touched
    it."""
    from deepspeed_tpu.analysis.jaxpr_passes import subjaxprs
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grid = eqn.params["grid_mapping"]
                found.append((eqn.params["name"], grid.grid, grid.num_inputs,
                              grid.num_outputs, tuple(
                                  tuple(getattr(size, "block_size", size)
                                        for size in block.block_shape)
                                  for block in grid.block_mappings),
                              tuple(eqn.params["input_output_aliases"]),
                              "".join(axis[0] for axis in eqn.params["compiler_params"][
                                  "mosaic_tpu"].dimension_semantics)))
            for sub in subjaxprs(eqn):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*(jax.ShapeDtypeStruct(s, dtype) for s in shapes)).jaxpr)
    return found


def _tiles(rows, width=128):
    return (1, rows, width)


# What each serving prefill and the two training gradients (the fused projection's and
# the split one's: the same blocks, k and v found by a lane offset) call the kernels
# with: PR 45 changed what a remat policy KEEPS of the fused path and no call.
_STATS = (1, 2, 1, 8, 1024)
_GRADIENT = [
    ("flash_fwd", (24, 6, 1, 1), 3, 2, (_tiles(1024),) * 4 + (_STATS,), (), "ppaa"),
    # q, k, v, do, o and lse in; dq and delta out (PR 56: delta is made here)
    ("flash_bwd_dq", (24, 6, 1, 1), 6, 2,
     (_tiles(1024),) * 5 + (_STATS,) + (_tiles(1024), _STATS), (), "ppaa"),
    ("flash_bwd_dkv", (24, 6, 1, 1), 6, 2,
     (_tiles(1024),) * 4 + (_STATS,) * 2 + (_tiles(1024),) * 2, (), "ppaa")]
_CALLS = {
    "bloom-prefill": [
        ("flash_fwd", (1, 32, 1, 1), 4, 2,
         (_tiles(512),) * 3 + ((1, 8, 128), _tiles(512), (1, 1, 1, 8, 512)), (), "ppaa")],
    "sdar-prefill": [
        ("flash_fwd", (1, 32, 1, 1), 3, 2,
         (_tiles(512),) * 4 + ((1, 1, 1, 8, 512),), (), "ppaa")],
    "d64-prefill": [
        ("flash_fwd", (1, 16, 1, 1), 3, 2,
         (_tiles(512),) * 4 + ((1, 2, 1, 8, 512),), (), "ppaa")],
    "gpt2-split-gradient": _GRADIENT,
    "gpt2-fused-gradient": _GRADIENT,
}


@pytest.mark.parametrize("case", sorted(_CALLS))
def test_the_kernels_are_called_with_the_blocks_they_were(case):
    from deepspeed_tpu.ops.attention import flash
    make, shapes, dtype = _flash_cases()[case]
    calls = _pallas_calls(make(flash), shapes, dtype)
    assert [call[0] for call in calls] == [call[0] for call in _CALLS[case]]
    for got, want in zip(calls, _CALLS[case]):
        assert got == want, f"{got[0]}: grid, operands, results, blocks, aliases, axes"


# ------------------------------------------------------------- sarvam-105b (PR 57)
@pytest.mark.parametrize("tokens", [32, 512, 2048, 4096, 6143])
def test_the_gated_expert_kernel_compiles_at_sarvams_published_widths(
        one_chip, tokens, monkeypatch):
    """``moe_grouped_ffn`` in its gated form at sarvam-105b's widths, the cell
    ``sarvam-105b.doc4k32``'s kernel shape ``(16, 4096, 2048)`` (the chip's 16
    of 128 experts), the top 8: the fifth and largest expert size on the chip
    (50.3 MB). Three whole-matrix blocks, double-buffered, are 96 MiB: past
    the kernel's 64 MiB, so the experts' width is cut in two
    (``width_blocks``) and the kernel called once a half, 48 MiB in flight;
    every size the chip had before stays whole, one call. A decode step's
    tiles are 16 rows, the 512 bucket's 32, and from 128 rows an expert (the
    2,048 bucket on: the cell's 4,096) 128 rows, still in two width blocks."""
    from deepspeed_tpu.ops.moe import grouped_ffn as g
    monkeypatch.setattr(g, "_interpret", lambda: False)
    held, d, width, k = 16, 4096, 2048, 8
    tm = g.tile_rows(tokens * k, 128)
    assert tm == {32: 16, 512: 32}.get(tokens, 128)     # 128 rows an expert and more
    tiles = tokens * k // tm + held
    assert g.width_blocks(d, width, 3, 2, tm) == 2
    assert 2 * 3 * d * (width // 2) * 2 + g.tile_bytes(tm, d, width // 2, 2) \
        < g.VMEM_LIMIT_BYTES
    for l, f, mats in ((1024, 2688, 2), (2048, 768, 3), (2048, 1792, 3), (4096, 768, 3)):
        assert {g.width_blocks(l, f, mats, 2, rows) for rows in (16, 32, 128)} == {1}

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    up = sds((held, d, width), jnp.bfloat16)
    text = jax.jit(functools.partial(g.grouped_ffn, act=jax.nn.silu, tm=tm)).lower(
        sds((tiles * tm, d), jnp.bfloat16), sds((tiles,), jnp.int32),
        sds((tiles,), jnp.int32), up, sds((held, width, d), jnp.bfloat16),
        w_gate=up).compile().as_text()
    assert text.count("tpu_custom_call") == 2 and "moe_grouped_ffn" in text
    # no half of a matrix is copied out: the halves are picked in the index maps
    assert f"bf16[{held},{d},{width // 2}]" not in text


def test_sarvams_prefill_expert_layer_pays_for_what_is_held(one_chip, monkeypatch):
    """One expert layer of ``sarvam-105b.doc4k32``'s 4,096-token prefill
    (4,096 x 8 assignments, 16 held of 128, 4096 x 2048 gated) compiles for
    the described v5e under ``VMEM_LIMIT_BYTES``, and its program does not do
    the worst case's work (PR 58), at the height ``tile_rows`` gives 256 rows
    an expert (128: PR 60) and ``width_blocks`` at that height (two): the
    plan holds no ``A x A`` and no ``R x A`` comparison (prefix sums and one scatter at this size), the two width
    blocks are two kernel calls of which the second is handed the first's
    result aliased, and no ``f32[34816,4096]`` is added outside them."""
    from deepspeed_tpu.ops.moe import grouped_ffn as g
    monkeypatch.setattr(g, "_interpret", lambda: False)
    tokens, held, d, width, k = 4096, 16, 4096, 2048, 8
    rows = g.plan_rows(tokens * k, held, 128)
    assert rows == 34816 and g.tile_rows(tokens * k, 128) == 128
    assert g.width_blocks(d, width, 3, 2, 128) == 2 and g.plan_by_prefix_sums(tokens * k)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def layer(x, idx, w, up, down, gate):
        return g.grouped_experts(x, idx, w, 0, held, 128, up, down, jax.nn.silu, None,
                                 gate)

    up = sds((held, d, width), jnp.bfloat16)
    compiled = jax.jit(layer).lower(
        sds((tokens, d), jnp.bfloat16), sds((tokens, k), jnp.int32),
        sds((tokens, k), jnp.float32), up, sds((held, width, d), jnp.bfloat16),
        up).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "moe_grouped_ffn" in line]
    assert len(calls) == 2 and text.count("tpu_custom_call") == 2
    # call 1 reads call 0's result and writes over it
    first = re.match(r"\s*(%[\w.\-]+) = ", calls[0]).group(1)
    assert first + ")" in calls[1] or first + "," in calls[1]
    assert "output_to_operand_aliasing={{}: (7, {})}" in calls[1]
    result = f"f32[{rows},{d}]"
    assert not [line.strip()[:120] for line in text.splitlines()
                if result in line.split(" = ")[-1][:40] and " add(" in line]
    assert f"[{tokens * k},{tokens * k}]" not in text
    assert f"[{rows},{tokens * k}]" not in text
    assert text.count(" scatter(") == 1 and " sort(" not in text


@pytest.mark.parametrize("tm,blocks", [(128, 2), (128, 4), (256, 2), (256, 4)])
def test_the_tile_the_kernel_is_given_is_counted_against_its_vmem(
        one_chip, tm, blocks, monkeypatch):
    """``width_blocks`` reckons the tile it is given (``tile_bytes``), and the
    chip's compiler agrees on both sides of ``VMEM_LIMIT_BYTES`` at sarvam's
    expert: 128 rows fit beside two width blocks in flight (and four), 256
    rows only beside four (22.5 MiB of activations + 48 MiB of blocks)."""
    from deepspeed_tpu.ops.moe import grouped_ffn as g
    monkeypatch.setattr(g, "_interpret", lambda: False)
    held, d, width, tiles = 16, 4096, 2048, 40
    fits = blocks >= g.width_blocks(d, width, 3, 2, tm)
    assert fits is ((tm, blocks) != (256, 2))
    assert (2 * 3 * d * (width // blocks) * 2 + g.tile_bytes(tm, d, width // blocks, 2)
            <= g.VMEM_LIMIT_BYTES) is fits
    monkeypatch.setattr(g, "width_blocks", lambda *a: blocks)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    up = sds((held, d, width), jnp.bfloat16)
    lowered = jax.jit(functools.partial(g.grouped_ffn, act=jax.nn.silu, tm=tm)).lower(
        sds((tiles * tm, d), jnp.bfloat16), sds((tiles,), jnp.int32),
        sds((tiles,), jnp.int32), up, sds((held, width, d), jnp.bfloat16), w_gate=up)
    if fits:
        assert lowered.compile().as_text().count("tpu_custom_call") == blocks
    else:
        with pytest.raises(Exception, match="vmem"):
            lowered.compile()


@pytest.mark.parametrize("tokens", [512, 4096])
def test_the_flash_forward_compiles_with_queries_and_keys_wider_than_values(
        one_chip, tokens, monkeypatch):
    """sarvam-105b's expanded prefill: 64 heads, queries and keys of 192 lanes
    padded to 256 (two lane tiles a head), values of 128, the head picked in the
    index maps of all four operands: one forward kernel, no transpose."""
    from deepspeed_tpu.ops.attention import flash
    monkeypatch.setattr(flash, "_interpret", lambda: False)
    wide = jax.ShapeDtypeStruct((1, tokens, 64, 256), jnp.bfloat16, sharding=one_chip)
    narrow = jax.ShapeDtypeStruct((1, tokens, 64, 128), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(functools.partial(flash.flash_attention_local,
                                         softmax_scale=0.135234)).lower(
        wide, wide, narrow).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "flash_fwd" in text
    assert " transpose(" not in text
    assert f"bf16[1,{tokens},8192]" in text          # the output has the values' lanes


def test_sarvams_decode_chunk_walks_the_latent_rows_without_relaying_them(
        one_chip, monkeypatch):
    """Two published layers of sarvam-105b (the dense one and an expert layer)
    at the cell's shapes, 32 slots x cap 6144 on pages of 16: the chunk's own
    ``while``, one walk over live blocks a latent layer
    (``latent_decode_attention``) and no other loop; the expert kernel (a call
    a half of the experts' width) is the only Mosaic kernel; a step appends its row without a scatter, nothing the
    shape of the pages is copied, and the view is never made float32 (the two
    products take the rows as stored)."""
    import json
    import os
    from deepspeed_tpu.inference.decode_fns import (build_paged_decode_chunk,
                                                    make_slot_select_fn)
    from deepspeed_tpu.models.causal_lm import sarvam_mla_cfg
    from deepspeed_tpu.ops.moe import grouped_ffn as g
    monkeypatch.setattr(g, "_interpret", lambda: False)
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    with open(os.path.join(root, "benchmarks", "chipbench", "configs",
                           "sarvam-105b.json")) as f:
        model = json.load(f)["model"]
    slots, cap, pages, page, chunk = 32, 6144, 12289, 16, 8
    cfg = sarvam_mla_cfg(max_seq_len=cap, **dict(model, num_hidden_layers=2))
    assert cfg.layer_pattern == "LFLE"

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    module, params, caches, _ = _abstract_model(cfg, slots, cap, pages, page, sds)
    assert caches[0]["k"].shape == (pages, 1, page, 640) and caches[1] == {}
    fn = build_paged_decode_chunk(module, lambda p: p,
                                  make_slot_select_fn(False, 1.0, 0, 1.0),
                                  chunk, kv_cap=cap, with_stats=True)
    text = jax.jit(fn, donate_argnums=(2,)).lower(
        params, sds((slots, 1)), caches, sds((slots, cap // page)), sds((slots,)),
        sds((slots,), jnp.bool_), sds((slots,)), sds((slots,)), sds((slots,)),
        sds((slots,)), sds((2,), jnp.uint32)).compile().as_text()
    loops = [line for line in text.splitlines() if " while(" in line]
    walks = [line for line in loops
             if "ds.attn.latent/jit(latent_decode_attention)/while" in line]
    assert len(loops) == 3 and len(walks) == 2, [
        line.split(" = ")[0].strip() for line in loops]
    assert text.count("tpu_custom_call") == 2 and "moe_grouped_ffn" in text   # two halves
    assert " scatter(" not in text
    assert _pool_relayouts(text, (pages, 1, page, 640)) == []
    assert f"f32[{slots},{cap},640]" not in text and f"f32[{slots},1,{cap},640]" not in text


# ------------------------------------------------ phi-4-mini-flash (SambaY), PR 59
def _phi4flash(layers=8):
    import json
    import os
    from deepspeed_tpu.models.causal_lm import phi4flash_cfg
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    with open(os.path.join(root, "benchmarks", "chipbench", "configs",
                           "phi-4-mini-flash-reasoning.json")) as f:
        model = json.load(f)["model"]
    return phi4flash_cfg(max_seq_len=6144, dtype=jnp.bfloat16,
                         **dict(model, num_hidden_layers=layers))


@pytest.mark.parametrize("tokens", [512, 4096])
def test_the_selective_scan_compiles_at_the_published_widths(one_chip, tokens, monkeypatch):
    """``selective_scan`` at Phi-4-mini-flash's Mamba-1 widths (5,120 channels,
    16 state lanes) for the cell's two prefill buckets: ten channel tiles of
    512, time blocks of 128, the state in VMEM scratch."""
    import importlib
    ss = importlib.import_module("deepspeed_tpu.ops.ssm.selective_scan")
    monkeypatch.setattr(ss, "_interpret", lambda: False)
    c, n = 5120, 16

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    text = jax.jit(lambda *a: ss.selective_scan(*a)).lower(
        sds(1, tokens, c), sds(1, tokens, c), sds(n, c), sds(1, tokens, n),
        sds(1, tokens, n), sds(c)).compile().as_text()
    assert text.count("tpu_custom_call") == 1 and "selective_scan" in text
    assert " while(" not in text


@pytest.mark.parametrize("tokens", [512, 4096])
def test_the_flash_forward_compiles_under_a_window(one_chip, tokens, monkeypatch):
    """A windowed layer's prefill: 40 padded query heads of a differential
    pair's 128 lanes, window 512 in blocks of 512 (what ``_band_attention``
    asks for)."""
    from deepspeed_tpu.ops.attention import flash
    monkeypatch.setattr(flash, "_interpret", lambda: False)
    x = jax.ShapeDtypeStruct((1, tokens, 40, 128), jnp.bfloat16, sharding=one_chip)
    text = jax.jit(lambda q, k, v: flash.flash_attention(
        q, k, v, causal=True, softmax_scale=0.125, block_q=512, block_k=512,
        window=512)).lower(x, x, x).compile().as_text()
    assert text.count("tpu_custom_call") == 1 and "flash_fwd" in text


def test_phi4flashs_decode_chunk_reads_one_view_through_the_decode_kernel(
        one_chip, monkeypatch):
    """Eight published layers of Phi-4-mini-flash (``S W S W S * G X``: every
    kind) at the cell's shapes, 32 slots x cap 6144 on pages of 16: the chunk's
    own ``while`` and no other loop; ``decode_attention`` four times a step
    (two rings, the full layer, the cross layer over the SAME view),
    ``selective_step`` three times (a Mamba-1 layer's update, a kernel for its
    rounding) and no other Mosaic kernel; ONE layer's pages are gathered, a step appends its
    rows without a scatter and nothing the shape of the pages is copied."""
    from deepspeed_tpu.inference.decode_fns import (build_paged_decode_chunk,
                                                    make_slot_select_fn)
    import importlib
    from deepspeed_tpu.ops.attention import decode
    ss = importlib.import_module("deepspeed_tpu.ops.ssm.selective_scan")
    monkeypatch.setattr(decode, "_interpret", lambda: False)
    monkeypatch.setattr(ss, "_interpret", lambda: False)
    slots, cap, pages, page, chunk = 32, 6144, 12289, 16, 8
    cfg = _phi4flash(8)
    assert cfg.layer_pattern == "SFWFSFWFSF*FGFXF"

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    module, params, caches, kv_shape = _abstract_model(cfg, slots, cap, pages, page, sds)
    assert kv_shape == (pages, 10, page, 128)
    assert caches[2]["k"].shape == (slots, 10, 512, 128)          # a ring a slot
    assert caches[0]["ssm"].shape == (slots, 16, 5120) and caches[14] == {}
    fn = build_paged_decode_chunk(module, lambda p: p,
                                  make_slot_select_fn(False, 1.0, 0, 1.0),
                                  chunk, kv_cap=cap)
    text = jax.jit(fn, donate_argnums=(2,)).lower(
        params, sds((slots, 1)), caches, sds((slots, cap // page)), sds((slots,)),
        sds((slots,), jnp.bool_), sds((slots,)), sds((slots,)), sds((slots,)),
        sds((slots,)), sds((2,), jnp.uint32)).compile().as_text()
    assert len([line for line in text.splitlines() if " while(" in line]) == 1
    assert text.count("tpu_custom_call") == 7
    assert "decode_attention" in text and "selective_step" in text
    assert " scatter(" not in text
    assert _pool_relayouts(text, kv_shape) == []


def test_phi4flashs_prefill_stops_at_the_full_layer(one_chip, monkeypatch):
    """The same eight layers' 4,096-token miss prefill: three selective scans,
    two banded flash calls, and ``decode_attention`` twice (the full layer's
    one query a sequence and the cross layer's, over the rows just written);
    the memory unit's and the cross layer's matmuls have one row, and every
    leaf of the donated batch-1 cache is aliased to a result."""
    import importlib
    from deepspeed_tpu.analysis.donation import _alias_param_positions, _flat_args_info
    from deepspeed_tpu.inference.decode_fns import build_prefill, make_slot_select_fn
    from deepspeed_tpu.inference.serving.executor import _prefill
    from deepspeed_tpu.models.causal_lm import init_cache
    from deepspeed_tpu.ops.attention import decode, flash
    ss = importlib.import_module("deepspeed_tpu.ops.ssm.selective_scan")
    for mod in (decode, flash, ss):
        monkeypatch.setattr(mod, "_interpret", lambda: False)
    cfg, cap, bucket = _phi4flash(8), 6144, 4096

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    module, params, _, _ = _abstract_model(cfg, 32, cap, 12289, 16, sds)
    one = jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda: init_cache(cfg, 1, cap, dtype=cfg.dtype)))
    fn = _prefill(build_prefill(module, lambda p: p),
                  make_slot_select_fn(False, 1.0, 0, 1.0), cfg, cap, cfg.dtype)
    lowered = jax.jit(fn, donate_argnums=(1,), keep_unused=True).lower(
        params, one, sds((1, bucket)), sds((2,)), sds((2,), jnp.uint32))
    text = lowered.compile().as_text()
    assert text.count("tpu_custom_call") == 7 and " while(" not in text
    for kernel in ("selective_scan", "flash_fwd", "decode_attention"):
        assert kernel in text, kernel
    hlo = lowered.as_text()
    assert f"tensor<1x{bucket}x10240xbf16>" in hlo   # a Mamba in_proj at every position
    assert "tensor<1x1x5120xbf16>" in hlo           # the memory unit's gate at one
    assert f"tensor<1x{bucket}x20480xbf16>" not in hlo and "tensor<1x1x10240xbf16>" in hlo
    donated = [i for i, (_, info) in enumerate(_flat_args_info(lowered)) if info.donated]
    assert len(donated) == len(jax.tree_util.tree_leaves(one)) > 0
    assert len(_alias_param_positions(text)) == len(donated)
