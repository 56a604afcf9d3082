"""Shared compiled-step builders for generation: prefill, whole-batch decode loop,
and fixed-shape chunked decode.

This is the factored-out core of ``InferenceEngine._loop_fns``: the single-call
``generate`` path keeps its one-``lax.while_loop``-per-call shape (the XLA analogue
of CUDA-graph replay), while the serving executor composes the same prefill with
:func:`build_paged_decode_chunk` — K fixed steps over a fixed slot-batch, returning to the
host between chunks so the continuous-batching scheduler can admit/retire requests
mid-stream. Both paths share the token-selection closures here, so sampling
semantics cannot drift between them.

Key-stream contract: the batched :func:`make_select_fn` draws ONE key per step for
the whole batch (cheap, but a row's sample depends on its batch position);
:func:`make_slot_select_fn` folds a per-slot ``(seed, step)`` into the base key, so
a request's sampled tokens are a pure function of its own seed and token index —
independent of which KV slot it lands in and of who shares the slot-batch. Serving
needs the latter: continuous batching re-binds requests to slots arbitrarily.
"""

import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import scope
from ..parallel.overlap import overlap_scope


def apply_model(module, params, with_stats: bool, *args, **kw):
    """``module.apply`` -> ``(its outputs, stats)``. With ``with_stats`` the
    counts the model's expert layers sow (``stats`` collection: assignments on
    held experts, distinct held experts read) come back summed over the layers
    as a ``(2,)`` int32; without, the plain call and ``None``."""
    if not with_stats:
        return module.apply({"params": params}, *args, **kw), None
    out, sown = module.apply({"params": params}, *args, mutable=["stats"], **kw)
    leaves = jax.tree_util.tree_leaves(sown)
    with scope("moe.plan"):
        return out, (sum(leaves) if leaves else jnp.zeros((2,), jnp.int32))


def _chunk_body(step_model, slot_select, base_key, seeds, eos_ids):
    """The ``fori_loop`` body every decode chunk shares: one model step
    (``step_model(toks, caches, lens) -> ((logits, caches), stats or None)``),
    token selection, and the per-slot bookkeeping. The carry is ``(toks,
    caches, lens, active, remaining, steps, buf)`` and, where the model step
    hands out stats, their running sum as an eighth element."""

    def body(i, s):
        toks, caches, lens, active, remaining, steps, buf = s[:7]
        (logits, caches), stats = step_model(toks, caches, lens)
        nxt = slot_select(logits[:, -1], base_key, seeds, steps)
        with scope("chunk.state"):
            tok = jnp.where(active[:, None], nxt,
                            jnp.maximum(eos_ids, 0)[:, None]).astype(jnp.int32)
            buf = buf.at[:, i].set(tok[:, 0])
            remaining = remaining - active.astype(jnp.int32)
            finished = jnp.logical_or(tok[:, 0] == eos_ids, remaining <= 0)
            lens = lens + active.astype(jnp.int32)
            steps = steps + active.astype(jnp.int32)
            active = jnp.logical_and(active, jnp.logical_not(finished))
        out = (tok, caches, lens, active, remaining, steps, buf)
        if stats is None:
            return out
        with scope("chunk.state"):
            return out + (s[7] + stats,)

    return body


def logits_transform(do_sample: bool, temperature: float, top_k: int,
                     top_p: float) -> Callable[[Any], Any]:
    """Temperature/top-k/top-p masking over ``(b, V)`` logits (sampling only)."""

    def transform(x):
        x = x / jnp.maximum(temperature, 1e-6)
        if top_k and top_k > 0:
            kth = jnp.sort(x, axis=-1)[:, -top_k][:, None]
            x = jnp.where(x < kth, -jnp.inf, x)
        if top_p < 1.0:
            sorted_logits = jnp.sort(x, axis=-1)[:, ::-1]
            probs = jax.nn.softmax(sorted_logits, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            cutoff_idx = jnp.sum(cum < top_p, axis=-1, keepdims=True)
            cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
            x = jnp.where(x < cutoff, -jnp.inf, x)
        return x

    return transform


def make_select_fn(do_sample: bool, temperature: float, top_k: int, top_p: float):
    """``(b, V)`` logits + one shared key → ``(b, 1)`` tokens (generate path)."""
    transform = logits_transform(do_sample, temperature, top_k, top_p)

    @scope("sample")
    def select(logits, rng):
        if not do_sample:
            return jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        return jax.random.categorical(rng, transform(logits),
                                      axis=-1)[:, None].astype(jnp.int32)

    return select


def make_slot_select_fn(do_sample: bool, temperature: float, top_k: int,
                        top_p: float):
    """``(S, V)`` logits + per-slot ``(seed, step)`` → ``(S, 1)`` tokens.

    Greedy is slot-independent by construction; sampling folds each slot's seed and
    per-request step counter into the base key so co-batched requests never share a
    key stream.
    """
    transform = logits_transform(do_sample, temperature, top_k, top_p)

    @scope("sample")
    def select(logits, base_key, seeds, steps):
        if not do_sample:
            return jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        x = transform(logits)

        def one(row, seed, step):
            key = jax.random.fold_in(jax.random.fold_in(base_key, seed), step)
            return jax.random.categorical(key, row)

        return jax.vmap(one)(x, seeds, steps)[:, None].astype(jnp.int32)

    return select


def build_prefill(module, dequant, overlap=None, with_stats: bool = False):
    """Prefill: one forward over the (right-padded) prompt, logits read only at each
    sequence's last valid position (``logits_positions`` skips the rest of the head
    matmul), KV written into the fixed cache buffers. The rows' real lengths go in
    as ``seq_lens`` too: a layer with a recurrent state takes it after the last
    real token. ``with_stats``: also return the expert layers' counts
    (:func:`apply_model`).

    ``overlap``: the owning engine's ``OverlapConfig`` — installed for the
    duration of the TRACE (``overlap_scope``) so the compiled body bakes in
    that engine's comm-overlap lowering regardless of ambient global state.
    This is ALSO how the fused quantized ring reaches serving: with
    weight-quant row-parallel params AND an active scope, ``quant_dense_apply``
    routes through ``parallel/qring.py`` (intN wire, ``chunk_bits``/
    ``quant_block`` read from this config) instead of the monolithic psum —
    no builder below carries ring-specific code.
    """

    def prefill(params, ids, caches, lens0):
        with overlap_scope(overlap):
            params = dequant(params)
            with scope("chunk.state"):
                where = dict(cache_lens=jnp.zeros_like(lens0),
                             logits_positions=jnp.maximum(lens0 - 1, 0))
            (logits, new_caches), stats = apply_model(
                module, params, with_stats, ids, caches=caches, seq_lens=lens0,
                **where)
        if with_stats:
            return logits[:, 0], new_caches, stats
        return logits[:, 0], new_caches

    return prefill


def build_prefix_prefill(module, dequant, overlap=None):
    """Suffix prefill at a nonzero cache offset — the prefix-cache hit path.

    ``caches`` arrive with a restored prompt-prefix KV slab in rows
    ``[0, prefix_len)``; the forward runs over the (right-padded) suffix only,
    writes suffix K/V at rows ``prefix_len + i``, attends each suffix token over
    prefix + suffix, and reads logits at the suffix's last valid position. The
    prefix's prefill compute is skipped entirely — a cache hit costs one
    suffix-bucket forward instead of a full-prompt one.
    """

    def prefix_prefill(params, ids, caches, prefix_len, suffix_len):
        b, t = ids.shape
        positions = prefix_len[:, None] + jnp.arange(t)[None]
        with overlap_scope(overlap):
            logits, new_caches = module.apply(
                {"params": dequant(params)}, ids, positions=positions,
                caches=caches, cache_lens=prefix_len,
                logits_positions=jnp.maximum(suffix_len - 1, 0),
                prefix_fill=True)
        return logits[:, 0], new_caches

    return prefix_prefill


def build_paged_spec_verify(module, dequant, kv_cap: int, overlap=None):
    """Speculative one-pass verify over a slot-batch.

    ``ids (S, t)`` is each slot's verify window ``[cur_tok, draft_0 ..
    draft_{t-2}]``. Each slot's pages are gathered to the dense view once and
    the forward runs in ``prefix_fill`` mode at cache offset ``lens`` — the
    window's K/V land in rows ``lens + j`` and every window position attends
    over committed rows + the in-window prefix (``key_pos <= query_pos``),
    exactly the suffix-prefill math. Unlike :func:`build_prefix_prefill` the
    LM head runs at EVERY window position (``logits_positions=None``): the
    accept rule needs the target's distribution after each draft prefix.

    Then ONLY the valid window rows ``[lens, lens + valid)`` of live slots
    go back through the page table, as the chunk's do at its end: one slab
    write a page (:func:`~deepspeed_tpu.ops.paged_attention.write_view_rows`;
    no loop, so nothing for ``dequant`` to be hoisted out of). ``valid (S,)``
    is ``spec_len + 1`` — the cur-token row plus the real (un-padded) draft
    rows; where a row is a pad row, an inactive slot's, or at/past
    ``kv_cap``, the slab write keeps what the page held, so released or
    shared pages never change.

    Rollback is the caller's job and is free: rows written past the accepted
    prefix stay stale-but-masked (attention masks ``>= cache_len``) and are
    overwritten by later appends — committing is a ``cache_len`` advance,
    rejecting is not advancing. Returns ``(logits (S, t, V), new_caches)``.
    """
    from ..ops.paged_attention import gather_kv_dense, write_view_rows

    def spec_verify(params, ids, caches, page_table, lens, valid, active):
        params = dequant(params)
        b, t = ids.shape
        with scope("kv.gather"):
            dense = [dict(zip(("k", "v"),
                              gather_kv_dense(c["k"], c["v"], page_table, kv_cap)))
                     for c in caches]
        positions = lens[:, None] + jnp.arange(t)[None]
        with overlap_scope(overlap):
            logits, dense = module.apply(
                {"params": params}, ids, positions=positions,
                caches=dense, cache_lens=lens,
                logits_positions=None, prefix_fill=True)

        with scope("kv.copy_back"):
            new_caches = write_view_rows(
                caches, dense, page_table, lens,
                jnp.where(active, valid, 0).astype(lens.dtype), t, kv_cap)
        return logits, new_caches

    return spec_verify


def build_decode_loop(module, dequant, select, gen_cap: int, overlap=None):
    """Whole-batch run-to-completion decode: ONE ``lax.while_loop`` for all remaining
    tokens, EOS termination as an on-device reduction in the loop condition
    (``InferenceEngine.generate``'s decode shape). Returns ``(buf, n, caches)``:
    the caches come back so that a caller that donates them gives the loop its
    argument's buffers to update in place; one that does not pays a second copy
    of them as the loop's carry (at 64 rows of Granite's recurrent state 6 GB,
    more than the chip has beside the weights)."""

    def decode_loop_inner(params, tok0, caches, lens, n_new, eos, rng):
        # HOISTED param prep: on the XLA fallback path ``dequant`` collapses
        # quant nodes here, OUTSIDE the while_loop — the dequantized weights
        # become loop constants, computed once per dispatch instead of per
        # decode step (HLO-pinned: no int8 operands inside the loop body).
        # On the fused path it is the identity and quantized bytes stream
        # from HBM inside each step's projection kernels.
        params = dequant(params)
        b = tok0.shape[0]
        buf = jnp.zeros((b, gen_cap), jnp.int32).at[:, 0].set(tok0[:, 0])
        finished0 = tok0[:, 0] == eos          # eos = -1 when unused: never matches

        def cond(s):
            i, _, _, _, finished, _ = s
            return jnp.logical_and(i < n_new, jnp.logical_not(jnp.all(finished)))

        def body(s):
            i, tok, caches, lens, finished, buf = s
            positions = lens[:, None]
            logits, caches = module.apply(
                {"params": params}, tok, positions=positions,
                caches=caches, cache_lens=lens)
            tok = select(logits[:, -1], jax.random.fold_in(rng, i))
            with scope("chunk.state"):
                # finished sequences keep emitting eos (HF pad-with-eos behaviour)
                tok = jnp.where(finished[:, None], jnp.maximum(eos, 0), tok)
                finished = jnp.logical_or(finished, tok[:, 0] == eos)
                buf = buf.at[:, i].set(tok[:, 0])
                return i + 1, tok, caches, lens + 1, finished, buf

        # lens is each sequence's append position: the prompt's true length (generated
        # tokens overwrite right-pad slots in the cache; decode masks by cache_len)
        state = (jnp.int32(1), tok0, caches, lens, finished0, buf)
        n, _, caches, _, _, buf = jax.lax.while_loop(cond, body, state)
        return buf, n, caches

    def decode_loop(*args):
        # overlap_scope is a trace-time effect: the while_loop body traces
        # inside it, baking the owning engine's comm-overlap lowering in
        with overlap_scope(overlap):
            return decode_loop_inner(*args)

    return decode_loop


def _dense_view(keeps, caches, page_table, rows: int):
    """The caches a chunk's steps run on: the dense per-slot view (``rows``
    rows) of the layers that keep rows in pages (``keeps``:
    ``CausalLMConfig.layer_keeps``; keys and values, or a latent layer's one
    row a token), every other layer's cache as it is."""
    from ..models.causal_lm import PAGED
    from ..ops.paged_attention import gather_pages_dense
    return [dict(zip(c, gather_pages_dense(tuple(c.values()), page_table, rows)))
            if keep in PAGED else c for keep, c in zip(keeps, caches)]


def _copy_back(keeps, caches, dense, page_table, lens_in, lens, span: int,
               kv_cap: int):
    """The end of a chunk that ran on the dense view: the rows a slot
    appended or committed in it, ``[lens_in, lens)`` (at most ``span``, below
    ``kv_cap``), go from the view into the slot's pages as slab writes
    (:func:`~deepspeed_tpu.ops.paged_attention.write_view_rows`); a layer
    with per-slot state hands on the loop's carry, which IS its state."""
    from ..models.causal_lm import PAGED
    from ..ops.paged_attention import write_view_rows
    paged = [i for i, keep in enumerate(keeps) if keep in PAGED]
    with scope("kv.copy_back"):
        written = write_view_rows([caches[i] for i in paged],
                                  [dense[i] for i in paged], page_table,
                                  lens_in, lens - lens_in, span, kv_cap)
    out = list(dense)
    for i, pages in zip(paged, written):
        out[i] = pages
    return out


def build_paged_decode_chunk(module, dequant, slot_select, chunk_size: int,
                             kv_cap: int, overlap=None,
                             with_stats: bool = False):
    """Fixed-shape chunked decode over a slot-batch: exactly ``chunk_size``
    steps, every shape static, one compile per (slots, total-pages, page, cap,
    chunk, sampling) key.

    Per-slot state (all ``(S,)`` unless noted):

    - ``toks (S, 1)``: each slot's last emitted token (the next step's input);
    - ``lens``: the slot's KV append position — advances only while the slot is
      active, so a retired slot's cache rows below ``lens`` stay intact;
    - ``active``: slot holds a live, unfinished request. Inactive slots still flow
      through the batch (fixed shapes) but emit ``max(eos, 0)`` and freeze;
    - ``remaining``: decode-token budget (prefill's first token already spent);
    - ``eos_ids``: per-request EOS (−1 = none, never matches);
    - ``seeds`` / ``steps``: per-request sampling stream coordinates.

    A slot's real tokens in the returned ``buf (S, chunk_size)`` are the prefix of
    length ``steps_out[s] - steps_in[s]`` — active→inactive is one-way inside a
    chunk, so no gaps. The scheduler harvests on the host between chunks.
    ``with_stats`` appends the expert layers' counts summed over the chunk's
    steps (:func:`apply_model`) to the outputs.

    The caches of the layers that keep keys and values are GLOBAL KV pages
    (``{"k": (P, hk / r, page, r * d), ...}``), read and written through the
    slot's static-shape ``page_table`` row — the table itself never changes
    inside a chunk (pages are bound at admission). A slot's page COUNT is
    runtime data in the table, so page growth across requests never mints a
    compile key (pinned by the analysis sweep's serving lane). A layer with a recurrent state carries its per-slot
    ``{"conv", "ssm"}`` arrays through the loop as they are, and a layer that
    keeps nothing an empty dict.

    The dense per-slot view is gathered ONCE per chunk — hoisted out of the
    ``fori_loop``, same loop-invariance idea as the dequant hoist — and
    carried through the steps; each step runs
    the contiguous-cache decode math of ``engine.generate`` on the carry
    (greedy bit-identity with it is then structural, not analytical) and its
    appended K/V rows go into the pages at the end of the chunk, one slab
    write a page a slot (:func:`_copy_back`: a row a slot never advanced
    past, or at/past ``kv_cap``, keeps what the page held), so they stay the
    source of truth across chunks. A per-step gather cost S·cap
    bytes every step; per-chunk it is 1/K of that. ``kv_cap`` bounds the dense
    view at exactly ``cap`` rows."""
    keeps = module.config.layer_keeps
    stats0 = (jnp.zeros((2,), jnp.int32),) if with_stats else ()

    def decode_chunk(params, toks, caches, page_table, lens, active, remaining,
                     eos_ids, seeds, steps, base_key):
        # hoisted out of the fori_loop body — same loop-invariance contract as
        # build_decode_loop (dequant once per chunk dispatch, not per step)
        params = dequant(params)
        S = toks.shape[0]
        buf = jnp.zeros((S, chunk_size), jnp.int32)

        # the hoisted per-chunk gather, contiguous-cache steps over
        # the dense carry, the appended rows written back into the pages at
        # the end of the chunk — the pages leave/enter the loop nowhere
        lens_in = lens
        with scope("kv.gather"):
            dense = _dense_view(keeps, caches, page_table, kv_cap)

        def step_model(toks, dense, lens):
            return apply_model(module, params, with_stats, toks,
                               positions=lens[:, None], caches=dense,
                               cache_lens=lens)

        body = _chunk_body(step_model, slot_select, base_key, seeds, eos_ids)
        with overlap_scope(overlap):     # trace-time: fori body traces inside
            out = jax.lax.fori_loop(
                0, chunk_size, body,
                (toks, dense, lens, active, remaining, steps, buf) + stats0)
        toks, dense, lens, active, remaining, steps, buf = out[:7]
        new_caches = _copy_back(keeps, caches, dense, page_table, lens_in, lens,
                                chunk_size, kv_cap)
        return (buf, toks, new_caches, lens, active, remaining, steps) + out[7:]

    return decode_chunk


# ------------------------------------------------- generation by diffusion over blocks
def block_unmask(cfg, masked, logits, x0):
    """Which masked positions of a block a denoise forward unmasks (``cfg`` a
    ``CausalLMConfig`` with ``gen_block_length``): ``masked`` (S, B) bool,
    ``logits`` (S, B, V) at the block's positions, ``x0`` (S, B) the token
    chosen at each. ``block / steps`` positions a forward: ``sequential`` the
    leftmost masked; ``low_confidence_static`` the masked positions whose
    chosen token has the largest probability; ``low_confidence_dynamic``
    every masked position whose probability passes the threshold where those
    are at least as many, else the static choice. Ties go to the left."""
    B = cfg.gen_block_length
    n = B // cfg.gen_denoising_steps
    if cfg.gen_remasking == "sequential":
        return masked & (jnp.cumsum(masked, axis=1) <= n)
    lg = logits.astype(jnp.float32)
    chosen = jnp.take_along_axis(lg, x0[..., None], axis=-1)[..., 0]
    conf = jnp.exp(chosen - jax.nn.logsumexp(lg, axis=-1))
    conf = jnp.where(masked, conf, -jnp.inf)
    place = jnp.arange(B)
    ahead = (conf[:, None, :] > conf[:, :, None]) | (
        (conf[:, None, :] == conf[:, :, None]) & (place[None, None, :] < place[None, :, None]))
    static = masked & (jnp.sum(ahead, axis=2) < n)
    if cfg.gen_remasking == "low_confidence_static":
        return static
    high = masked & (conf > cfg.gen_confidence_threshold)
    return jnp.where(jnp.sum(high, axis=1, keepdims=True) >= n, high, static)


def _block_body(cfg, step_model, slot_select, base_key, seeds, eos_ids, steps_in,
                width: int):
    """One FORWARD of generation by blocks over a slot-batch, the body the
    serving chunk and ``InferenceEngine.generate`` share. A forward carries
    TWO blocks a slot: the block in flight (still-masked positions fed as the
    mask token) at rows ``[lens, lens + B)`` of the slot's cache, and behind
    it a block of mask tokens at ``[lens + B, lens + 2B)``. ``step_model(ids
    (S, 2B), caches, lens, second (S,) bool) -> ((logits (S, B, V), caches),
    stats or None)`` (:func:`_block_step_model`) writes the keys and values of
    both, lets the first see rows ``[0, lens + B)`` and the second ``[0, lens
    + 2B)``, and hands out the logits of the second block where ``second``,
    else of the first; ``lens`` does not move in it.

    A slot whose block still has a masked position DENOISES: a token is
    chosen at each masked position from the logits AT it and some are kept
    (:func:`block_unmask`); its second block is padding, which no query of
    the slot's first block sees and which the next forward rewrites. A slot
    whose block has none COMMITS and OPENS THE NEXT BLOCK in the same forward:
    the first block's rows are the finished block's, ``lens += B``, the
    block's generated tokens go to ``buf`` (those the prompt opened it with,
    ``skip`` of them, and those past the tokens asked or an EOS do not), and
    the second block is the next one, all masked, seeing exactly the committed
    blocks and itself: its first positions are unmasked from the logits at its
    rows. A slot that ends at its commit (length or EOS) throws the opened
    block away.

    The carry is ``(blk (S, B), masked (S, B), skip, caches, lens, active,
    remaining, steps, buf (S, width), counts (3,): blocks committed, positions
    unmasked, and commits that opened their next block)`` and, with stats,
    their running sum."""
    B = cfg.gen_block_length
    place = jnp.arange(B, dtype=jnp.int32)
    cols = jnp.arange(width, dtype=jnp.int32)

    def body(i, s):
        blk, masked, skip, caches, lens, active, remaining, steps, buf, counts = s[:10]
        S = blk.shape[0]
        with scope("chunk.state"):
            commit = active & ~jnp.any(masked, axis=1)
            ids = jnp.concatenate(
                [jnp.where(masked, cfg.mask_token_id, blk),
                 jnp.full((S, B), cfg.mask_token_id, blk.dtype)],
                axis=1).astype(jnp.int32)
        (logits, caches), stats = step_model(ids, caches, lens, commit)
        with scope("chunk.state"):
            # the commit: the finished block's tokens to ``buf``
            rel = place[None] - skip[:, None]      # place among the generated tokens
            m = jnp.minimum(B - skip, remaining)
            is_eos = (rel >= 0) & (rel < m[:, None]) & (blk == eos_ids[:, None])
            any_eos = jnp.any(is_eos, axis=1)
            m = jnp.where(any_eos, jnp.argmax(is_eos, axis=1) - skip + 1, m)
            m = jnp.where(commit, m, 0).astype(jnp.int32)
            at = cols[None] - (steps - steps_in)[:, None]
            tok = jnp.take_along_axis(blk, jnp.clip(at + skip[:, None], 0, B - 1),
                                      axis=1)
            buf = jnp.where((at >= 0) & (at < m[:, None]), tok, buf)
            steps = steps + m
            remaining = remaining - m
            active = active & ~(commit & ((remaining <= 0) | any_eos))
            lens = lens + jnp.where(commit, B, 0).astype(lens.dtype)
            masked = masked | commit[:, None]
            skip = jnp.where(commit, 0, skip)
        # the denoise: of the block in flight, or of the block just opened
        with scope("sample"):
            pos = lens[:, None] + place[None]
            x0 = slot_select(logits.reshape(S * B, -1), base_key,
                             jnp.repeat(seeds, B), pos.reshape(-1)).reshape(S, B)
            unmask = block_unmask(cfg, masked, logits, x0) & active[:, None]
        with scope("chunk.state"):
            blk = jnp.where(unmask, x0, blk)
            masked = masked & ~unmask
            counts = counts + jnp.stack([jnp.sum(commit), jnp.sum(unmask),
                                         jnp.sum(commit & active)]).astype(jnp.int32)
        out = (blk, masked, skip, caches, lens, active, remaining, steps, buf, counts)
        if stats is None:
            return out
        with scope("chunk.state"):
            return out + (s[10] + stats,)

    return body


def _block_step_model(module, params, with_stats: bool):
    """:func:`_block_body`'s ``step_model``: one forward of two blocks a
    sequence on the dense caches at offset ``lens``. Only the rows that
    count reach the expert layers (the first block, and the second where it
    is a block: ``seq_lens``), and only the block whose logits are read goes
    through the head (``logits_positions``)."""
    B = module.config.gen_block_length
    place = jnp.arange(B, dtype=jnp.int32)

    def step_model(ids, caches, lens, second):
        with scope("chunk.state"):
            first_row = jnp.where(second, B, 0).astype(jnp.int32)
            where = dict(positions=lens[:, None] + jnp.arange(2 * B)[None],
                         seq_lens=B + first_row,
                         logits_positions=first_row[:, None] + place[None])
        return apply_model(module, params, with_stats, ids, caches=caches,
                           cache_lens=lens, block_step=True, **where)

    return step_model


def block_chunk_width(cfg, forwards: int) -> int:
    """Tokens a slot can emit in ``forwards`` forwards at most. A chunk's
    first forward may commit; a block opened by a commit is unmasked ``block
    / steps`` positions a forward under the ``sequential`` and
    ``low_confidence_static`` orders, so the next commit comes ``steps``
    forwards later; ``low_confidence_dynamic`` may unmask it whole in the
    forward that opens it: a commit every forward."""
    per = 1 if cfg.gen_remasking == "low_confidence_dynamic" else cfg.gen_denoising_steps
    return (1 + (forwards - 1) // per) * cfg.gen_block_length


def block_view_rows(cfg, cap: int) -> int:
    """Rows of the dense cache a block step runs on, for sequences of at most
    ``cap`` tokens: a forward writes two blocks at ``lens`` whatever the slot
    does, and ``_cache_update`` clamps a write that would pass the end back
    over committed rows, so the view holds two blocks of spare rows past
    ``cap``, rounded up so that the decode kernel keeps the key block it has
    at ``cap`` rows."""
    unit = math.gcd(cap, 128)
    return cap + -(-2 * cfg.gen_block_length // unit) * unit


def build_block_decode_chunk(module, dequant, slot_select, forwards: int,
                             kv_cap: int, overlap=None, with_stats: bool = False):
    """The decode chunk of a model that generates by diffusion over blocks:
    exactly ``forwards`` forwards over a slot-batch (:func:`_block_body`),
    every shape static. The pages are gathered into the dense per-slot view
    once (:func:`block_view_rows` rows: the spare ones come from the null
    page), the forwards run on it (a block's rows are written before they
    count: ``lens`` moves only on a commit), and the blocks COMMITTED in the
    chunk, rows ``[lens_in, lens_out)`` of a slot, are copied back into its
    pages at the end, one in-place slab write a page (:func:`_copy_back`; a
    block that was not committed leaves its page as it was). The
    block in flight stays out of the pages: its rows are rewritten by the
    next forward. Per-slot state between chunks: the block's tokens ``blk``,
    which are still ``masked``, and how many of them the prompt gave
    (``skip``); a block with nothing masked is committed, and the next one
    opened, by the next chunk's first forward."""
    cfg = module.config
    keeps = cfg.layer_keeps
    width = block_chunk_width(cfg, forwards)
    rows_view = block_view_rows(cfg, kv_cap)
    stats0 = (jnp.zeros((2,), jnp.int32),) if with_stats else ()

    def decode_chunk(params, blk, masked, skip, caches, page_table, lens, active,
                     remaining, eos_ids, seeds, steps, base_key):
        params = dequant(params)
        S = blk.shape[0]
        buf = jnp.zeros((S, width), jnp.int32)
        ps = next(c["k"].shape[2] for keep, c in zip(keeps, caches)
                  if keep == "kv")
        lens_in = lens
        with scope("kv.gather"):
            # the null page (0) behind every slot's own: the view's spare rows
            table = jnp.pad(page_table, ((0, 0), (0, -(-(rows_view - kv_cap) // ps))))
            dense = _dense_view(keeps, caches, table, rows_view)
        body = _block_body(cfg, _block_step_model(module, params, with_stats),
                           slot_select, base_key, seeds, eos_ids, steps, width)
        with overlap_scope(overlap):
            out = jax.lax.fori_loop(
                0, forwards, body,
                (blk, masked, skip, dense, lens, active, remaining, steps, buf,
                 jnp.zeros((3,), jnp.int32)) + stats0)
        blk, masked, skip, dense, lens, active, remaining, steps, buf, counts = out[:10]
        new_caches = _copy_back(keeps, caches, dense, page_table, lens_in, lens,
                                width, kv_cap)
        return (buf, blk, masked, skip, new_caches, lens, active, remaining, steps,
                counts) + out[10:]

    return decode_chunk


def build_block_decode_loop(module, dequant, slot_select, gen_cap: int, overlap=None):
    """``InferenceEngine.generate``'s loop for a model that generates by
    diffusion over blocks: :func:`_block_body` on the contiguous caches
    (:func:`block_view_rows` rows for their cap) in ONE ``lax.while_loop``
    until no row is active. ``remaining`` (rows,) are the tokens asked of
    each row (0: a row that holds nothing); returns ``buf`` (rows, gen_cap),
    a row's tokens its prefix, the rest ``max(eos, 0)``, each row's count of
    tokens and the forwards the loop ran."""
    cfg = module.config

    def decode_loop_inner(params, blk, masked, skip, caches, lens, remaining, eos_ids,
                          seeds, base_key):
        params = dequant(params)
        rows = blk.shape[0]
        buf = jnp.broadcast_to(jnp.maximum(eos_ids, 0)[:, None],
                               (rows, gen_cap)).astype(jnp.int32)
        zeros = jnp.zeros((rows,), jnp.int32)
        body = _block_body(cfg, _block_step_model(module, params, False), slot_select,
                           base_key, seeds, eos_ids, zeros, gen_cap)
        state = (jnp.int32(0), (blk, masked, skip, caches, lens, remaining > 0,
                                remaining, zeros, buf, jnp.zeros((3,), jnp.int32)))
        n, out = jax.lax.while_loop(lambda s: jnp.any(s[1][5]),
                                    lambda s: (s[0] + 1, body(s[0], s[1])), state)
        return out[8], out[7], n

    def decode_loop(*args):
        with overlap_scope(overlap):
            return decode_loop_inner(*args)

    return decode_loop


def open_block(cfg, prompt_tail):
    """Host state of a sequence's first block: ``prompt_tail`` are the
    ``P % block`` prompt tokens after its last whole block. Returns ``(blk
    (B,), masked (B,) bool, skip)``: the block opens with them, unmasked."""
    B = cfg.gen_block_length
    r = len(prompt_tail)
    blk = np.zeros(B, np.int32)
    blk[:r] = prompt_tail
    return blk, np.arange(B) >= r, r
