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

from typing import Any, Callable

import jax
import jax.numpy as jnp

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
        tok = jnp.where(active[:, None], nxt,
                        jnp.maximum(eos_ids, 0)[:, None]).astype(jnp.int32)
        buf = buf.at[:, i].set(tok[:, 0])
        remaining = remaining - active.astype(jnp.int32)
        finished = jnp.logical_or(tok[:, 0] == eos_ids, remaining <= 0)
        lens = lens + active.astype(jnp.int32)
        steps = steps + active.astype(jnp.int32)
        active = jnp.logical_and(active, jnp.logical_not(finished))
        out = (tok, caches, lens, active, remaining, steps, buf)
        return out if stats is None else out + (s[7] + stats,)

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
            (logits, new_caches), stats = apply_model(
                module, dequant(params), with_stats, ids, caches=caches,
                cache_lens=jnp.zeros_like(lens0),
                logits_positions=jnp.maximum(lens0 - 1, 0), seq_lens=lens0)
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
    are mirrored back through the page table (the chunk's end-of-chunk
    writeback idiom). ``valid (S,)`` is ``spec_len + 1`` — the cur-token row
    plus the real (un-padded) draft rows; pad rows, inactive slots, and rows
    at/past ``kv_cap`` route to the out-of-range page index and the scatter
    drops them, so released or shared pages are never written.

    Rollback is the caller's job and is free: rows written past the accepted
    prefix stay stale-but-masked (attention masks ``>= cache_len``) and are
    overwritten by later appends — committing is a ``cache_len`` advance,
    rejecting is not advancing. Returns ``(logits (S, t, V), new_caches)``.

    The mirror is a ``fori_loop`` over the window rows — the loop the
    analysis sweep's dequant pin targets: ``dequant`` collapses the quantized
    params ONCE above it, so int8 payloads must never appear as loop-body
    inputs (the same loop-invariance contract as the decode-chunk body).
    """
    from ..ops.paged_attention import gather_kv_dense, page_address

    def spec_verify(params, ids, caches, page_table, lens, valid, active):
        # hoisted: dequant once per verify dispatch, never inside the mirror
        params = dequant(params)
        b, t = ids.shape
        ps = caches[0]["k"].shape[2]
        P_total = caches[0]["k"].shape[0]
        dense = [dict(zip(("k", "v"),
                          gather_kv_dense(c["k"], c["v"], page_table, kv_cap)))
                 for c in caches]
        positions = lens[:, None] + jnp.arange(t)[None]
        with overlap_scope(overlap):
            logits, dense = module.apply(
                {"params": params}, ids, positions=positions,
                caches=dense, cache_lens=lens,
                logits_positions=None, prefix_fill=True)

        def mirror(j, pages):
            rows = lens + j
            pidx, off = page_address(page_table, rows, kv_cap, ps, P_total,
                                     live=lambda: active & (j < valid))
            idx = jnp.minimum(rows, kv_cap - 1)[:, None, None, None]
            out = []
            for c, dn in zip(pages, dense):
                k_new = jnp.take_along_axis(dn["k"], idx, axis=2)[:, :, 0, :]
                v_new = jnp.take_along_axis(dn["v"], idx, axis=2)[:, :, 0, :]
                out.append(
                    {"k": c["k"].at[pidx, :, off, :].set(
                        k_new.astype(c["k"].dtype)),
                     "v": c["v"].at[pidx, :, off, :].set(
                        v_new.astype(c["v"].dtype))})
            return out

        new_caches = jax.lax.fori_loop(0, t, mirror, list(caches))
        return logits, new_caches

    return spec_verify


def build_decode_loop(module, dequant, select, gen_cap: int, overlap=None):
    """Whole-batch run-to-completion decode: ONE ``lax.while_loop`` for all remaining
    tokens, EOS termination as an on-device reduction in the loop condition
    (``InferenceEngine.generate``'s decode shape)."""

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
            # finished sequences keep emitting eos (HF pad-with-eos behaviour)
            tok = jnp.where(finished[:, None], jnp.maximum(eos, 0), tok)
            finished = jnp.logical_or(finished, tok[:, 0] == eos)
            buf = buf.at[:, i].set(tok[:, 0])
            return i + 1, tok, caches, lens + 1, finished, buf

        # lens is each sequence's append position: the prompt's true length (generated
        # tokens overwrite right-pad slots in the cache; decode masks by cache_len)
        state = (jnp.int32(1), tok0, caches, lens, finished0, buf)
        n, _, _, _, _, buf = jax.lax.while_loop(cond, body, state)
        return buf, n

    def decode_loop(*args):
        # overlap_scope is a trace-time effect: the while_loop body traces
        # inside it, baking the owning engine's comm-overlap lowering in
        with overlap_scope(overlap):
            return decode_loop_inner(*args)

    return decode_loop


def build_paged_decode_chunk(module, dequant, slot_select, chunk_size: int,
                             kv_cap: int, overlap=None, fused: bool = False,
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
    (``{"k": (P, hk, page, d), ...}``) and each step writes at the page-mapped
    row of the slot's static-shape ``page_table`` row — the table itself never
    changes inside a chunk (pages are bound at admission), so it rides as a
    loop constant. A slot's page COUNT is runtime data in the table, so page
    growth across requests never mints a compile key (pinned by the analysis
    sweep's serving lane). A layer with a recurrent state carries its per-slot
    ``{"conv", "ssm"}`` arrays through the loop as they are, and a layer that
    keeps nothing an empty dict.

    ``fused=True`` (TPU / ``DS_TPU_PAGED_FORCE_FUSED=1``): each step attends
    straight against the pages through the Pallas gather-by-page-index kernel
    — the dense view never materialises.

    ``fused=False`` (the XLA fallback): the dense per-slot view is gathered
    ONCE per chunk — hoisted out of the ``fori_loop``, same loop-invariance
    idea as the dequant hoist — and carried through the steps; each step runs
    the contiguous-cache decode math of ``engine.generate`` on the carry
    (greedy bit-identity with it is then structural, not analytical) and its
    appended K/V rows are mirrored into the pages at the end of the chunk so
    they stay the source of truth across chunks. A per-step gather cost S·cap
    bytes every step; per-chunk it is 1/K of that. ``kv_cap`` bounds the dense
    view at exactly ``cap`` rows."""
    from ..ops.paged_attention import gather_kv_dense, page_address
    stats0 = (jnp.zeros((2,), jnp.int32),) if with_stats else ()

    def decode_chunk(params, toks, caches, page_table, lens, active, remaining,
                     eos_ids, seeds, steps, base_key):
        # hoisted out of the fori_loop body — same loop-invariance contract as
        # build_decode_loop (dequant once per chunk dispatch, not per step)
        params = dequant(params)
        S = toks.shape[0]
        buf = jnp.zeros((S, chunk_size), jnp.int32)

        if fused:
            def step_model(toks, caches, lens):
                return apply_model(module, params, with_stats, toks,
                                   positions=lens[:, None], caches=caches,
                                   cache_lens=lens, page_table=page_table,
                                   kv_cap=kv_cap)

            body = _chunk_body(step_model, slot_select, base_key, seeds, eos_ids)
            with overlap_scope(overlap):
                out = jax.lax.fori_loop(
                    0, chunk_size, body,
                    (toks, caches, lens, active, remaining, steps, buf) + stats0)
            toks, caches, lens, active, remaining, steps, buf = out[:7]
            return (buf, toks, caches, lens, active, remaining, steps) + out[7:]

        # XLA fallback: hoisted per-chunk gather, contiguous-cache steps over
        # the dense carry, ONE end-of-chunk mirror of the appended rows back
        # into the pages — the pages leave/enter the loop nowhere
        paged = [c for c in caches if "k" in c]
        ps = paged[0]["k"].shape[2]
        P_total = paged[0]["k"].shape[0]
        lens_in = lens
        dense = [dict(zip(("k", "v"),
                          gather_kv_dense(c["k"], c["v"], page_table, kv_cap)))
                 if "k" in c else c for c in caches]

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
        # mirror rows [lens_in, lens) (this chunk's appends) into the pages;
        # rows a slot never advanced past, or beyond cap, are dropped
        done = lens - lens_in
        new_caches = []
        for c, dn in zip(caches, dense):
            if "k" not in c:             # per-slot state: the loop's carry IS it
                new_caches.append(dn)
                continue
            k_p, v_p = c["k"], c["v"]
            for j in range(chunk_size):
                rows = lens_in + j
                pidx, off = page_address(page_table, rows, kv_cap, ps, P_total,
                                         live=lambda: j < done)
                idx = jnp.minimum(rows, kv_cap - 1)[:, None, None, None]
                k_new = jnp.take_along_axis(dn["k"], idx, axis=2)[:, :, 0, :]
                v_new = jnp.take_along_axis(dn["v"], idx, axis=2)[:, :, 0, :]
                k_p = k_p.at[pidx, :, off, :].set(k_new.astype(k_p.dtype))
                v_p = v_p.at[pidx, :, off, :].set(v_new.astype(v_p.dtype))
            new_caches.append({"k": k_p, "v": v_p})
        return (buf, toks, new_caches, lens, active, remaining, steps) + out[7:]

    return decode_chunk
