"""Traffic kind ``serve_closed``: callers that each wait for their reply.

``clients`` callers drive one ``ContinuousBatchingScheduler`` in this process:
a caller submits its next request when the ``step()`` that finished its
previous one returns. The lengths of the requests are a fixed list that
``lengths.py`` draws from the distribution the traffic file names, the same
for every seed; the seed orders each cycle of the list and draws the tokens. With ``document_tokens`` > 0 a caller's prompt is its
current document followed by the question, and it asks ``asks_per_document``
questions before it moves to the next document.

Set-up builds the engine and the scheduler, then serves one request for every
prefill bucket (and, with documents, every suffix-prefill bucket) that the
list reaches, so that nothing compiles later. The loop then starts; the
window opens at the return of the ``step()`` after which every caller has
finished a request, and closes at the first return ``--seconds`` later. A
request counts if it was submitted and finished inside the window.

Reported: the median first-token time; the time per output token over ALL
the window's requests (the sum of their decode times over the sum of their
tokens after the first); and the 90th percentile, over every delivery of
tokens to a stream, of the time since that stream's previous delivery (tokens
arrive a decode chunk at a time, and a prefill of the other caller's request
inside a step holds the delivery up). The per-request gap's median and 90th
percentile are printed and read by per-layer metrics.

Clocks: ``submitted`` and every ``step()`` are taken here on
``time.monotonic()``; the first token's time is the scheduler's own stamp
``handle.first_token_at`` (same clock, set when the prefill's token reaches
the host: ``step()`` returns only a decode chunk later). ``handle.ttft`` and
``handle.tpot`` are not read.
"""

import gc
import statistics
import time

import numpy as np

from benchmarks.chipbench import registry, stats
from benchmarks.chipbench.harness import Result, say, seed31
from benchmarks.chipbench.lengths import fixed_requests
from benchmarks.chipbench.probe import first_int_arg_shape, kernel_names


def request_stream(requests, seed: int):
    """Whole cycles of the fixed list, each in an order the seed draws."""
    rng = np.random.default_rng(seed)
    while True:
        for j in rng.permutation(len(requests)):
            yield tuple(requests[int(j)])


class Caller:
    """One closed-loop client: its documents, its request in flight."""

    def __init__(self, index: int, seed: int, vocab: int, document_tokens: int,
                 asks_per_document: int):
        self.index = index
        self.rng = np.random.default_rng(seed)
        self.vocab = vocab
        self.document_tokens = document_tokens
        self.asks = max(1, asks_per_document)
        self.sent = 0
        self.document = None
        self.current = None          # the record of the request in flight
        self.finished = 0

    def tokens(self, n: int) -> np.ndarray:
        return self.rng.integers(1, self.vocab, size=n).astype(np.int32)

    def prompt(self, length: int) -> np.ndarray:
        if not self.document_tokens:
            return self.tokens(length)
        if self.sent % self.asks == 0:
            self.document = self.tokens(self.document_tokens)
        return np.concatenate([self.document, self.tokens(length)])


def build_scheduler(ctx):
    """The engine as ``ds.init_inference`` builds it (weights from the run's
    seed, which ``init_inference`` passes none of) and the scheduler over it."""
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.serving.prefix_cache import PrefixCacheConfig
    from deepspeed_tpu.inference.serving.scheduler import (
        ContinuousBatchingScheduler, ServingConfig)
    from deepspeed_tpu.utils.device import enable_compile_cache
    s = ctx.config["serve"]
    cap = int(s["max_seq_len"])
    # the configuration names the program's builder of its model: its
    # ``model`` section is the builder's keywords
    cfg = registry.resolve(ctx.config["model_builder"])(
        max_seq_len=cap, **ctx.config["model"])
    enable_compile_cache()
    engine = InferenceEngine(cfg, DeepSpeedInferenceConfig(
        dtype=s["dtype"], max_out_tokens=cap), seed=seed31(ctx.seed))
    sched = ContinuousBatchingScheduler(engine, ServingConfig(
        slots=int(s["slots"]), chunk_size=int(s["chunk_size"]), max_seq_len=cap,
        max_queue=int(s["max_queue"]), kv_pool=s["kv_pool"],
        kv_page_size=int(s["kv_page_size"]),
        kv_total_pages=int(s["kv_total_pages"]),
        prefix_cache=PrefixCacheConfig(**s["prefix_cache"])))
    return cfg, engine, sched


def warm_up(ctx, sched, vocab: int, requests):
    """Serve one request per program the traffic reaches; returns what was
    served, ``[(prompt, tokens, prefix hit, whole-bucket miss)]``, for the
    comparisons after the window."""
    tr = ctx.traffic
    rng = np.random.default_rng(seed31(ctx.seed, 7))
    doc_len = int(tr["document_tokens"])
    ex = sched.executor
    out_tokens = int(tr["parity_output_tokens"])
    plans = []                                       # (prompt, kept for parity)
    for n in tr["parity_prompts"]:                   # whole-bucket prompts
        plans.append((rng.integers(1, vocab, size=int(n)).astype(np.int32), True))
    if doc_len:
        # one document: its miss, then one hit for every suffix bucket
        doc = rng.integers(1, vocab, size=doc_len).astype(np.int32)
        by_bucket = {}
        for q, _ in requests:
            by_bucket.setdefault(ex.bucket_for(int(q)), int(q))
        for q in [next(iter(by_bucket.values()))] + list(by_bucket.values()):
            question = rng.integers(1, vocab, size=q).astype(np.int32)
            plans.append((np.concatenate([doc, question]), False))
        # and one hit whose match ends inside a page (it shares the last
        # question's first tokens), so that the copy-on-write program is warm
        again = question.copy()
        again[3:] = rng.integers(1, vocab, size=again.size - 3)
        plans.append((np.concatenate([doc, again]), False))
    else:
        seen = {ex.bucket_for(int(n)) for n in tr["parity_prompts"]}
        for p, _ in requests:
            b = ex.bucket_for(int(p))
            if b not in seen:
                seen.add(b)
                plans.append((rng.integers(1, vocab, size=int(p)).astype(np.int32),
                              False))
    kept = []
    for prompt, keep in plans:
        h = sched.submit(prompt, max_new_tokens=out_tokens)
        sched.run()
        say(f"warm-up: prompt {prompt.size} -> {h.state.value} "
            f"{len(h.tokens)} tokens, prefix hit {h.prefix_hit_tokens}")
        kept.append((prompt, list(h.tokens), int(h.prefix_hit_tokens), keep))
    return kept


def run(ctx) -> Result:
    from deepspeed_tpu.inference.serving.scheduler import RequestState
    tr = ctx.traffic
    cfg, engine, sched = build_scheduler(ctx)
    vocab = int(cfg.vocab_size)
    requests = fixed_requests(tr, int(ctx.config["serve"]["max_seq_len"]))
    served = warm_up(ctx, sched, vocab, requests)
    hits_before = sched.telemetry.prefix_hits

    stream = request_stream(requests, seed31(ctx.seed, 3))
    callers = [Caller(i, seed31(ctx.seed, 11 + i), vocab,
                      int(tr["document_tokens"]), int(tr["asks_per_document"]))
               for i in range(int(tr["clients"]))]
    records, steps = [], []          # every request sent; (s0, s1) of every step
    live_tokens = []                 # tokens in the slots' caches, per step

    def submit(c: Caller):
        length, out = next(stream)
        prompt = c.prompt(int(length))
        with ctx.span("chipbench.submit"):
            t = time.monotonic()
            h = sched.submit(prompt, max_new_tokens=int(out))
        c.sent += 1
        c.current = {"caller": c.index, "handle": h, "submitted": t,
                     "prompt_tokens": int(prompt.size), "asked": int(out),
                     "finished_at": None, "delivered": 0, "delivered_at": None,
                     "delivery_gaps": []}
        records.append(c.current)

    for c in callers:
        submit(c)
    t_open = t_close = None
    trace_at = None
    while t_close is None:
        with ctx.span("chipbench.step"):
            s0 = time.monotonic()
            sched.step()
            s1 = time.monotonic()
        steps.append((s0, s1))
        live_tokens.append(sum(h.prompt.size + len(h.tokens)
                               for h in sched.active_requests))
        if ctx.tracing and s1 - trace_at >= float(tr["traced_seconds"]):
            ctx.stop_trace()
        closing = t_open is not None and s1 - t_open >= ctx.seconds
        for c in callers:
            stats.note_delivery(c.current, len(c.current["handle"].tokens),
                                c.current["handle"].first_token_at, s1)
            if c.current["handle"].done:
                c.current["finished_at"] = s1
                c.finished += 1
                if not closing:
                    submit(c)
        if t_open is None and all(c.finished >= 1 for c in callers):
            t_open = s1
            if ctx.trace:
                ctx.start_trace()
                trace_at = time.monotonic()
        if closing:
            t_close = s1
    ctx.stop_trace()
    ctx.note_memory()

    # ---------------------------------------------------------- the window
    inside = [r for r in records if r["submitted"] >= t_open
              and r["finished_at"] is not None and r["finished_at"] <= t_close]
    dropped = sum(1 for r in records if r["submitted"] >= t_open
                  and r["finished_at"] is None)
    ok, reasons = [], []
    for r in inside:
        h = r["handle"]
        if h.state == RequestState.FINISHED and len(h.tokens) == r["asked"] \
                and all(0 <= t < vocab for t in h.tokens):
            ok.append(r)
    failed = len(inside) - len(ok)
    if failed:
        reasons.append(f"{failed} request(s) did not finish with exactly the "
                       "tokens asked, all inside the vocabulary")
    ttft, tpot, gaps, decode_s = [], [], [], 0.0
    for r in ok:
        h = r["handle"]
        t = stats.request_times(r["submitted"], h.first_token_at,
                                r["finished_at"], len(h.tokens))
        ttft.append(t["ttft_ms"])
        if t["tpot_ms"] is not None:
            tpot.append(t["tpot_ms"])
            decode_s += r["finished_at"] - h.first_token_at
        gaps += [g * 1e3 for g in r["delivery_gaps"]]
        if not any(s0 <= h.first_token_at <= s1 for s0, s1 in steps) \
                or h.first_token_at < r["submitted"]:
            reasons.append(f"request {h.id}: the first-token stamp lies outside "
                           "every step() span after its submit")
    out_tokens = sum(len(r["handle"].tokens) for r in ok)
    window = t_close - t_open
    say(f"requests: sent {len(records)}, in the window {len(inside)} finished "
        f"({failed} failed), {dropped} still running when it closed and dropped")
    if not ok or not tpot:
        reasons.append("no request finished inside the window")
        return Result(window=(t_open, t_close), attempted=len(inside),
                      failed=failed, end_to_end={}, reasons=reasons)
    e2e = {"ttft_p50_ms": statistics.median(ttft),
           "tpot_mean_ms": decode_s * 1e3 / (out_tokens - len(ok)),
           "delivery_gap_p90_ms": stats.percentile(gaps, 90),
           "tpot_p50_ms": statistics.median(tpot),
           "tpot_p90_ms": stats.percentile(tpot, 90)}
    if not ctx.rehearse:
        say(f"output tokens/s over the window: {out_tokens / window:.2f} "
            f"({out_tokens} tokens in {window:.3f} s, {len(steps)} steps in all)")
        say(f"ttft_p50_ms {e2e['ttft_p50_ms']:.3f} over {len(ttft)} requests, "
            "quartiles " + " ".join(f"{x:.1f}" for x in stats.quartiles(ttft))
            + f", 90th percentile {stats.percentile(ttft, 90):.1f}")
        say(f"tpot_mean_ms {e2e['tpot_mean_ms']:.3f} over {out_tokens - len(ok)} "
            f"tokens of {len(tpot)} requests; per request: tpot_p50_ms "
            f"{e2e['tpot_p50_ms']:.3f}, tpot_p90_ms {e2e['tpot_p90_ms']:.3f}, "
            f"{stats.beyond(tpot, 90)} beyond the 90th percentile")
        say(f"delivery_gap_p90_ms {e2e['delivery_gap_p90_ms']:.3f} over "
            f"{len(gaps)} deliveries, {stats.beyond(gaps, 90)} beyond; quartiles "
            + " ".join(f"{x:.1f}" for x in stats.quartiles(gaps)))

    hit_tokens = sum(int(r["handle"].prefix_hit_tokens) for r in ok)
    prompt_tokens = sum(r["prompt_tokens"] for r in ok)
    hit_requests = sum(1 for r in ok if r["handle"].prefix_hit_tokens > 0)
    say(f"prefix cache: {hit_requests} of {len(ok)} requests hit, {hit_tokens} "
        f"of {prompt_tokens} prompt tokens; scheduler counted "
        f"{sched.telemetry.prefix_hits - hits_before} hits since the warm-up")
    if int(tr["document_tokens"]) and not hit_requests:
        reasons.append("no request of the window hit the prefix cache")
    if not int(tr["document_tokens"]) and hit_requests:
        reasons.append(f"{hit_requests} request(s) hit the prefix cache on "
                       "traffic that shares nothing")

    in_window_steps = [(a, b) for a, b in steps if a >= t_open and b <= t_close]
    counters = {
        "requests": len(ok), "hit_tokens": hit_tokens,
        "prompt_tokens": prompt_tokens, "hit_requests": hit_requests,
        "output_tokens": out_tokens, "steps": len(in_window_steps),
        "chunk_size": int(ctx.config["serve"]["chunk_size"]),
        "live_tokens_mean": statistics.fmean(
            [n for (a, b), n in zip(steps, live_tokens)
             if a >= t_open and b <= t_close and n] or [0]),
        "output_tokens_per_s": out_tokens / window,
    }
    reasons += check_routes(ctx)
    reasons += check_parity(ctx, engine, sched, served)
    reasons += check_reference(ctx, engine, served)
    return Result(window=(t_open, t_close), attempted=len(inside), failed=failed,
                  end_to_end=e2e, counters=counters, reasons=reasons,
                  counts_only=("prefix_hit_pct",))


def check_routes(ctx) -> list:
    """The lowered programs hold the routes the configuration states: no
    Mosaic kernel in the decode chunk (ALiBi takes XLA's dense gather), flash
    in a prefill from ``prefill_flash_from`` tokens and none below."""
    routes = ctx.config["routes"]
    out, seen = [], []
    for name, text in ctx.probe.new_modules():
        kernels = kernel_names(text)
        if name == "decode_chunk":
            seen.append(f"decode_chunk: {sorted(kernels) or 'XLA only'}")
            if ctx.on_tpu and sorted(kernels) != sorted(routes["decode_chunk"]):
                out.append(f"decode_chunk holds {sorted(kernels)}, the "
                           f"configuration states {routes['decode_chunk']}")
        elif name == "prefill":
            shape = first_int_arg_shape(text)
            bucket = int(shape.split("x")[-1]) if shape else 0
            seen.append(f"prefill {shape}: {sorted(kernels) or 'XLA only'}")
            want = bucket >= int(routes["prefill_flash_from"]) and bucket % 128 == 0
            if ctx.on_tpu and bucket and ("flash_fwd" in kernels) != want:
                out.append(f"prefill bucket {bucket}: flash "
                           f"{'missing' if want else 'present'}")
        elif name == "suffix_prefill":
            seen.append(f"suffix_prefill {first_int_arg_shape(text)}: "
                        f"{sorted(kernels) or 'XLA only'}")
    say("routes: " + "; ".join(seen))
    return out


def check_parity(ctx, engine, sched, parity) -> list:
    """Outside the window: the warm-up's whole-bucket requests, token for
    token, against ``engine.generate`` (the single-call path shares neither
    scheduler, pool nor prefix cache). Both paths prefill the same padded
    shape. A prefix hit is not compared: its suffix prefill rounds differently
    from a whole-prompt prefill, and with random weights a rounding flips the
    largest logit."""
    out = []
    sched.evict_all("parity")
    sched.executor.pool.caches = None       # the pool's arrays make room
    gc.collect()
    for prompt, served, _, whole_bucket in parity:
        if not whole_bucket:
            continue
        ref = engine.generate(prompt[None, :], max_new_tokens=len(served))
        ref = [int(t) for t in np.asarray(ref)[0, prompt.size:]]
        agree = next((i for i, (a, b) in enumerate(zip(ref, served)) if a != b),
                     len(served))
        say(f"parity vs engine.generate: prompt {prompt.size}: {agree} of "
            f"{len(served)} tokens agree")
        if agree != len(served):
            out.append(f"scheduler {served} != generate {ref} for a prompt of "
                       f"{prompt.size} tokens")
    return out


def check_reference(ctx, engine, served) -> list:
    """Outside the window, against the plain float32 reference the
    configuration names, on the served model's own weights at full depth:
    every request of the warm-up (misses and, with documents, prefix hits
    through the pool and the suffix prefill). The scheduler hands out tokens
    and no logits, so the reference computes its logits for the sequence as
    served (prompt, then the served tokens) and each served token's reference
    logit has to lie within ``tolerance_spreads`` standard deviations (of the
    logits over the vocabulary) of the largest: with random weights a rounding
    may turn the largest logit into the second, and no further. Tokens say
    little about precision where the reference's first choice lies far ahead of
    its second (the printed margin), so the program's model is also held logit
    by logit: its whole-prompt forward, in the served type at full depth, over
    the last positions of the warm-up's first whole-bucket prompt, within
    ``logit_tolerance_spreads``."""
    ref, spec = ctx.reference()
    if ref is None:
        say("reference: the configuration names none; the served tokens are "
            "NOT compared with a reference")
        return []
    tol = float(spec["tolerance_spreads"])
    logit_tol = float(spec["logit_tolerance_spreads"])
    name, model, out = ctx.config["reference"]["module"], ctx.config["model"], []
    for prompt, tokens, hit, _ in served:
        ids = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
        at = np.arange(prompt.size - 1, ids.size)
        logits = ref.next_token_logits(engine.params, model, ids, at)
        spread = float(logits.std(axis=-1).mean())
        short = logits.max(axis=-1) - logits[np.arange(len(tokens)), tokens]
        same = int((logits.argmax(axis=-1) == np.asarray(tokens)).sum())
        worst = float(short.max()) / spread
        top2 = np.sort(logits, axis=-1)[:, -2:]
        margin = float((top2[:, 1] - top2[:, 0]).min()) / spread
        say(f"reference {name} (float32, full depth): prompt {prompt.size}, prefix "
            f"hit {hit}: {same} of {len(tokens)} served tokens are the reference's "
            f"own; the worst lies {worst:.4f} spreads under the reference's largest "
            f"logit (tolerance {tol}; the reference's own second choice lies at "
            f"least {margin:.4f} under)")
        if not worst <= tol:
            out.append(f"a served token lies {worst:.3f} logit spreads under the "
                       f"reference's choice (prompt {prompt.size}, prefix hit {hit})")
    prompt = next((p for p, _, _, whole in served if whole), None)
    if prompt is not None:
        last = min(8, prompt.size)
        got = np.asarray(engine.forward(prompt[None])[0, -last:], np.float32)
        want = ref.next_token_logits(engine.params, model, prompt,
                                     np.arange(prompt.size - last, prompt.size))
        err = float(np.abs(got - want).max()) / float(want.std(axis=-1).mean())
        say(f"reference {name}: the program's forward of {prompt.size} tokens, "
            f"last {last} positions: largest logit error {err:.4f} spreads "
            f"(tolerance {logit_tol})")
        if not err <= logit_tol:
            out.append(f"the program's logits are {err:.3f} spreads off the "
                       f"reference's for a prompt of {prompt.size} tokens")
    return out
