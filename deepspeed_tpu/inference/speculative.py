"""Speculative decoding: draft-propose / one-pass-verify on the decode path.

The r5 north-star measured 7B decode as weight-bandwidth-bound (14.1 GB of
weights stream per step; batching amortizes the stream across rows but
single-stream latency is stuck at one token per pass). Speculation is the
single-stream analogue of batching: a cheap *draft proposer* guesses k tokens,
ONE target forward over the (k+1)-token window scores all of them
(``prefix_fill`` masked attention — the PR 9 suffix-prefill mode), and the
accept rule keeps the longest valid prefix plus one bonus token. Same weight
bytes as a single decode step, up to k+1 tokens out.

Pieces:

- **proposers** — :class:`NgramProposer` (self-speculative: match the recent
  suffix of the prompt+generated stream against its own history; no second
  model, deterministic, CPU-testable) and :class:`DraftModelProposer` (a
  second tiny engine greedy-decodes the draft). Both are deterministic, so a
  draft is a point-mass proposal distribution — rejection sampling below
  stays exact for either.
- **accept rules** — :func:`greedy_accept` is exact longest-prefix-match
  against the verify argmax, which makes greedy speculative output
  bit-identical to non-speculative greedy decode *by construction*: every
  emitted token IS a target argmax. :func:`accept_tokens` adds the sampled
  path: per-slot-keyed rejection sampling (accept draft x with prob
  ``p_target(x)``, resample the rejection from the renormalized residual)
  which preserves the target distribution exactly.
- **rollback** — there is none to do on the KV side: the verify step writes
  the whole window's K/V at rows ``[cache_len, cache_len+valid)`` and the
  caller simply advances ``cache_len`` by the number of tokens actually
  committed. Rows beyond the new ``cache_len`` are attention-masked and get
  overwritten by later appends — a page-table/cache_len rewind, never a copy
  (the same structural argument that makes paged release O(pages)).

Key-stream contract (sampled path): position ``i`` of a request's stream uses
``fold_in(fold_in(base_key, seed), step0 + i)`` — the same per-slot ``(seed,
step)`` coordinates as ``decode_fns.make_slot_select_fn`` — so a request's
sampled tokens are a pure function of its own seed and token index,
independent of slot placement and co-batching. A fully-accepted round's bonus
draw bit-matches ``make_slot_select_fn``'s stream for that position; a
rejection consumes the residual stream instead, so an individual sampled
trajectory may diverge from the speculation-off one after a rejection — what
is preserved exactly is the per-position *distribution* (and greedy output,
which is bit-identical always). The accept test and the residual resample
each fold a distinct constant so the three draws per position never alias.
"""

from dataclasses import dataclass
from typing import List, Tuple

import numpy as np

#: per-position key folds for the sampled accept path (must differ so the
#: accept-uniform and the residual-resample never share a stream with the
#: bonus-token categorical, which uses the UNFOLDED per-step key to match
#: ``make_slot_select_fn`` exactly on a full acceptance)
_FOLD_ACCEPT = 1
_FOLD_RESAMPLE = 2


@dataclass
class SpeculativeConfig:
    """Knobs for the draft-propose / one-pass-verify loop."""
    k: int = 4                      # draft tokens per verify window
    proposer: str = "ngram"         # "ngram" | "draft_model"
    ngram_max: int = 4              # longest suffix-match tried, down to min
    ngram_min: int = 1
    draft_engine: object = None     # tiny InferenceEngine for "draft_model"

    def __post_init__(self):
        if self.k < 1:
            raise ValueError(f"spec k must be >= 1, got {self.k}")
        if self.proposer not in ("ngram", "draft_model"):
            raise ValueError(f"proposer must be 'ngram' or 'draft_model', "
                             f"got {self.proposer!r}")
        if not 1 <= self.ngram_min <= self.ngram_max:
            raise ValueError(f"need 1 <= ngram_min <= ngram_max, got "
                             f"({self.ngram_min}, {self.ngram_max})")


# ------------------------------------------------------------------ proposers
class NgramProposer:
    """Self-speculative n-gram draft: match the stream's recent suffix against
    its own history and propose the continuation of the most recent earlier
    occurrence.

    For ``n`` from ``ngram_max`` down to ``ngram_min``: find the latest
    position ``< len - n`` where the last ``n`` tokens of ``context`` occurred
    before, and propose the (up to) ``k`` tokens that followed. Longest match
    wins; no match proposes nothing (the verify step then degenerates to a
    plain single-token decode). Deterministic: the proposal is a pure function
    of the token stream, so checkpointless retry re-derives identical drafts
    wherever the request lands."""

    deterministic = True

    def __init__(self, ngram_max: int = 4, ngram_min: int = 1):
        if not 1 <= ngram_min <= ngram_max:
            raise ValueError(f"need 1 <= ngram_min <= ngram_max, got "
                             f"({ngram_min}, {ngram_max})")
        self.ngram_max = int(ngram_max)
        self.ngram_min = int(ngram_min)

    def propose(self, context: np.ndarray, k: int) -> np.ndarray:
        context = np.asarray(context, dtype=np.int32).reshape(-1)
        T = context.size
        if k < 1 or T < self.ngram_min + 1:
            return np.zeros(0, np.int32)
        for n in range(min(self.ngram_max, T - 1), self.ngram_min - 1, -1):
            suffix = context[T - n:]
            # latest earlier occurrence: scan right-to-left over start indices
            # whose match window ends strictly before the suffix itself
            for start in range(T - n - 1, -1, -1):
                if np.array_equal(context[start:start + n], suffix):
                    cont = context[start + n:start + n + k]
                    if cont.size:
                        return cont.astype(np.int32)
                    break           # suffix-adjacent match: try a shorter n
        return np.zeros(0, np.int32)


class DraftModelProposer:
    """Small-draft-model proposer: a second (tiny) ``InferenceEngine`` greedy-
    decodes ``k`` continuation tokens from the context tail. Greedy drafting
    keeps the proposal deterministic — a point-mass distribution — so the
    rejection-sampling accept rule stays exact without needing the draft's
    probabilities on the wire."""

    deterministic = True

    def __init__(self, engine):
        self.engine = engine
        self.cap = int(engine._config.max_out_tokens)

    def propose(self, context: np.ndarray, k: int) -> np.ndarray:
        context = np.asarray(context, dtype=np.int32).reshape(-1)
        if k < 1 or context.size < 1:
            return np.zeros(0, np.int32)
        # tail-slice so prompt + k fits the draft engine's own KV cap
        keep = max(self.cap - k, 1)
        tail = context[-keep:]
        out = self.engine.generate(tail[None, :], max_new_tokens=k,
                                   do_sample=False)
        return np.asarray(out[0, tail.size:], dtype=np.int32)


def make_proposer(cfg: SpeculativeConfig):
    if cfg.proposer == "draft_model":
        if cfg.draft_engine is None:
            raise ValueError("proposer='draft_model' needs a draft_engine")
        return DraftModelProposer(cfg.draft_engine)
    return NgramProposer(cfg.ngram_max, cfg.ngram_min)


# ---------------------------------------------------------------- accept rules
def greedy_accept(draft: np.ndarray, target_argmax: np.ndarray) -> int:
    """Longest prefix of ``draft`` matching the verify argmax at the same
    positions. Every accepted token equals the token greedy decode would have
    picked there — bit-identity with the non-speculative stream is structural,
    not numerical luck."""
    n = int(min(draft.size, target_argmax.size))
    a = 0
    while a < n and int(draft[a]) == int(target_argmax[a]):
        a += 1
    return a


def accept_tokens(draft: np.ndarray, logits: np.ndarray, *,
                  sampling: Tuple[bool, float, int, float],
                  base_key, seed: int, step0: int) -> Tuple[List[int], int]:
    """Accept/reject one slot's draft against its verify-window logits.

    ``draft``: (L,) proposed tokens; ``logits``: (L+1, V) target logits at
    window positions 0..L (position i scored the prefix through draft i-1).
    Returns ``(emitted, accepted)``: up to L+1 emitted tokens (accepted draft
    prefix + one bonus/correction token) and the accepted-draft count.

    Greedy: exact longest-prefix-match, bonus = argmax at the first mismatch
    (or at L on a full match) — the emitted sequence is exactly what
    step-by-step greedy decode would produce. Sampled: per-position rejection
    sampling against the point-mass draft — accept token x with probability
    ``p(x)``, on rejection emit a sample from the renormalized residual
    ``p`` minus the rejected mass and stop; a full acceptance draws the bonus
    with the plain per-step key, bit-matching ``make_slot_select_fn``'s
    stream for that position. Either way the emitted tokens are distributed
    exactly as the target distribution (q is a point mass: accept prob p(x)
    puts mass p(x) on x, and the residual path distributes 1-p(x) over y≠x
    as p(y)/(1-p(x)) — total mass p(y) for every y)."""
    import jax
    import jax.numpy as jnp
    from .decode_fns import logits_transform

    draft = np.asarray(draft, dtype=np.int32).reshape(-1)
    L = int(draft.size)
    do_sample = bool(sampling[0])
    if not do_sample:
        tgt = np.argmax(logits, axis=-1).astype(np.int32)
        a = greedy_accept(draft, tgt[:L])
        return [int(x) for x in draft[:a]] + [int(tgt[a])], a

    transform = logits_transform(*sampling)
    x = np.asarray(transform(jnp.asarray(logits, jnp.float32)))
    probs = np.asarray(jax.nn.softmax(jnp.asarray(x), axis=-1))
    emitted: List[int] = []
    for i in range(L):
        key = jax.random.fold_in(jax.random.fold_in(base_key, seed),
                                 step0 + i)
        u = float(jax.random.uniform(jax.random.fold_in(key, _FOLD_ACCEPT)))
        px = float(probs[i, draft[i]])
        if u < px:
            emitted.append(int(draft[i]))
            continue
        # rejection: resample from the renormalized residual (target minus
        # the rejected point mass) and stop — later drafts were conditioned
        # on the rejected token and carry no usable information
        resid = probs[i].astype(np.float64).copy()
        resid[draft[i]] = 0.0
        z = resid.sum()
        if z <= 0.0:                    # p was a point mass AT the draft:
            emitted.append(int(draft[i]))   # accept is the only outcome
            continue
        tok = int(jax.random.categorical(
            jax.random.fold_in(key, _FOLD_RESAMPLE),
            jnp.log(jnp.asarray(resid / z))))
        emitted.append(tok)
        return emitted, i
    # full acceptance: bonus token from position L with the plain per-step
    # key — exactly make_slot_select_fn's draw for that step index
    key = jax.random.fold_in(jax.random.fold_in(base_key, seed), step0 + L)
    bonus = int(jax.random.categorical(key, jnp.asarray(x[L])))
    emitted.append(bonus)
    return emitted, L


# ------------------------------------------------------------------- telemetry
@dataclass
class SpecStats:
    """Per-scheduler speculative-decoding counters (host-side, cumulative)."""
    rounds: int = 0          # verify dispatches (== target forward passes)
    proposed: int = 0        # draft tokens offered to the verifier
    accepted: int = 0        # draft tokens that survived accept/reject
    tokens: int = 0          # tokens emitted by spec rounds (incl. bonus)
    draft_s: float = 0.0     # cumulative proposer wall time
    verify_s: float = 0.0    # cumulative verify dispatch+fetch wall time

    @property
    def acceptance_rate(self) -> float:
        return self.accepted / self.proposed if self.proposed else 0.0

    @property
    def passes_per_token(self) -> float:
        """Target forward passes per emitted decode token (non-speculative
        decode is exactly 1.0)."""
        return self.rounds / self.tokens if self.tokens else 1.0

    def snapshot(self) -> dict:
        return {
            "spec_rounds": self.rounds,
            "spec_proposed": self.proposed,
            "spec_accepted": self.accepted,
            "spec_tokens": self.tokens,
            "spec_acceptance_rate": self.acceptance_rate,
            "spec_passes_per_token": self.passes_per_token,
            "spec_draft_s": self.draft_s,
            "spec_verify_s": self.verify_s,
        }


def emit_spec_events(telemetry, stats: SpecStats, round_draft_s: float,
                     tick: int) -> None:
    """Publish the ``serving/spec_*`` tags for one spec round through the
    owning :class:`~.serving.telemetry.ServingTelemetry` (registry feed +
    monitor backends). Lives here — not in telemetry.py — so the emission
    site sits in the subsystem that owns the semantics (this module is listed
    in ``observability.schema.EMITTER_MODULES`` and tag-linted)."""
    telemetry._write([
        ("serving/spec_acceptance_rate", stats.acceptance_rate, tick),
        ("serving/spec_proposed_total", float(stats.proposed), tick),
        ("serving/spec_accepted_total", float(stats.accepted), tick),
        ("serving/spec_draft_ms", round_draft_s * 1e3, tick),
    ])
