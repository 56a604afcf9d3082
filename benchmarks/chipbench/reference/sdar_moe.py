"""SDAR's mixture-of-experts model in plain float32 ``jax.numpy``: no kernel,
no cache, no batching; every matmul at highest precision; every expert on
every token, weighted by the top-k mask. It follows the published
architecture (``model_type: sdar_moe``: Qwen3-MoE's layer under a
block-causal mask) and the family's ``generate.py: block_diffusion_generate``.

One layer, for ``x`` (t, d): ``h = rmsnorm(x)``; ``q = h W_q``, ``k = h W_k``,
``v = h W_v`` in heads of ``head_dim``; ``q, k = rmsnorm(q), rmsnorm(k)`` per
head with learned weights; a rotation over the whole head at base
``rope_theta``; ``o = softmax(q k^T / sqrt(head_dim) + mask) v``; ``x = x + o
W_o``; ``h = rmsnorm(x)``; ``p = softmax(h W_r)`` over all experts; the
``num_experts_per_tok`` largest, ``w = p[idx] / sum(p[idx])`` where
``norm_topk_prob``; ``x = x + sum_j w_j W_down[idx_j] (silu(h W_gate[idx_j]) *
(h W_up[idx_j]))``. Final RMSNorm, untied head. ``mask``: key ``j`` is seen
by query ``i`` iff ``j // B <= i // B`` (``B`` = ``gen_block_length``).

Generation: the sequence grows a block of ``B`` positions at a time. A block
starts as mask tokens (its first one opens with the ``P % B`` prompt tokens
past the prompt's last whole block); a forward over the WHOLE sequence gives
logits AT each masked position (no shift), a token is chosen there (argmax)
and ``B / gen_denoising_steps`` positions are unmasked, in the order the
strategy says; when none is masked the next block starts.

Departures from the published description: the parameters are read in the
layout of the program's tree (a published layer is the pair ``layers_<2i>``
(attention) and ``layers_<2i+1>`` (experts), each ``{norm, ...}``); only the
experts of ``experts_held`` exist, so what absent experts would add is left
out (the configuration holds all of them); ``next_token_logits`` states what
the harness's "next-token logits" are for a model that has no next-token
head: see there.
"""

import jax
import jax.numpy as jnp
import numpy as np

HI = "highest"
EXPERT_BLOCK = 16          # experts made float32 at a time


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _eps(model) -> float:
    return float(model.get("rms_norm_eps", 1e-6))


class _Frozen(dict):
    """The model section as a hashable static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def block_mask(t: int, block: int) -> np.ndarray:
    b = np.arange(t) // block
    return b[None, :] <= b[:, None]


def rotate(x, base: float):
    """``x`` (t, heads, d): rotary embedding over all ``d`` dims at positions
    ``0..t-1``, halves paired (``x1, x2 -> x1 cos - x2 sin, x2 cos + x1 sin``)."""
    t, _, d = x.shape
    inv = 1.0 / (base ** (np.arange(0, d, 2, dtype=np.float32) / d))
    ang = np.arange(t, dtype=np.float32)[:, None] * inv[None]
    cos, sin = jnp.asarray(np.cos(ang))[:, None, :], jnp.asarray(np.sin(ang))[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention_layer(x, lp, mask, model):
    lp = _f32(lp)
    nh, nk, hd = (int(model["num_attention_heads"]),
                  int(model["num_key_value_heads"]), int(model["head_dim"]))
    eps, base = _eps(model), float(model.get("rope_theta", 1e6))
    with jax.default_matmul_precision(HI):
        t = x.shape[0]
        hn = rmsnorm(x, lp["norm"]["scale"], eps)
        q = (hn @ lp["q_proj"]["kernel"]).reshape(t, nh, hd)
        k = (hn @ lp["k_proj"]["kernel"]).reshape(t, nk, hd)
        v = (hn @ lp["v_proj"]["kernel"]).reshape(t, nk, hd)
        q = rotate(rmsnorm(q, lp["q_norm"]["scale"], eps), base)
        k = rotate(rmsnorm(k, lp["k_norm"]["scale"], eps), base)
        k, v = (jnp.repeat(a, nh // nk, axis=1) for a in (k, v))
        scores = jnp.einsum("thd,shd->hts", q, k) / np.sqrt(hd)
        scores = jnp.where(mask[None], scores, -jnp.inf)
        attn = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, -1), v)
        return x + attn.reshape(t, nh * hd) @ lp["o_proj"]["kernel"]


def _held(model):
    held = model.get("experts_held") or [0, int(model["num_experts"])]
    return int(held[0]), int(held[1])


def moe_route(x, lp, model):
    """Normed input and the dense weights ``(t, held experts)``."""
    first, count = _held(model)
    with jax.default_matmul_precision(HI):
        hn = rmsnorm(x, jnp.asarray(lp["norm"]["scale"], jnp.float32), _eps(model))
        p = jax.nn.softmax(hn @ jnp.asarray(lp["moe"]["router"], jnp.float32), axis=-1)
        w, idx = jax.lax.top_k(p, int(model["num_experts_per_tok"]))
        if model.get("norm_topk_prob", True):
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        dense = jnp.zeros_like(p).at[jnp.arange(x.shape[0])[:, None], idx].set(w)
        return hn, dense[:, first:first + count]


def expert_block(hn, weights, gate, up, down):
    """Every expert of the block on every token, weighted: ``(t, d)``."""
    gate, up, down = _f32((gate, up, down))
    with jax.default_matmul_precision(HI):
        g = jnp.einsum("td,edf->etf", hn, gate)
        u = jnp.einsum("td,edf->etf", hn, up)
        y = jnp.einsum("etf,efd->etd", g * jax.nn.sigmoid(g) * u, down)
        return jnp.einsum("te,etd->td", weights, y)


_attention_jit = jax.jit(attention_layer, static_argnums=3)
_route_jit = jax.jit(moe_route, static_argnums=2)
_block_jit = jax.jit(expert_block)


def moe_layer(x, lp, model, block: int = EXPERT_BLOCK):
    hn, weights = _route_jit(x, lp, model)
    m = lp["moe"]
    out = x
    for a in range(0, m["experts_up"].shape[0], block):
        out = out + _block_jit(hn, weights[:, a:a + block],
                               m["experts_gate"][a:a + block],
                               m["experts_up"][a:a + block],
                               m["experts_down"][a:a + block])
    return out


def head(x, ln_f, kernel_cols, model):
    with jax.default_matmul_precision(HI):
        return rmsnorm(x, jnp.asarray(ln_f["scale"], jnp.float32), _eps(model)) \
            @ _f32(kernel_cols)


_head_jit = jax.jit(head, static_argnums=3)


def hidden(params, model, ids, mask=None):
    """``ids`` (t,) as they are fed (mask tokens included) -> the last
    layer's output ``(t, d)`` float32 under ``mask`` (t, t), by default the
    block-causal one."""
    model = _Frozen(model)
    ids = np.asarray(ids)
    if mask is None:
        mask = block_mask(len(ids), int(model["gen_block_length"]))
    mask = jnp.asarray(mask)
    x = jnp.asarray(params["wte"][jnp.asarray(ids)], jnp.float32)
    for i in range(int(model["num_hidden_layers"])):
        x = _attention_jit(x, params[f"layers_{2 * i}"], mask, model)
        x = moe_layer(x, params[f"layers_{2 * i + 1}"], model)
    return x


def logits_at(params, model, ids, positions, vocab_block: int = 32768):
    """Float32 logits ``(len(positions), vocab)`` of the fed sequence ``ids``
    at ``positions``, the head in blocks of ``vocab_block`` columns."""
    x = hidden(params, model, ids)[jnp.asarray(np.asarray(positions))]
    kernel = params["lm_head"]["kernel"]
    return np.concatenate(
        [np.asarray(_head_jit(x, params["ln_f"], kernel[:, a:a + vocab_block],
                              _Frozen(model)))
         for a in range(0, kernel.shape[1], vocab_block)], axis=-1)


def next_token_logits(params, model: dict, ids, positions, pad_to: int = 64):
    """What token ``p + 1`` is chosen from given ``ids[:p + 1]``, for each
    ``p`` of ``positions``: float32 ``(len(positions), vocab)``. This model
    has no next-token head; it chooses the token of position ``p + 1`` from
    the logits AT ``p + 1`` of a forward in which ``p + 1`` and the rest of
    its block are mask tokens and everything before is clean, which is the
    state in which the ``sequential`` order fills that position. One plain
    forward of ``ids[:p + 1]`` + masks to the end of the block a position,
    every one padded on the right with mask tokens to one length, a multiple
    of ``pad_to`` (itself a multiple of the block): later blocks are seen by
    no earlier position, and the number of compiled shapes stays small."""
    ids = np.asarray(ids, np.int32)
    B, mask_id = int(model["gen_block_length"]), int(model["mask_token_id"])
    longest = (max(int(p) for p in positions) + 1) // B * B + B
    total = -(-longest // pad_to) * pad_to
    rows = []
    for p in positions:
        q = int(p) + 1
        fed = np.full(total, mask_id, np.int32)
        fed[:q] = ids[:q]
        rows.append(logits_at(params, model, fed, [q])[0])
    return np.stack(rows)


def choose_unmask(masked, conf, n: int, strategy: str, threshold: float):
    """The places of one block a forward unmasks: ``masked`` (B,) bool,
    ``conf`` (B,) the probability of the token chosen at each place."""
    places = np.flatnonzero(masked)
    if strategy == "sequential":
        return places[:n]
    ranked = sorted(places, key=lambda j: (-conf[j], j))
    if strategy == "low_confidence_static":
        return np.sort(ranked[:n])
    if strategy == "low_confidence_dynamic":
        high = [j for j in places if conf[j] > threshold]
        return np.asarray(high if len(high) >= n else sorted(ranked[:n]))
    raise ValueError(f"unknown strategy {strategy!r}")


def generate(params, model: dict, prompt, n: int, strategy=None, pad_to: int = 64):
    """``n`` tokens after ``prompt`` by the plain block loop, the whole
    sequence recomputed each forward, greedy. Returns the tokens and one
    record a forward: ``(places unmasked as sequence positions, by how much
    the least confident place taken led the most confident place left, the
    smallest lead of the largest logit over the second at the places
    unmasked)``, both leads in spreads of the logits (the first as a
    difference of log-probabilities, which is what a logit's error moves):
    where a lead is under the error a served type may make, another
    rounding may rightly choose otherwise, and what follows differs with
    it."""
    B, mask_id = int(model["gen_block_length"]), int(model["mask_token_id"])
    per = B // int(model["gen_denoising_steps"])
    strategy = strategy or model.get("gen_remasking", "sequential")
    thr = float(model.get("gen_confidence_threshold", 0.9))
    prompt = np.asarray(prompt, np.int32)
    P = len(prompt)
    total = -(-(P + n) // pad_to) * pad_to
    seq = np.full(total, mask_id, np.int32)
    seq[:P] = prompt
    masked = np.ones(total, bool)
    masked[:P] = False
    records = []
    for s in range(P // B * B, P + n, B):
        blk = slice(s, s + B)
        while masked[blk].any():
            logits = logits_at(params, model, np.where(masked, mask_id, seq),
                               np.arange(s, s + B))
            x0 = logits.argmax(-1)
            z = logits - logits.max(-1, keepdims=True)
            conf = np.exp(z[np.arange(B), x0]) / np.exp(z).sum(-1)
            take = choose_unmask(masked[blk], conf, per, strategy, thr)
            left = [j for j in np.flatnonzero(masked[blk]) if j not in take]
            top2 = np.sort(logits, axis=-1)[:, -2:]
            spread = float(logits.std(-1).mean())
            records.append((
                [s + int(j) for j in take],
                float(np.log(min(conf[take])) - np.log(max(conf[left]))) / spread
                if left else np.inf,
                float((top2[take, 1] - top2[take, 0]).min()) / spread))
            seq[s + take] = x0[take]
            masked[s + take] = False
    return seq[P:P + n], records
