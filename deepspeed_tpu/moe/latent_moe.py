"""Latent mixture of experts, one chip's share of it.

The router scores every expert of the layer (``n_routed``) with a sigmoid in
float32, chooses the ``top_k`` largest of score + selection bias, and weights
the chosen by their scores (normalised, then scaled). The experts live in a
latent space: ``z = h W_down``, each expert is ``W2_e act(W1_e z)`` there, and
the weighted sum goes back through ``W_up``; a shared expert of the full width
is added. Router and shared expert read the full-width ``h``.

The layer is TOLD which experts it holds, ``experts_held = (first, count)``:
it routes over all ``n_routed`` and sums only the assignments that fall on its
own experts (the parameters hold only those). What the absent experts would
add is left out; with ``count == n_routed`` it is the whole layer. Under
expert parallelism the partial sums of the chips add up (before ``W_up``,
which is linear) and the shared expert is counted once; on one chip there is
no exchange and nothing stands in for the other chips.
"""

import functools
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..observability import scope
from ..ops.moe.grouped_ffn import grouped_experts


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def route(h, w_router, bias, top_k: int, scale: float, norm: bool,
          eps: float = 1e-20):
    """``h`` (T, d). Returns the chosen experts ``idx`` (T, k) and their
    weights (T, k) float32: sigmoid scores, selection by score + bias, weights
    from the scores alone, over ``sum + eps`` where ``norm``, times ``scale``."""
    s = jax.nn.sigmoid(jnp.dot(h.astype(jnp.float32), w_router.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps)
    return idx.astype(jnp.int32), w * scale


class LatentMoE(nn.Module):
    d_model: int
    n_routed: int
    top_k: int
    expert_width: int
    shared_width: int
    latent: int
    scale: float
    norm_topk: bool
    experts_held: Tuple[int, int]
    dtype: Any
    init_std: float
    out_std: float
    act: Callable = relu2

    @nn.compact
    def __call__(self, h, valid: Optional[jnp.ndarray] = None):
        """``h`` (b, t, d) normed input; ``valid`` (b, t) bool marks real
        tokens (padding is routed nowhere). Returns the layer's output and
        ``(assignments on held experts, distinct held experts touched)``."""
        first, count = self.experts_held
        d, l, f, dt = self.d_model, self.latent, self.expert_width, self.dtype
        init = nn.initializers.normal(self.init_std)
        out_init = nn.initializers.normal(self.out_std)
        w_r = self.param("router", init, (d, self.n_routed), jnp.float32)
        # seeded non-zero (a layer that drops the selection bias picks other
        # experts) and small beside the scores' spread, as after a training
        # that balanced the load with it: every expert is chosen about as often
        b_r = self.param("router_bias", nn.initializers.normal(0.01),
                         (self.n_routed,), jnp.float32)
        w_down = self.param("down", init, (d, l), jnp.float32)
        w_up = self.param("up", out_init, (l, d), jnp.float32)
        w1 = self.param("experts_w1", init, (count, l, f), jnp.float32)
        w2 = self.param("experts_w2", init, (count, f, l), jnp.float32)
        s1 = self.param("shared_w1", init, (d, self.shared_width), jnp.float32)
        s2 = self.param("shared_w2", out_init, (self.shared_width, d), jnp.float32)

        b_, t, _ = h.shape
        with scope("moe.router"):
            x = h.reshape(b_ * t, d).astype(dt)
            idx, w = route(x, w_r, b_r, self.top_k, self.scale, self.norm_topk)
        with scope("moe.shared"):
            z = x @ w_down.astype(dt)                                 # (T, l)
        with scope("moe.experts"):
            args = (w1.astype(dt), w2.astype(dt), self.act,
                    None if valid is None else valid.reshape(-1))
        r, stats = grouped_experts(z, idx, w, first, count, w_r.shape[1], *args)
        with scope("moe.shared"):
            shared = self.act(jnp.dot(x, s1.astype(dt),
                                      preferred_element_type=jnp.float32))
            out = r.astype(dt) @ w_up.astype(dt) + shared.astype(dt) @ s2.astype(dt)
            return out.reshape(b_, t, d), stats


def level_selection_bias(scores, bias, top_k: int, steps: int = 120,
                         rate: float = 0.03):
    """The selection bias under which the ``top_k`` largest of ``scores +
    bias`` (``scores`` (T, n) float32) fall on the ``n`` experts evenly over
    these ``T`` tokens: the auxiliary-loss-free balancing rule (Wang et al.,
    arXiv 2408.15664, as DeepSeek-V3 trains with it), ``b_e += u sign(mean
    load - load_e)``, iterated on one batch with ``u`` falling from ``rate``
    to a hundredth of it so that it settles. Returns the bias and the largest
    load over the mean load, before and after."""
    n = scores.shape[-1]
    experts = jnp.arange(n, dtype=jnp.int32)

    def load_of(b):
        _, idx = jax.lax.top_k(scores + b, top_k)
        return jnp.sum(idx[..., None] == experts, axis=(0, 1), dtype=jnp.float32)

    def step(i, b):
        load = load_of(b)
        u = rate * jnp.exp(-4.6 * i / steps)
        return b + u * jnp.sign(jnp.mean(load) - load)

    def busiest(b):
        load = load_of(b)
        return jnp.max(load) / jnp.mean(load)

    bias = bias.astype(jnp.float32)
    new = jax.lax.fori_loop(0, steps, step, bias)
    return new, busiest(bias), busiest(new)


def level_expert_load(cfg, params, seed: int, batches: int = 8, tokens: int = 512):
    """Finish RANDOMLY initialised parameters of a model with expert layers:
    set each such layer's selection bias so that its load is level on random
    tokens, layer by layer in order (a layer's input depends on the biases
    before it). Every layer's output has a part that is the same for all
    tokens, so a random router sends several times the mean load to a few
    experts, other ones for every seed; training levels that with this bias,
    and a stand-in that is to load its experts as a trained model does needs
    the same. ``cfg.level_random_experts`` asks for it where the stand-in
    weights are made; nothing else calls it. Returns the parameters and the
    largest load over the mean, worst layer, before and after. A layer counts
    if its router has a selection bias (``moe["router_bias"]``: the latent
    mixture, and the gated one behind the ``sigmoid_bias`` router)."""
    from ..models.causal_lm import _norm_mod, causal_lm_segments  # it imports this module
    segs = causal_lm_segments(cfg, layers_per_group=1)
    key = jax.random.PRNGKey(seed)
    feeds = [{"input_ids": jax.random.randint(jax.random.fold_in(key, i), (1, tokens),
                                              0, cfg.vocab_size)}
             for i in range(batches)]
    applies = {}

    def run(seg, *args):
        if seg.apply_fn not in applies:
            applies[seg.apply_fn] = jax.jit(seg.apply_fn)
        return applies[seg.apply_fn](tuple(params[k] for k in seg.param_keys), *args)

    @jax.jit
    def scores_of(layer, xs):
        h = _norm_mod(cfg).apply({"params": layer["norm"]}, xs).astype(cfg.dtype)
        return jax.nn.sigmoid(jnp.dot(
            h.reshape(-1, h.shape[-1]).astype(jnp.float32),
            layer["moe"]["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))

    level = jax.jit(functools.partial(level_selection_bias, top_k=cfg.experts_per_token))
    params = dict(params)
    xs = [run(segs[0], feed, None) for feed in feeds]
    before = after = 1.0
    for seg in segs[1:-1]:
        (name,) = seg.param_keys
        if "router_bias" in params[name].get("moe", {}):
            layer = dict(params[name])
            old = layer["moe"]["router_bias"]
            new, b, a = level(scores_of(layer, jnp.concatenate(xs, axis=0)), old)
            new = new.astype(old.dtype)
            layer["moe"] = {**layer["moe"], "router_bias":
                            jax.device_put(new, old.sharding)
                            if hasattr(old, "sharding") else new}
            params[name] = layer
            before, after = max(before, float(b)), max(after, float(a))
        xs = [run(seg, x, feed, None) for x, feed in zip(xs, feeds)]
    return params, before, after


# a home's router weight on its code lane: x a lane of init_std (0.02) x the
# norm's factor (2-5 on a stream of 0.2-0.5 a lane) = a logit of +-40 to +-100
# against the seeded rows' spread of ~1.3: sigmoid's 1 and 0 in float32
HOME_GAIN = 1024.0
# the matrices of a mixer layer that WRITE to the residual stream
_STREAM_WRITERS = ("o_proj", "fc_out")
_MOE_STREAM_WRITERS = ("experts_down", "shared_down")


def home_random_routers(cfg, params, seed: int):
    """Finish RANDOMLY initialised parameters of a model whose experts sit
    behind ``sigmoid_bias`` routers so that NO rounding moves a router's
    choice: every token id gets ``experts_per_token`` home experts a layer,
    and the router finds them with a margin of tens of score spreads.

    Why: a seeded router's ``top_k`` of ``n`` scores has its k-th and k+1-th a
    twentieth of a spread apart, so the half per cent that bfloat16 leaves on
    a residual stream moves about one choice in ten, and ONE moved choice of a
    random stand-in swaps a whole expert term: a comparison with a float32
    reference then reads its router's luck, a spread of a logit, whatever the
    precision of anything else. A trained model's neighbours in score are
    alike; a stand-in's are not. How, with the layer's own arithmetic (router
    matmul, sigmoid, bias, ``top_k``, weights over their sum, scaling):

    - lanes ``[0, n)`` of the stream carry the token's CODE: the embedding's
      row holds ``+init_std`` on the lanes of its homes and ``-init_std`` on
      the others (the size every other lane of a row has), homes drawn from
      the seed, ``experts_per_token`` of ``n`` without replacement, so loads
      are level by construction and the selection bias stays as seeded;
    - nothing writes there: the columns ``[0, n)`` of every matrix that
      writes to the stream (``o_proj``, ``fc_out``, an expert's and the shared
      expert's down matrix) are zero, so the code reaches every router as the
      embedding gave it, in the program and in any reference alike;
    - rows ``[0, n)`` of a layer's router are ``HOME_GAIN`` times a
      permutation of its own (another set of homes a layer); its other rows
      keep their seeded weights. A home scores ``sigmoid(+gain x lane)``, 1,
      every other expert ``sigmoid(-gain x lane)``, 0.

    ``cfg.home_random_routers`` asks for it where the stand-in weights are made;
    nothing else calls it. Returns the parameters."""
    n, k = cfg.n_routed_experts, cfg.experts_per_token
    if not 0 < n <= cfg.n_embd:
        raise ValueError(f"home_random_routers needs {n} code lanes in a stream of {cfg.n_embd}")
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 0x686F6D65)

    def put(old, fn, *args):
        # the old array gives up its buffer: the tree is the caller's to
        # replace, and at the published widths a second copy of the down
        # matrices raised the set-up's peak by 0.8 GB
        sharding = getattr(old, "sharding", None)
        new = jax.jit(fn, donate_argnums=0)(old, *args)
        return new if sharding is None else jax.device_put(new, sharding)

    def code(wte):
        draw = jax.random.uniform(key, (wte.shape[0], n))
        home = draw <= jnp.sort(draw, axis=-1)[:, k - 1:k]
        return wte.at[:, :n].set(jnp.where(home, cfg.init_std, -cfg.init_std).astype(wte.dtype))

    def homes(router, layer_key):
        perm = jax.random.permutation(layer_key, n)
        rows = HOME_GAIN * jax.nn.one_hot(perm, router.shape[1])
        return router.at[:n].set(rows.astype(router.dtype))

    def unwritten(w):
        return w.at[..., :n].set(0)

    params = dict(params)
    params["wte"] = put(params["wte"], code)
    for i in range(cfg.n_layer):
        name = f"layers_{i}"
        layer = dict(params[name])
        for w in _STREAM_WRITERS:
            if w in layer:
                layer[w] = {**layer[w], "kernel": put(layer[w]["kernel"], unwritten)}
        if "moe" in layer:
            moe = dict(layer["moe"])
            moe["router"] = put(moe["router"], homes, jax.random.fold_in(key, i))
            for w in _MOE_STREAM_WRITERS:
                moe[w] = put(moe[w], unwritten)
            layer["moe"] = moe
        params[name] = layer
    return params
