"""Pipeline module: LayerSpec / TiedLayerSpec / PipelineModule.

Reference: ``deepspeed/runtime/pipe/module.py`` (``LayerSpec:26``, ``TiedLayerSpec:74``,
``PipelineModule:88``, partitioning ``_partition_layers:367``, tied weights ``:423-445``).

TPU-native redesign: instead of materialising per-stage ``nn.Sequential`` fragments in separate
processes, the module classifies its layer list into

- ``pre``  — leading heterogeneous layers (embeddings…), computed on every device (replicated
  over the ``pipe`` axis, sharded over data/tensor axes as usual);
- ``body`` — the longest homogeneous run of layers (the transformer blocks): their params are
  *stacked* along a leading layer dimension and sharded over the ``pipe`` mesh axis, so each
  stage physically holds only its own blocks;
- ``post`` — trailing layers (final norm, LM head), replicated like ``pre``.

The pipelined forward is an SPMD collective-permute loop (GPipe fill-drain over
``micro_batches + stages - 1`` iterations) under ``jax.shard_map`` manual only over ``pipe``;
``jax.lax.ppermute`` moves activations stage→stage+1 and autodiff through the loop transposes it
into the backward drain (reverse permutes), giving the 1F1B-equivalent bubble fraction
``(S-1)/(M+S-1)``. Activation memory is bounded by per-microbatch remat (``jax.checkpoint``) —
the role 1F1B plays in the reference.

Tied layers (``TiedLayerSpec``) share one parameter entry under ``params['tied'][key]``; since
pre/post are replicated over ``pipe`` there is no tied-weight gradient all-reduce to schedule —
XLA's psum over the batch axes already covers it.
"""

import re
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ...parallel.mesh import AXIS_EXPERT, AXIS_PIPE, MeshSpec
from ...utils.logging import logger
from ...utils.jax_compat import shard_map


# --------------------------------------------------------------------------- layer contract
class PipeLayer:
    """A pipeline layer: ``init(rng, x) -> params`` and ``apply(params, x, rng) -> y``.

    Layers with an auxiliary scalar loss (MoE load-balancing) set ``has_aux = True``
    and implement ``apply_with_aux(params, x, rng) -> (y, aux)``; the 1F1B executor
    aggregates aux across layers, stages and microbatches into the total loss
    (reference MoE aux-loss plumbing through the pipeline engine)."""

    has_aux = False

    def init(self, rng, x):
        return {}

    def apply(self, params, x, rng=None):
        raise NotImplementedError

    def apply_with_aux(self, params, x, rng=None):
        return self.apply(params, x, rng), jnp.float32(0.0)


class LambdaLayer(PipeLayer):
    """Parameterless function layer (reference allows bare callables in the layer list)."""

    def __init__(self, fn: Callable):
        self.fn = fn

    def apply(self, params, x, rng=None):
        return self.fn(x)


class FlaxPipeLayer(PipeLayer):
    """Adapt a ``flax.linen`` module to the PipeLayer contract.

    ``deterministic_kwarg``: pass ``deterministic=(rng is None)`` through to the module (the
    convention of our transformer blocks).

    Tensor-parallel support (body layers only): ``tp_apply_factory(tp, axis)`` returns a
    manual-collective forward consuming LOCAL parameter shards (e.g.
    ``models.gpt2.block_tp_apply``); ``tp_col``/``tp_row`` name the column-/row-parallel
    sublayers so :meth:`PipelineModule.param_specs` can emit the matching physical
    sharding. Layers without a factory run replicated over any tensor axis.
    """

    def __init__(self, module, deterministic_kwarg: bool = False,
                 tp_apply_factory=None, tp_col: tuple = (), tp_row: tuple = (),
                 sp_apply_factory=None):
        self.module = module
        self.deterministic_kwarg = deterministic_kwarg
        self.tp_apply_factory = tp_apply_factory
        self.tp_col = tuple(tp_col)
        self.tp_row = tuple(tp_row)
        # seq-parallel forward: sp_apply_factory(sp, axis) returns a ring-local
        # layer fn consuming SEQUENCE-SHARDED activations (pipe×seq 1F1B bodies)
        self.sp_apply_factory = sp_apply_factory

    def _kwargs(self, rng):
        return {"deterministic": rng is None} if self.deterministic_kwarg else {}

    def init(self, rng, x):
        rngs = {"params": rng, "dropout": rng}
        return self.module.init(rngs, x, **self._kwargs(rng))["params"]

    def apply(self, params, x, rng=None):
        rngs = {"dropout": rng} if rng is not None else {}
        return self.module.apply({"params": params}, x, rngs=rngs, **self._kwargs(rng))


class LayerSpec:
    """Deferred layer construction (reference ``module.py:26``) — lets huge models describe
    themselves without materialising parameters until partitioning is known."""

    def __init__(self, typename, *module_args, **module_kwargs):
        self.typename = typename
        self.module_args = module_args
        self.module_kwargs = module_kwargs

    def build(self) -> PipeLayer:
        obj = self.typename(*self.module_args, **self.module_kwargs)
        return _as_pipe_layer(obj)


class TiedLayerSpec(LayerSpec):
    """Layer sharing parameters with every other tied layer of the same ``key``
    (reference ``module.py:74``)."""

    def __init__(self, key, typename, *module_args, forward_fn=None, **module_kwargs):
        super().__init__(typename, *module_args, **module_kwargs)
        self.key = key
        self.forward_fn = forward_fn

    def build(self) -> PipeLayer:
        layer = super().build()
        if self.forward_fn is not None:
            fwd = self.forward_fn
            base = layer

            class _TiedForward(PipeLayer):
                def init(self, rng, x):
                    return base.init(rng, x)

                def apply(self, params, x, rng=None):
                    return fwd(base, params, x)

            return _TiedForward()
        return layer


def _as_pipe_layer(obj) -> PipeLayer:
    if isinstance(obj, PipeLayer):
        return obj
    if callable(obj) and not hasattr(obj, "init"):
        return LambdaLayer(obj)
    if hasattr(obj, "apply") and hasattr(obj, "init"):  # flax module duck-type
        return FlaxPipeLayer(obj)
    raise TypeError(f"Cannot adapt {obj!r} to a pipeline layer")


def _split_batch(batch):
    """(inputs, labels) from the accepted batch forms — shared by every pipeline path."""
    if isinstance(batch, (tuple, list)) and len(batch) == 2:
        return batch[0], batch[1]
    if isinstance(batch, dict):
        return batch["inputs"], batch.get("labels")
    return batch, None


def partition_weights(layers: Sequence, abstract_params: Sequence,
                      method: str) -> List[float]:
    """Per-layer weights for stage balancing (reference ``module.py:_partition_layers``
    methods): ``uniform``, ``parameters``, or ``type:<regex>``. Shared by
    :class:`PipelineModule` and the eager executor."""
    method = method.lower()
    if method == "uniform":
        return [1.0] * len(layers)
    if method == "parameters":
        return [float(sum(int(np.prod(l.shape))
                          for l in jax.tree_util.tree_leaves(p))) or 1.0
                for p in abstract_params]
    if method.startswith("type:"):
        pat = re.compile(method[len("type:"):], re.IGNORECASE)
        return [1.0 if pat.search(type(layer).__name__) else 0.0
                for layer in layers]
    raise NotImplementedError(f"partition_method {method!r}")


# --------------------------------------------------------------------------- partitioning
def partition_balanced(weights: Sequence[float], num_parts: int) -> List[int]:
    """Split ``weights`` into ``num_parts`` contiguous parts minimising the heaviest part.

    Returns part boundaries of length ``num_parts + 1`` (reference
    ``deepspeed/runtime/utils.py:partition_balanced`` used by ``module.py:_partition_layers``).
    Classic binary search over the bottleneck value.
    """
    n = len(weights)
    prefix = np.concatenate([[0.0], np.cumsum(weights)])

    def parts_needed(limit: float) -> Optional[List[int]]:
        bounds = [0]
        start = 0
        for _ in range(num_parts):
            # furthest end such that sum(start:end) <= limit
            end = int(np.searchsorted(prefix, prefix[start] + limit, side="right")) - 1
            if end <= start and start < n:
                end = start + 1  # always make progress (single item exceeds limit)
            end = min(end, n)
            bounds.append(end)
            start = end
        return bounds if bounds[-1] >= n else None

    lo, hi = float(max(weights) if len(weights) else 0.0), float(prefix[-1])
    for _ in range(64):
        mid = (lo + hi) / 2
        if parts_needed(mid) is not None:
            hi = mid
        else:
            lo = mid
    bounds = parts_needed(hi)
    bounds[-1] = n
    return bounds


# --------------------------------------------------------------------------- module
class PipelineModule:
    """See module docstring. Public surface mirrors reference ``PipelineModule:88``."""

    def __init__(self,
                 layers: Sequence,
                 num_stages: Optional[int] = None,
                 topology=None,
                 loss_fn: Optional[Callable] = None,
                 sample_input=None,
                 partition_method: str = "uniform",
                 activation_checkpoint_interval: int = 0,
                 aux_loss_coef: float = 0.0,
                 sp_loss_fn=None,
                 seed: int = 1234):
        if num_stages is None and topology is None:
            raise RuntimeError("must provide num_stages or topology")
        if topology is not None and num_stages is None:
            num_stages = topology.get_dim("pipe")
        self.num_stages = int(num_stages)
        self.topology = topology
        self.loss_fn = loss_fn
        self.partition_method = partition_method
        self.activation_checkpoint_interval = activation_checkpoint_interval
        # weight of body layers' auxiliary losses (MoE load balancing) in the total
        self.aux_loss_coef = float(aux_loss_coef)
        # sp_loss_fn(out_local, lab_local, axis_name): sequence-sharded tail loss
        # (psums its sum/count over the seq axis) — required for sp 1F1B
        self.sp_loss_fn = sp_loss_fn
        # optional post-processing of reference_apply's output in to_model's
        # apply_fn (keeps the logits contract when the head emits something else)
        self.apply_transform = None
        self.seed = seed
        if not (sample_input is not None):
            raise AssertionError("PipelineModule needs sample_input (abstract is fine) to trace layer shapes")
        self.sample_input = sample_input

        self._specs = list(layers)
        self._layers: List[PipeLayer] = []
        self._tied_keys: List[Optional[str]] = []
        for spec in self._specs:
            if isinstance(spec, LayerSpec):
                self._layers.append(spec.build())
                self._tied_keys.append(getattr(spec, "key", None))
            else:
                self._layers.append(_as_pipe_layer(spec))
                self._tied_keys.append(None)

        self._trace_structure()

    # ------------------------------------------------------------------ tracing
    def _trace_structure(self):
        """eval_shape every layer on the propagated sample activation; find the homogeneous
        body run; compute stage boundaries."""
        rng = jax.random.PRNGKey(self.seed)
        x = self.sample_input
        shapes = []   # (treedef_repr, leaf shapes) per layer
        self._abstract_params: List[Any] = []
        tied_abstract: Dict[str, Any] = {}
        for i, layer in enumerate(self._layers):
            key = self._tied_keys[i]
            if key is not None and key in tied_abstract:
                p = tied_abstract[key]
            else:
                p = jax.eval_shape(partial(layer.init), rng, x)
                if key is not None:
                    tied_abstract[key] = p
            self._abstract_params.append(p)
            leaves = jax.tree_util.tree_leaves(p)
            # signature includes layer IDENTITY (type + wrapped-module repr), not just param
            # shapes: two different layer types with coincidentally equal param trees must
            # not be merged into one body and applied with the first layer's apply()
            ident = type(layer).__name__
            inner = getattr(layer, "module", None)
            if inner is not None:
                ident += ":" + repr(inner)
            sig = (ident,
                   str(jax.tree_util.tree_structure(p)),
                   tuple((tuple(l.shape), str(l.dtype)) for l in leaves))
            shapes.append(sig)
            x = jax.eval_shape(partial(layer.apply), p, x, None)
        self._output_shape = x

        # longest homogeneous run of layers with parameters
        best = (0, 0)  # (start, length)
        i = 0
        n = len(self._layers)
        while i < n:
            # tied layers can never join the body: their params live in params['tied'] and
            # stacking a copy into params['body'] would silently untie the weights
            if (not jax.tree_util.tree_leaves(self._abstract_params[i])
                    or self._tied_keys[i] is not None):
                i += 1
                continue
            j = i + 1
            while j < n and shapes[j] == shapes[i] and self._tied_keys[j] is None:
                j += 1
            if j - i > best[1]:
                best = (i, j - i)
            i = j
        start, length = best
        S = self.num_stages
        if length < S:
            raise ValueError(
                f"Pipeline needs a homogeneous block run >= num_stages: found {length} "
                f"homogeneous layers for {S} stages")
        # trim the run so the body length divides num_stages; spill extras to pre/post
        spill = length % S
        start += spill  # keep early layers (closer to embeddings) in pre
        length -= spill
        self.body_start = start
        self.body_end = start + length
        self.layers_per_stage = length // S
        if spill:
            logger.info(f"PipelineModule: spilled {spill} block(s) to the pre segment so "
                        f"{length} body layers divide {S} stages")

        self.parts = self._compute_parts()

    def _compute_parts(self) -> List[int]:
        """Stage boundaries over the full layer list (reference ``_partition_layers:367``) —
        informational/ckpt-naming; the SPMD executor uses the body stacking above."""
        weights = partition_weights(self._layers, self._abstract_params,
                                    self.partition_method)
        return partition_balanced(weights, self.num_stages)

    # ------------------------------------------------------------------ params
    def init_fn(self, rng):
        """Build the structured param tree: pre/body(stacked)/post/tied."""
        params = {"pre": {}, "body": None, "post": {}, "tied": {}}
        x_abs = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), self.sample_input)
        body_stack: List[Any] = []
        for i, layer in enumerate(self._layers):
            lrng = jax.random.fold_in(rng, i)
            key = self._tied_keys[i]
            x_dummy = jax.tree_util.tree_map(
                lambda l: jnp.zeros(l.shape, l.dtype), x_abs)
            if key is not None and key in params["tied"]:
                p = params["tied"][key]
            else:
                p = layer.init(lrng, x_dummy)
                if key is not None:
                    params["tied"][key] = p
            if self.body_start <= i < self.body_end:
                body_stack.append(p)
            elif key is None and jax.tree_util.tree_leaves(p):
                seg = "pre" if i < self.body_start else "post"
                params[seg][str(i)] = p
            x_abs = jax.eval_shape(partial(layer.apply), _abstract(p), x_abs, None)
        params["body"] = jax.tree_util.tree_map(
            lambda *ls: jnp.stack(ls), *body_stack)
        return params

    def param_specs(self, abstract_params=None, tp_axis: Optional[str] = None,
                    tp_size: Optional[int] = None,
                    ep_size: Optional[int] = None) -> Any:
        """PartitionSpec tree: body stacked dim shards over ``pipe``; rest replicated.

        With ``tp_axis``, body weights shard per the body layer's Megatron
        classification (``FlaxPipeLayer.tp_col``/``tp_row``): column-parallel kernels
        and biases shard their LAST dim, row-parallel kernels their first weight dim
        (bias replicated). This is the PHYSICAL layout the 1F1B shard_map's
        manual-collective stage_fn consumes (see :meth:`make_1f1b_loss_fn`). Layers
        without tp rules fall back to naive last-dim sharding of ndim>=3 leaves
        (GSPMD-correct for non-shard_map executors, may insert reshards).
        ``tp_size`` defaults to the global mesh's axis size."""
        if abstract_params is None:
            abstract_params = jax.eval_shape(self.init_fn, jax.random.PRNGKey(0))
        from ...parallel.mesh import get_global_mesh
        if tp_axis and tp_size is None:
            mesh = get_global_mesh()
            tp_size = mesh.size(tp_axis) if mesh is not None else 1
        if ep_size is None or ep_size < 1:   # None/-1 = unresolved ("infer")
            gmesh = get_global_mesh()
            ep_size = gmesh.size(AXIS_EXPERT) if gmesh is not None else 1
        body_layer = self._layers[self.body_start]
        tp_col = tuple(getattr(body_layer, "tp_col", ()))
        tp_row = tuple(getattr(body_layer, "tp_row", ()))
        ep_paths = tuple(getattr(body_layer, "ep_paths", ()))
        use_rules = bool(tp_axis and tp_size and tp_size > 1 and (tp_col or tp_row))

        def body_spec_by_path(path, leaf):
            entries = [AXIS_PIPE] + [None] * (leaf.ndim - 1)
            names = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
            if ep_paths and any(n in ep_paths for n in names):
                # expert-stacked leaf (L_per, e, ...): expert dim over the expert
                # axis (reference expert-parallel groups, utils/groups.py:109);
                # non-divisible expert counts replicate, like the TP rules
                if leaf.ndim >= 2 and ep_size > 1 and \
                        leaf.shape[1] % ep_size == 0:
                    entries[1] = AXIS_EXPERT
                return P(*entries)
            parent = names[-2] if len(names) >= 2 else ""
            kind = names[-1] if names else ""
            if use_rules and parent in tp_col and leaf.shape[-1] % tp_size == 0:
                entries[-1] = tp_axis                     # kernel AND bias follow cols
            elif use_rules and parent in tp_row and kind == "kernel" \
                    and leaf.ndim >= 3 and leaf.shape[1] % tp_size == 0:
                entries[1] = tp_axis                      # first weight dim (inputs)
            elif not use_rules and tp_axis and leaf.ndim >= 3 and tp_size \
                    and tp_size > 1 and leaf.shape[-1] % tp_size == 0:
                entries[-1] = tp_axis                     # generic last-dim fallback
            return P(*entries)

        def seg_spec(seg_name):
            def one(leaf):
                return P(*([None] * leaf.ndim))
            return one

        out = {}
        for seg in ("pre", "body", "post", "tied"):
            if seg == "body":
                out[seg] = jax.tree_util.tree_map_with_path(
                    body_spec_by_path, abstract_params[seg])
            else:
                out[seg] = jax.tree_util.tree_map(seg_spec(seg),
                                                  abstract_params[seg])
        return out

    # ------------------------------------------------------------------ forward paths
    def _segment_apply(self, params, x, rng, lo, hi):
        """Apply layers [lo, hi) sequentially (non-body segments + reference executor)."""
        for i in range(lo, hi):
            if self.body_start <= i < self.body_end:
                continue
            layer = self._layers[i]
            key = self._tied_keys[i]
            p = (params["tied"][key] if key is not None
                 else params.get("pre", {}).get(str(i),
                      params.get("post", {}).get(str(i), {})))
            lrng = None if rng is None else jax.random.fold_in(rng, i)
            x = layer.apply(p, x, lrng)
        return x

    def reference_apply(self, params, x, rng=None):
        """Sequential (non-pipelined) forward — ground truth for tests and single-stage."""
        body_layer = self._layers[self.body_start]
        x = self._segment_apply(params, x, rng, 0, self.body_start)

        def body_one(carry, xs):
            p, r = xs
            return body_layer.apply(p, carry, None if rng is None else r), None

        n_body = self.body_end - self.body_start
        rngs = (jax.random.split(jax.random.fold_in(rng, 10**6), n_body)
                if rng is not None else jnp.zeros((n_body, 2), dtype=jnp.uint32))
        x, _ = jax.lax.scan(body_one, x, (params["body"], rngs))
        return self._segment_apply(params, x, rng, self.body_end, len(self._layers))

    def pipelined_apply(self, params, xs, mesh_spec: MeshSpec, rng=None,
                        remat: bool = True):
        """GPipe fill-drain loop over the ``pipe`` axis.

        ``xs``: microbatched activations entering the body, shape ``(M, mb, ...)``.
        Returns body outputs ``(M, mb, ...)``.
        """
        S = self.num_stages
        L_per = self.layers_per_stage
        body_layer = self._layers[self.body_start]
        M = xs.shape[0]
        if rng is None:
            rng = jax.random.PRNGKey(0)
            use_rng = False
        else:
            use_rng = True

        def stage_fn(stage_params, x, srng):
            def one(carry, xs_):
                p, r = xs_
                return body_layer.apply(p, carry, r if use_rng else None), None

            rngs = jax.random.split(srng, L_per)
            x, _ = jax.lax.scan(one, x, (stage_params, rngs))
            return x

        if remat:
            stage_fn = jax.checkpoint(stage_fn)

        n_iters = M + S - 1

        def run(body_params, xs_local, rng_in):
            stage = jax.lax.axis_index(AXIS_PIPE)
            recv0 = jnp.zeros_like(xs_local[0])
            outs0 = jnp.zeros_like(xs_local)

            def step(carry, t):
                recv, outs = carry
                x_in = jnp.where(stage == 0,
                                 jax.lax.dynamic_index_in_dim(
                                     xs_local, jnp.clip(t, 0, M - 1), 0, keepdims=False),
                                 recv)
                srng = jax.random.fold_in(jax.random.fold_in(rng_in, t), stage)
                y = stage_fn(body_params, x_in, srng)
                m = t - stage
                valid = jnp.logical_and(m >= 0, m < M)
                m_c = jnp.clip(m, 0, M - 1)
                outs = jnp.where(
                    valid,
                    jax.lax.dynamic_update_index_in_dim(outs, y, m_c, 0),
                    outs)
                recv_next = jax.lax.ppermute(
                    y, AXIS_PIPE, [(i, i + 1) for i in range(S - 1)])
                return (recv_next, outs), None

            (_, outs), _ = jax.lax.scan(step, (recv0, outs0), jnp.arange(n_iters))
            return outs[None]  # local (1, M, ...) → stacked (S, M, ...) outside

        if S == 1:
            return jax.vmap(lambda x, r: stage_fn(params["body"], x, r))(
                xs, jax.random.split(rng, M))

        mapped = shard_map(
            run,
            mesh=mesh_spec.mesh,
            axis_names={AXIS_PIPE},
            in_specs=(P(AXIS_PIPE), P(), P()),
            out_specs=P(AXIS_PIPE),
            check_vma=False,
        )
        stacked = mapped(params["body"], xs, rng)  # (S, M, mb, ...)
        return stacked[S - 1]

    # ------------------------------------------------------------------ 1F1B
    def make_1f1b_loss_fn(self, mesh_spec: Optional[MeshSpec] = None,
                          tp_axis: Optional[str] = None,
                          aux_loss_coef: Optional[float] = None,
                          sp_axis: Optional[str] = None):
        """Interleaved 1F1B with manual in-loop backward — O(stages) activation memory.

        Reference semantics: ``runtime/pipe/engine.py:295`` executing
        ``schedule.py:TrainSchedule`` (warmup forwards, steady-state one-forward-one-
        backward, drain). The SPMD realisation runs one lockstep ``lax.scan`` over
        ``2(M+S)-3`` ticks; at tick ``t`` stage ``s`` forwards microbatch ``(t-s)/2`` and
        backwards microbatch ``(t-(2S-2-s))/2`` (both when valid — steady-state ticks do
        one of each, the 1F1B alternation). Activations cross stages by ``ppermute``;
        cotangents ride the reverse permute one tick behind.

        Unlike the GPipe path (autodiff through the fill-drain loop, which stores an
        O(M) boundary-activation residual per stage), gradients here are computed *inside*
        the loop: each stage keeps a circular stash of its last ``S`` microbatch inputs and,
        on a backward tick, re-plays its block run under ``jax.vjp`` (per-microbatch remat
        — the 2× forward cost every 1F1B implementation pays via activation checkpointing)
        and folds parameter cotangents into fp32 accumulators carried by the scan. Nothing
        autodiffs *through* the scan, so peak activation memory is the stash — O(S·mb),
        flat in M (verified by ``test_1f1b_memory_flat_in_microbatches``).

        The pre segment (embeddings) runs on stage 0 *inside* its forward tick and the
        post segment + loss on the last stage inside its tick, so no O(M) staging buffer
        exists anywhere. Tied parameters may be consumed by both segments; their two
        cotangent streams meet in the cross-stage ``psum`` (the reference's
        ``ReduceTiedGrads``).

        With ``tp_axis``, the shard_map goes manual over {pipe, tensor}: body weights
        are PHYSICALLY sharded per the layer's Megatron col/row rules and the stage_fn
        is the layer's manual-collective ``tp_apply_factory`` forward (explicit psum
        after each row-parallel matmul) — reference 3D parallelism with TP inside
        pipeline stages (``runtime/pipe/topology.py:243``). Activations (and the
        pre/post/tied segments) replicate over tensor; their VJPs produce identical
        cotangents on every tensor shard.

        Returns ``fn(params, batch, rng) -> loss`` wrapped in ``jax.custom_vjp`` whose
        forward pass also produces the full parameter gradient (the engine's
        ``value_and_grad`` triggers exactly one loop execution).
        """
        S = self.num_stages
        L_per = self.layers_per_stage
        body_layer = self._layers[self.body_start]
        n_layers = len(self._layers)
        # MoE body layers emit an auxiliary load-balancing scalar per layer; it is
        # summed over layers in the stage scan, over stages in the final pipe psum,
        # and over microbatches in the loss accumulator — then weighted by
        # aux_loss_coef. Dense layers emit 0.0 (DCE'd by XLA).
        body_aux = bool(getattr(body_layer, "has_aux", False))
        if aux_loss_coef is None:
            aux_loss_coef = self.aux_loss_coef
        aux_coef = jnp.float32(aux_loss_coef)

        split_batch = _split_batch

        def pre_apply(pre_p, tied_p, x, mrng):
            view = {"pre": pre_p, "post": {}, "tied": tied_p}
            return self._segment_apply(view, x, mrng, 0, self.body_start)

        def tail_loss(post_p, tied_p, y, lab, mrng, sp=1):
            view = {"pre": {}, "post": post_p, "tied": tied_p}
            out = self._segment_apply(view, y, mrng, self.body_end, n_layers)
            if sp > 1:
                # sequence-sharded tail: per-shard loss contributions reduce to
                # the global mean via psum inside sp_loss_fn (sum/count over the
                # seq axis — unequal valid-token counts per shard stay exact)
                if not (self.sp_loss_fn is not None):
                    raise AssertionError("seq-parallel 1F1B needs PipelineModule.sp_loss_fn")
                return self.sp_loss_fn(out, lab, sp_axis)
            if self.loss_fn is not None:
                return self.loss_fn(out, lab)
            return out if out.ndim == 0 else jnp.mean(out)

        tp_fns = {}   # tp degree -> manual-collective layer forward (built lazily)
        sp_fns = {}   # sp degree -> ring-local layer forward (built lazily)

        def _layer_apply(tp, sp=1):
            if sp > 1 and sp_axis is not None:
                # pipe×seq: activations are sequence-sharded inside the stage;
                # attention all-gathers K/V over the seq axis (GROUPED collective
                # — a ppermute ring under the pipe-staggered conds is undefined,
                # see ops/attention/ring.py:allgather_attention_local)
                if not (not body_aux):
                    raise AssertionError("seq parallelism inside 1F1B does not compose with " \
                    "aux-loss (MoE) bodies yet")
                key = (tp, sp)
                if key not in sp_fns:
                    if tp > 1 and tp_axis is not None:
                        # pipe×tensor×seq 4D: the TP block with seq-sharded
                        # activations — dense/LN are per-token, only attention
                        # changes (local heads over seq-gathered K/V)
                        import inspect
                        factory = getattr(body_layer, "tp_apply_factory", None)
                        if not (factory is not None):
                            raise AssertionError("pipe×tensor×seq needs a body tp_apply_factory")
                        sig = inspect.signature(factory)
                        if not ("sp_axis" in sig.parameters or any(
                            p.kind == inspect.Parameter.VAR_KEYWORD
                            for p in sig.parameters.values())):
                            raise AssertionError("the body's tp_apply_factory does not accept "
                             "sp_axis — pipe×tensor×seq needs one that does "
                             "(e.g. gpt2 blocks, models/gpt2.py:block_tp_apply)")
                        sp_fns[key] = factory(tp, tp_axis, sp_axis=sp_axis)
                    else:
                        factory = getattr(body_layer, "sp_apply_factory", None)
                        if not (factory is not None):
                            raise AssertionError("sequence parallelism inside the 1F1B pipeline "
                             "needs a body layer with sp_apply_factory (e.g. "
                             "gpt2_pipe blocks with GPT2Config(split_qkv=True))")
                        sp_fns[key] = factory(sp, sp_axis)
                fn = sp_fns[key]
                return lambda p, x, r: (fn(p, x, r), jnp.float32(0.0))
            if tp <= 1 or tp_axis is None:
                if body_aux:
                    return lambda p, x, r: body_layer.apply_with_aux(p, x, r)
                return lambda p, x, r: (body_layer.apply(p, x, r),
                                        jnp.float32(0.0))
            if not (not body_aux):
                raise AssertionError("in-stage tensor parallelism and aux-loss (MoE) body layers are "
                 "not composed yet — run MoE pipelines with tp_axis=None and "
                 "shard experts over the expert axis instead")
            if tp not in tp_fns:
                factory = getattr(body_layer, "tp_apply_factory", None)
                if not (factory is not None):
                    raise AssertionError("tensor parallelism inside the 1F1B pipeline needs a body layer "
                     "with tp_apply_factory (e.g. gpt2_pipe blocks with "
                     "split_qkv=True)")
                tp_fns[tp] = factory(tp, tp_axis)
            fn = tp_fns[tp]
            return lambda p, x, r: (fn(p, x, r), jnp.float32(0.0))

        def make_stage_fn(tp, sp=1):
            layer_fn = _layer_apply(tp, sp)

            def stage_fn(stage_params, x, srng, use_rng):
                def one(carry, xs_):
                    p, r = xs_
                    y, aux = layer_fn(p, carry, r if use_rng else None)
                    return y, aux

                rngs = jax.random.split(srng, L_per)
                y, auxs = jax.lax.scan(one, x, (stage_params, rngs))
                return y, jnp.sum(auxs).astype(jnp.float32)
            return stage_fn

        def idx(tree, m):
            return jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, m, 0, keepdims=False), tree)

        def tree_add(acc, new):
            return jax.tree_util.tree_map(jnp.add, acc, new)

        def f32_cast(tree):
            return jax.tree_util.tree_map(
                lambda x: x.astype(jnp.float32), tree)

        def f32_zeros(tree):
            return jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), tree)

        def run_1f1b(params, batch, rng, use_rng: bool):
            mesh = mesh_spec or _require_global_mesh()
            tp = mesh.size(tp_axis) if tp_axis else 1
            sp = mesh.size(sp_axis) if sp_axis else 1
            stage_fn = make_stage_fn(tp, sp)
            inputs, labels = split_batch(batch)
            M = jax.tree_util.tree_leaves(inputs)[0].shape[0]
            n_ticks = 2 * (M + S) - 3
            rng_pre = jax.random.fold_in(rng, 1)
            rng_body = jax.random.fold_in(rng, 2)
            rng_tail = jax.random.fold_in(rng, 3)

            def run(body_p, pre_p, post_p, tied_p, inputs_, labels_):
                s = jax.lax.axis_index(AXIS_PIPE)

                # trace one pre output to size the activation stash
                x0_shape = jax.eval_shape(
                    pre_apply, _abstract(pre_p), _abstract(tied_p),
                    _abstract(idx(inputs_, 0)), rng_pre)
                # pipe×seq: the PRE segment runs on FULL sequences (embeddings
                # are cheap and position-offset-free); the BODY and TAIL carry
                # t/sp local chunks (tail loss reduces via sp_loss_fn's psum) —
                # stash, recv buffers and cross-stage permutes all shrink by sp,
                # and attention all-gathers K/V over the seq axis
                if sp > 1:
                    t_full = x0_shape.shape[1]
                    if not (t_full % sp == 0):
                        raise AssertionError((t_full, sp))
                    tl_sp = t_full // sp
                    s_sp = jax.lax.axis_index(sp_axis)
                    body_shape = (x0_shape.shape[0], tl_sp) + \
                        tuple(x0_shape.shape[2:])

                    def to_local(x_full):
                        return jax.lax.dynamic_slice_in_dim(
                            x_full, s_sp * tl_sp, tl_sp, axis=1)

                    def to_full_cot(dx_local):
                        zeros = jnp.zeros(tuple(x0_shape.shape),
                                          dx_local.dtype)
                        return jax.lax.dynamic_update_slice_in_dim(
                            zeros, dx_local, s_sp * tl_sp, axis=1)
                else:
                    body_shape = tuple(x0_shape.shape)
                    to_local = to_full_cot = lambda x: x
                stash0 = jnp.zeros((S,) + body_shape, x0_shape.dtype)

                carry0 = dict(
                    recv_f=jnp.zeros(body_shape, x0_shape.dtype),
                    recv_b=jnp.zeros(body_shape, x0_shape.dtype),
                    stash=stash0,
                    loss=jnp.float32(0.0),
                    dbody=f32_zeros(body_p),
                    dpre=f32_zeros(pre_p),
                    dpost=f32_zeros(post_p),
                    dtied=f32_zeros(tied_p),
                )

                def tick(carry, t):
                    # Every phase sits behind lax.cond on its validity predicate: for a
                    # given stage, forward ticks (t-s even) and backward ticks
                    # (t-(2S-2-s) even) share parity, so half of all ticks are no-ops —
                    # cond (not jnp.where-after-compute) lets XLA skip them, and the
                    # tail/pre VJPs additionally run only on the stage that keeps them.
                    last = s == S - 1
                    # ---------------- forward phase -----------------------------
                    mf_raw = t - s
                    is_f = (mf_raw >= 0) & (mf_raw % 2 == 0) & (mf_raw // 2 < M)
                    mf = jnp.clip(mf_raw // 2, 0, M - 1)

                    def fwd_block(stash_in, recv_f):
                        x0 = pre_apply(
                            pre_p, tied_p, idx(inputs_, mf),
                            jax.random.fold_in(rng_pre, mf) if use_rng else None)
                        x_in = jnp.where(s == 0, to_local(x0), recv_f)
                        y, aux = stage_fn(
                            body_p, x_in,
                            jax.random.fold_in(jax.random.fold_in(rng_body, mf), s),
                            use_rng)
                        return y, jax.lax.dynamic_update_index_in_dim(
                            stash_in, x_in, mf % S, 0), aux

                    def fwd_skip(stash_in, recv_f):
                        return jnp.zeros_like(recv_f), stash_in, jnp.float32(0.0)

                    y, stash, aux_m = jax.lax.cond(is_f, fwd_block, fwd_skip,
                                                   carry["stash"], carry["recv_f"])

                    def tail_block(y_):
                        lab_m = idx(labels_, mf) if labels_ is not None else None
                        if sp > 1 and lab_m is not None:
                            lab_m = jax.tree_util.tree_map(
                                lambda a: jax.lax.dynamic_slice_in_dim(
                                    a, s_sp * tl_sp, tl_sp, axis=1), lab_m)
                        loss_m, tail_vjp = jax.vjp(
                            lambda po, ti, yy: tail_loss(
                                po, ti, yy, lab_m,
                                jax.random.fold_in(rng_tail, mf) if use_rng
                                else None, sp=sp),
                            post_p, tied_p, y_)
                        dpost_m, dtied_m, dy_m = tail_vjp(jnp.float32(1.0))
                        return (loss_m.astype(jnp.float32), f32_cast(dpost_m),
                                f32_cast(dtied_m), dy_m.astype(y_.dtype))

                    def tail_skip(y_):
                        return (jnp.float32(0.0), f32_zeros(post_p),
                                f32_zeros(tied_p), jnp.zeros_like(y_))

                    loss_m, dpost_m, dtied_tail_m, dy_m = jax.lax.cond(
                        is_f & last, tail_block, tail_skip, y)
                    # every stage contributes its own layers' aux on its forward tick
                    loss = carry["loss"] + loss_m + aux_coef * aux_m
                    dpost = tree_add(carry["dpost"], dpost_m)
                    dtied = tree_add(carry["dtied"], dtied_tail_m)

                    # ---------------- backward phase ----------------------------
                    mb_raw = t - (2 * S - 2 - s)
                    is_b = (mb_raw >= 0) & (mb_raw % 2 == 0) & (mb_raw // 2 < M)
                    mb = jnp.clip(mb_raw // 2, 0, M - 1)
                    cot = jnp.where(last, dy_m, carry["recv_b"])

                    def bwd_block(stash_in, cot_):
                        x_saved = jax.lax.dynamic_index_in_dim(stash_in, mb % S, 0,
                                                               keepdims=False)
                        _, svjp = jax.vjp(
                            lambda bp, xx: stage_fn(
                                bp, xx,
                                jax.random.fold_in(jax.random.fold_in(rng_body, mb), s),
                                use_rng),
                            body_p, x_saved)
                        # aux output's cotangent is its loss weight: gate/expert
                        # params receive the load-balancing gradient here
                        dbody_m, dx = svjp((cot_, aux_coef))
                        return f32_cast(dbody_m), dx.astype(cot_.dtype)

                    def bwd_skip(stash_in, cot_):
                        return f32_zeros(body_p), jnp.zeros_like(cot_)

                    dbody_m, dx = jax.lax.cond(is_b, bwd_block, bwd_skip, stash, cot)
                    dbody = tree_add(carry["dbody"], dbody_m)

                    def pre_block(dx_):
                        # stage 0 re-plays the pre segment to push dx into embeddings/tied
                        # (sp: scatter the LOCAL chunk's cotangent into the full-
                        # sequence zeros — other chunks contribute via the sp psum)
                        _, pvjp = jax.vjp(
                            lambda pr, ti: pre_apply(
                                pr, ti, idx(inputs_, mb),
                                jax.random.fold_in(rng_pre, mb) if use_rng else None),
                            pre_p, tied_p)
                        dpre_m, dtied_m = pvjp(to_full_cot(dx_))
                        return f32_cast(dpre_m), f32_cast(dtied_m)

                    def pre_skip(dx_):
                        return f32_zeros(pre_p), f32_zeros(tied_p)

                    dpre_m, dtied_pre_m = jax.lax.cond(is_b & (s == 0),
                                                       pre_block, pre_skip, dx)
                    dpre = tree_add(carry["dpre"], dpre_m)
                    dtied = tree_add(dtied, dtied_pre_m)

                    new_carry = dict(
                        recv_f=jax.lax.ppermute(
                            y, AXIS_PIPE, [(i, i + 1) for i in range(S - 1)]),
                        recv_b=jax.lax.ppermute(
                            dx, AXIS_PIPE, [(i, i - 1) for i in range(1, S)]),
                        stash=stash, loss=loss, dbody=dbody, dpre=dpre,
                        dpost=dpost, dtied=dtied)
                    return new_carry, None

                out, _ = jax.lax.scan(tick, carry0, jnp.arange(n_ticks))
                inv_m = jnp.float32(1.0 / M)
                loss = jax.lax.psum(out["loss"] * inv_m, AXIS_PIPE)
                scale_tree = lambda tr: jax.tree_util.tree_map(
                    lambda g: g * inv_m, tr)
                # sp: pre/post/tied/body grads are per-shard partials (each seq
                # shard differentiated only its tokens' contribution) — sum them
                repl_axes = (AXIS_PIPE, sp_axis) if sp > 1 else AXIS_PIPE
                dpre = jax.lax.psum(scale_tree(out["dpre"]), repl_axes)
                dpost = jax.lax.psum(scale_tree(out["dpost"]), repl_axes)
                dtied = jax.lax.psum(scale_tree(out["dtied"]), repl_axes)
                dbody = scale_tree(out["dbody"])
                if sp > 1:
                    dbody = jax.lax.psum(dbody, sp_axis)
                return loss, dbody, dpre, dpost, dtied

            lab_spec = None if labels is None else P()
            if tp > 1:
                body_specs = self.param_specs(tp_axis=tp_axis, tp_size=tp)["body"]
                manual_axes = {AXIS_PIPE, tp_axis}
            else:
                body_specs = P(AXIS_PIPE)
                manual_axes = {AXIS_PIPE}
            if sp > 1:
                manual_axes = manual_axes | {sp_axis}
            mapped = shard_map(
                run,
                mesh=mesh.mesh,
                axis_names=manual_axes,
                in_specs=(body_specs, P(), P(), P(), P(), lab_spec),
                out_specs=(P(), body_specs, P(), P(), P()),
                check_vma=False,
            )
            loss, dbody, dpre, dpost, dtied = mapped(
                params["body"], params["pre"], params["post"], params["tied"],
                inputs, labels)
            grads = {"body": dbody, "pre": dpre, "post": dpost, "tied": dtied}
            grads = jax.tree_util.tree_map(
                lambda g, p: g.astype(p.dtype), grads,
                {"body": params["body"], "pre": params["pre"],
                 "post": params["post"], "tied": params["tied"]})
            return loss, grads

        @jax.custom_vjp
        def pipe_loss(params, batch, rng):
            loss, _ = run_1f1b(params, batch, rng, use_rng=True)
            return loss

        def pipe_loss_fwd(params, batch, rng):
            loss, grads = run_1f1b(params, batch, rng, use_rng=True)
            return loss, (grads, batch, rng)

        def pipe_loss_bwd(res, g):
            grads, batch, rng = res
            dparams = jax.tree_util.tree_map(lambda x: (x * g).astype(x.dtype), grads)
            return dparams, _zero_cotangent(batch), _zero_cotangent(rng)

        pipe_loss.defvjp(pipe_loss_fwd, pipe_loss_bwd)
        return pipe_loss

    # ------------------------------------------------------------------ model adapter
    def to_model(self, mesh_spec: Optional[MeshSpec] = None, name: str = "pipeline",
                 remat: Optional[bool] = None, schedule: str = "1f1b",
                 tp_axis: Optional[str] = None, tp_size: Optional[int] = None,
                 ep_size: Optional[int] = None, sp_axis: Optional[str] = None):
        """Bundle into the engine's :class:`Model` contract. ``loss_fn`` consumes microbatched
        batches ``(inputs, labels)`` with leading dim M and returns mean loss; ``rng=None``
        runs a deterministic (dropout-off) pass.

        ``schedule``: ``"1f1b"`` (default) trains through the interleaved
        one-forward-one-backward loop with in-loop gradients — O(stages) activation
        memory (see :meth:`make_1f1b_loss_fn`); ``"gpipe"`` trains by autodiff through
        the fill-drain loop (O(microbatches) boundary residuals, no recompute). Eval
        always uses the forward-only fill-drain pipeline.
        """
        # imported here, not at module top: models/__init__ imports gpt2_pipe which imports
        # this module — a top-level import would make the cycle order-dependent
        from ...models.base import Model
        if remat is None:
            remat = self.activation_checkpoint_interval > 0
        if not (schedule in ("1f1b", "gpipe")):
            raise AssertionError(schedule)
        body_has_aux = bool(getattr(self._layers[self.body_start], "has_aux",
                                    False))
        pipe_loss_1f1b = (self.make_1f1b_loss_fn(mesh_spec, tp_axis=tp_axis,
                                                 aux_loss_coef=self.aux_loss_coef,
                                                 sp_axis=sp_axis)
                          if schedule == "1f1b" and self.num_stages > 1 else None)
        if body_has_aux and pipe_loss_1f1b is None:
            raise NotImplementedError(
                "aux-loss (MoE) body layers train through the 1F1B schedule only "
                "(the fill-drain/GPipe loop does not aggregate aux losses) — use "
                "schedule='1f1b' with num_stages > 1")

        split_batch = _split_batch

        def loss_fn(params, batch, rng):
            mesh = mesh_spec or _require_global_mesh()
            inputs, labels = split_batch(batch)
            M = jax.tree_util.tree_leaves(inputs)[0].shape[0]
            if rng is None:  # deterministic pass (eval)
                if tp_axis is not None and mesh.size(tp_axis) > 1:
                    # TP body params are physically sharded; the fill-drain shard_map
                    # is pipe-manual-only and cannot consume them — evaluate via the
                    # sequential reference path under GSPMD auto-sharding instead
                    def eval_one(inp, lab):
                        out = self.reference_apply(params, inp, None)
                        if self.loss_fn is not None:
                            return self.loss_fn(out, lab)
                        return out if out.ndim == 0 else jnp.mean(out)

                    return jnp.mean(jax.vmap(eval_one)(inputs, labels))
                xs = jax.vmap(
                    lambda inp: self._segment_apply(params, inp, None, 0, self.body_start)
                )(inputs)
                ys = self.pipelined_apply(params, xs, mesh, rng=None, remat=remat)

                def tail_det(y, lab):
                    out = self._segment_apply(params, y, None, self.body_end,
                                              len(self._layers))
                    if self.loss_fn is not None:
                        return self.loss_fn(out, lab)
                    return out if out.ndim == 0 else jnp.mean(out)

                return jnp.mean(jax.vmap(tail_det)(ys, labels))

            if pipe_loss_1f1b is not None:
                return pipe_loss_1f1b(params, batch, rng)

            pre_rngs = jax.random.split(jax.random.fold_in(rng, 1), M)
            xs = jax.vmap(
                lambda inp, r: self._segment_apply(params, inp, r, 0, self.body_start)
            )(inputs, pre_rngs)
            ys = self.pipelined_apply(params, xs, mesh,
                                      rng=jax.random.fold_in(rng, 2), remat=remat)
            post_rngs = jax.random.split(jax.random.fold_in(rng, 3), M)

            def tail(y, lab, r):
                out = self._segment_apply(params, y, r, self.body_end, len(self._layers))
                if self.loss_fn is not None:
                    return self.loss_fn(out, lab)
                return out if out.ndim == 0 else jnp.mean(out)

            losses = jax.vmap(tail)(ys, labels, post_rngs)
            return jnp.mean(losses)

        def apply_fn(params, batch, rng=None):
            inputs, _ = split_batch(batch)
            out = self.reference_apply(params, inputs, rng)
            # builders whose head emits a non-logits payload (e.g. the chunked-
            # vocab (hidden, wte) tuple) install a transform so apply_fn keeps
            # the logits contract callers rely on
            if self.apply_transform is not None:
                out = self.apply_transform(out)
            return out

        return Model(loss_fn=loss_fn, init_fn=self.init_fn, apply_fn=apply_fn,
                     param_specs=self.param_specs(tp_axis=tp_axis, tp_size=tp_size,
                                                  ep_size=ep_size),
                     name=name)

    def __len__(self):
        return len(self._layers)


def _abstract(p):
    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), p)


def _zero_cotangent(tree):
    """Zero cotangents for a possibly-integer pytree (custom_vjp bwd for nondiff inputs):
    float leaves get zeros, integer leaves (tokens, PRNG keys) get float0."""
    def one(x):
        if jnp.issubdtype(jnp.result_type(x), jnp.inexact):
            return jnp.zeros_like(x)
        return np.zeros(jnp.shape(x), dtype=jax.dtypes.float0)
    return jax.tree_util.tree_map(one, tree)


def _require_global_mesh() -> MeshSpec:
    from ...parallel.mesh import get_global_mesh
    mesh = get_global_mesh()
    if not (mesh is not None):
        raise AssertionError("pipeline loss_fn needs a global mesh (set by the engine)")
    return mesh
