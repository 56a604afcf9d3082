"""Inference engine: TP-sharded serving with AOT-compiled prefill/decode.

Reference: ``deepspeed/inference/engine.py`` (``InferenceEngine:35``,
``_create_model_parallel_group:201``, ``_create_cuda_graph:479``, ``forward:541``,
``_generate:571``). TPU-native redesign:

- TP groups → a mesh with a ``tensor`` axis; params land sharded via Megatron-rule
  PartitionSpecs (the compile-time equivalent of ``ReplaceWithTensorSlicing``,
  ``module_inject/replace_module.py:25``);
- CUDA-graph capture → ``jax.jit`` AOT compilation of the prefill and decode steps with
  donated KV caches (fixed shapes, zero host round-trips between decode iterations);
- kernel injection → the fused Pallas decode-attention path inside ``models/causal_lm.py``
  (selected per family by the policy registry in ``module_inject``).
"""

import dataclasses
import os
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..observability.metrics import record_events as obs_record_events
from ..observability.trace import get_tracer
from ..models.causal_lm import (CausalLM, CausalLMConfig, causal_lm_param_specs,
                                init_cache)
from ..parallel.mesh import AXIS_DATA, AXIS_TENSOR, MeshSpec, set_global_mesh
from ..parallel.overlap import resolve_overlap_config, set_overlap_config
from ..utils.logging import log_dist, logger
from .config import DeepSpeedInferenceConfig
from .decode_fns import (block_view_rows, build_block_decode_loop, build_decode_loop,
                         build_prefill, make_select_fn, make_slot_select_fn, open_block)


def spec_fits(mesh_spec, shape, spec) -> bool:
    """Every named axis (incl. tuple entries) divides its dimension — the shared
    placement guard of the decoder and encoder serving engines (non-divisible
    leaves fall back to replication instead of crashing device_put)."""
    for i, entry in enumerate(tuple(spec)):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        for ax in axes:
            if shape[i] % mesh_spec.size(ax) != 0:
                return False
    return True


class InferenceEngine:
    """Serve a :class:`CausalLM` (or anything converted to one by ``module_inject``)."""

    def __init__(self, model, config: Optional[DeepSpeedInferenceConfig] = None,
                 params: Optional[Any] = None, mesh_spec: Optional[MeshSpec] = None,
                 seed: int = 0):
        with get_tracer().phase("setup.inference_engine_init"):
            self._init(model, config, params, mesh_spec, seed)

    def _init(self, model, config, params, mesh_spec, seed):
        self._config = config or DeepSpeedInferenceConfig()
        tp = self._config.resolved_tp()
        dp = max(1, int(self._config.data_parallel))
        self.mesh_spec = mesh_spec or MeshSpec(
            {AXIS_TENSOR: tp, AXIS_DATA: dp}, devices=jax.devices()[:tp * dp])
        # activate our mesh BEFORE any model tracing — a previously-active engine's mesh
        # must not leak into this engine's init/forward traces
        set_global_mesh(self.mesh_spec)
        # comm-compute overlap (chunked collective matmuls on the TP decode
        # path); installed like the mesh so every trace this engine initiates
        # sees ITS setting, and threaded into the compiled-step builders
        self.comm_overlap = resolve_overlap_config(self._config.comm_overlap)
        set_overlap_config(self.comm_overlap)

        # validate the impl override BEFORE any model resolution/tracing so a
        # bad value ('triton', 'XLA') fails fast at construction
        if self._config.moe_decode_impl is not None and \
                self._config.moe_decode_impl not in \
                CausalLMConfig.VALID_MOE_DECODE_IMPLS:
            raise ValueError(
                f"moe_decode_impl={self._config.moe_decode_impl!r} is not "
                f"one of {CausalLMConfig.VALID_MOE_DECODE_IMPLS}")
        self.model_config, self.params = self._resolve_model(model, params, seed)
        self.dtype = self._config.jax_dtype()
        # serve dtype wins over the model's training dtype (reference _convert_to_dtype:462)
        self.model_config.dtype = self.dtype
        if self._config.moe_decode_impl is not None:
            # applied before the module exists so every compiled fn sees it
            self.model_config.moe_decode_impl = self._config.moe_decode_impl
        self.module = CausalLM(self.model_config)

        self._shard_params()
        self._fns: Dict[str, Any] = {}
        self.ttft: Optional[float] = None
        self.tpot: Optional[float] = None          # seconds per decode token (per seq)
        self.decode_tps: Optional[float] = None    # decode tokens/sec across the batch
        self._monitor = None                       # optional MonitorMaster
        self._gen_count = 0
        log_dist(f"inference engine ready: {self.model_config.name} "
                 f"params≈{self.model_config.num_params():,} tp={tp} dp={dp} "
                 f"dtype={self.dtype.__name__}", ranks=[0])

    # ------------------------------------------------------------------ setup
    def _resolve_model(self, model, params, seed):
        if isinstance(model, CausalLMConfig):
            cfg = model
            if params is None:
                with get_tracer().phase("setup.init_params"):
                    params = self._init_params_segmented(cfg, seed)
                if cfg.home_random_routers:
                    from ..moe.latent_moe import home_random_routers
                    with get_tracer().phase("setup.balance_experts"):
                        params = home_random_routers(cfg, params, seed)
                    log_dist("stand-in weights: every token id has its home "
                             "experts (home_random_routers)", ranks=[0])
                if cfg.level_random_experts:
                    from ..moe.latent_moe import level_expert_load
                    with get_tracer().phase("setup.balance_experts"):
                        params, before, after = level_expert_load(
                            dataclasses.replace(cfg, dtype=self._config.jax_dtype()),
                            params, seed)
                    log_dist("stand-in weights: expert load, largest over mean "
                             f"{before:.2f} -> {after:.2f} (selection bias "
                             "levelled on random tokens)", ranks=[0])
            return cfg, params
        if isinstance(model, tuple) and len(model) == 2:
            cfg, params = model
            if isinstance(cfg, CausalLMConfig):
                return cfg, params
            # our training models' (config, params): GPT2Config / GPT2MoEConfig
            from ..models.gpt2 import GPT2Config
            if isinstance(cfg, GPT2Config):
                from ..module_inject.replace_module import convert_training_model
                return convert_training_model(cfg, params)
            return cfg, params
        # HF torch module → policy conversion (module_inject analogue)
        from ..module_inject.replace_module import convert_hf_model
        return convert_hf_model(model)

    def _init_params_segmented(self, cfg, seed):
        """Random weights in the SERVE dtype, initialised one model segment at a time
        (reuses the offload_param decomposition) and born in their final TP
        placement: a 7B bf16 model inits in ~14 GB of HBM spread over the mesh —
        never the ~28 GB a monolithic fp32 ``module.init`` would need, and never
        the whole model on device 0 waiting to be resharded. Transient fp32 peaks
        one segment."""
        from ..models.causal_lm import causal_lm_segments
        serve_dtype = self._config.jax_dtype()
        segs = causal_lm_segments(cfg, layers_per_group=1)
        rng = jax.random.PRNGKey(seed)
        mesh = self.mesh_spec
        is_spec = lambda x: isinstance(x, P)
        init_jits = {}
        params = {}
        for si, seg in enumerate(segs):
            if not seg.init_keys:
                continue
            if seg.init_fn not in init_jits:
                def casted(r, fn=seg.init_fn):
                    return jax.tree_util.tree_map(
                        lambda x: x.astype(serve_dtype)
                        if x.dtype == jnp.float32 else x, fn(r))
                # the TP rules key on the path BELOW the top-level name, so
                # one sharding tree serves every segment sharing this init_fn
                shapes = dict(zip(seg.init_keys, jax.eval_shape(casted, rng)))
                specs = causal_lm_param_specs(shapes, tensor_axis=AXIS_TENSOR)
                shardings = tuple(
                    jax.tree_util.tree_map(
                        lambda spec, a: NamedSharding(
                            mesh.mesh,
                            spec if spec_fits(mesh, a.shape, spec) else P()),
                        specs[k], shapes[k], is_leaf=is_spec)
                    for k in seg.init_keys)
                init_jits[seg.init_fn] = jax.jit(casted,
                                                 out_shardings=shardings)
            sub = init_jits[seg.init_fn](jax.random.fold_in(rng, si))
            for key, tree in zip(seg.init_keys, sub):
                params[key] = tree
        return params

    def _spec_fits(self, shape, spec) -> bool:
        return spec_fits(self.mesh_spec, shape, spec)

    # weight-path names eligible for quantization (matmul kernels; embeddings,
    # norms and the lm_head stay in fp — the head shares the huge-vocab logits
    # matmul with tied ``wte``, and the reference GroupQuantizer likewise skips
    # embeddings)
    _QUANT_NAMES = ("q_proj", "k_proj", "v_proj", "o_proj", "fc_in", "fc_out",
                    "gate_proj", "up_proj")

    def _shard_params(self):
        with get_tracer().phase("setup.place_params"):
            self.params = self._place_params(self.params)

    def _place_params(self, raw):
        """Cast to serve dtype, optionally grouped-quantize matmul weights
        (``weight_quant`` config block; the legacy ``quant``/``dtype="int8"``
        spellings resolve to its 8-bit defaults), and device_put with Megatron
        TP specs.

        Quantized leaves become ``{"__int8_q__"|"__int4_q__", *_scale__}``
        nodes that stay quantized through the decode hot path: the model's
        projection sites (``QuantDense``/``RowParallelDense``) feed them to the
        fused dequant-matmul kernels so int8/int4 bytes are what streams from
        HBM. On non-TPU backends :meth:`_dequant` collapses the tree once per
        dispatch instead.

        Every candidate matrix passes a quantize-time relative-error audit
        (``quantize_with_audit``): outlier-heavy matrices (relative Frobenius
        error above ``weight_quant.outlier_threshold``) and ``exclude``-listed
        paths stay in the serve dtype. Decisions — including the EFFECTIVE
        group size when the requested group does not divide k — land in
        ``self.quant_audit`` and are logged via ``log_dist`` /
        :meth:`set_monitor`."""
        specs = causal_lm_param_specs(raw, tensor_axis=AXIS_TENSOR)
        mesh = self.mesh_spec
        if self._config.quant.enabled or self._config.is_int8():
            from ..ops.quantizer import validate_quant_config
            validate_quant_config(self._config.quant)
        wq = self._config.resolved_weight_quant()
        if wq.enabled and wq.bits not in (8, 4):
            raise ValueError(f"weight_quant.bits={wq.bits} not in (8, 4)")
        if wq.enabled and wq.group < 1:
            raise ValueError(f"weight_quant.group={wq.group} must be >= 1")
        self._wq = wq
        threshold = wq.resolved_threshold()
        audit = []
        self._raw_template = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), getattr(x, "dtype", np.float32)),
            raw)

        def put(arr, spec):
            if not self._spec_fits(arr.shape, spec):
                spec = P(*([None] * arr.ndim))
            return jax.device_put(arr, NamedSharding(mesh.mesh, spec))

        def quantizable(path_tuple, arr):
            if arr.ndim < 2:
                return False
            names = set(path_tuple)
            if names & set(self._QUANT_NAMES) and path_tuple[-1] == "kernel":
                return True
            return "moe_experts" in names and path_tuple[-1] in ("w1", "w2")

        def walk(node, spec_node, path):
            if isinstance(node, dict):
                return {k: walk(v, spec_node[k], path + (k,)) for k, v in node.items()}
            arr = jnp.asarray(node)
            if arr.ndim >= 2 and arr.dtype in (jnp.float32, jnp.float16, jnp.bfloat16):
                arr = arr.astype(self.dtype)
            if wq.enabled and quantizable(path, arr):
                pstr = "/".join(path)
                if any(sub in pstr for sub in wq.exclude):
                    audit.append({"name": pstr, "decision": "excluded",
                                  "reason": "weight_quant.exclude match",
                                  "bits": wq.bits, "group_requested": wq.group,
                                  "group_effective": None, "rel_err": None})
                else:
                    from ..ops.quantizer import quantize_with_audit
                    qnode, info = quantize_with_audit(
                        arr, bits=wq.bits, group_size=wq.group,
                        threshold=threshold, name=pstr)
                    audit.append(info)
                    if qnode is not None:
                        spec_t = tuple(spec_node) + \
                            (None,) * (arr.ndim - len(tuple(spec_node)))
                        return {k: put(v, P(*spec_t)) for k, v in qnode.items()}
            return put(arr, spec_node)

        placed = walk(raw, specs, ())
        self._param_specs = specs
        self.quant_audit = audit
        n_q = sum(1 for e in audit if e["decision"] == "quantized")
        self._quantized = wq.enabled and n_q > 0
        if wq.enabled:
            for e in audit:
                if e["decision"] != "quantized":
                    log_dist(f"weight_quant: {e['name']} kept fp — {e['reason']}",
                             ranks=[0])
                elif e["group_effective"] != e["group_requested"]:
                    log_dist(f"weight_quant: {e['name']} effective group "
                             f"{e['group_effective']} (requested {wq.group})",
                             ranks=[0])
            log_dist(
                f"weight_quant: int{wq.bits} group={wq.group} — {n_q} matrices "
                f"quantized, {len(audit) - n_q} kept fp "
                f"(outlier_threshold={threshold})", ranks=[0])
        return placed

    def weight_stream_report(self) -> Dict[str, float]:
        """Modeled HBM weight-stream bytes for one full pass over the params
        (≈ one decode step: every matmul weight read once). Quant nodes use
        the fused kernel's own block accounting (``node_weight_bytes`` —
        payload + scales, each block read exactly once). Everything fp — the
        kept-fp matrices AND the bf16-equivalent of quantized ones — is
        billed at 2 bytes/elem, so the model describes a bf16 TPU deployment
        with one consistent denominator regardless of the dtype a CPU test
        engine happens to serve in. ``reduction_quantized_nodes`` is the
        kernel-accounting reduction over the quantized set (the modeled
        bytes-per-step figure); ``reduction_total`` includes the
        fp-kept matrices (embeddings/lm_head/excluded)."""
        from ..ops.quantizer import (dense_weight_bytes, is_quant_node,
                                     node_logical_shape, node_weight_bytes)
        acc = {"quantized_bytes": 0, "quantized_bf16_equiv": 0, "fp_bytes": 0}

        def walk(node):
            if is_quant_node(node):
                acc["quantized_bytes"] += node_weight_bytes(node)
                acc["quantized_bf16_equiv"] += dense_weight_bytes(
                    node_logical_shape(node), jnp.bfloat16)
            elif isinstance(node, dict):
                for v in node.values():
                    walk(v)
            elif getattr(node, "ndim", 0) >= 2:
                acc["fp_bytes"] += dense_weight_bytes(node.shape, jnp.bfloat16)

        walk(self.params)
        step = acc["quantized_bytes"] + acc["fp_bytes"]
        bf16_equiv = acc["quantized_bf16_equiv"] + acc["fp_bytes"]
        return {
            **acc,
            "modeled_step_bytes": step,
            "bf16_equiv_step_bytes": bf16_equiv,
            "reduction_total": bf16_equiv / step if step else 1.0,
            "reduction_quantized_nodes": (
                acc["quantized_bf16_equiv"] / acc["quantized_bytes"]
                if acc["quantized_bytes"] else 1.0),
        }

    def _dequant(self, params):
        """Per-dispatch parameter prep for the compiled-step builders.

        Unquantized: identity. Quantized on the fused backend (TPU, or forced
        via ``DS_TPU_WQ_FORCE_FUSED=1`` in tests): quant nodes pass through to
        the per-site fused dequant-matmul kernels — int8/int4 bytes stream
        from HBM inside the decode loop. Quantized on the XLA fallback backend
        (CPU hosts, excluded matrices): collapse the tree ONCE here — the
        builders call this OUTSIDE the compiled loop bodies, so the dequant is
        loop-invariant (HLO-pinned by ``test_weight_quant.py``) instead of
        re-derived every while_loop step."""
        if not getattr(self, "_quantized", False):
            return params
        from ..ops.quantizer import dequantize_tree, fused_backend_active
        if fused_backend_active():
            return params
        return dequantize_tree(params, self.dtype)

    # ------------------------------------------------------------------ compiled steps
    def _build_fns(self):
        if self.model_config.gen_block_length:
            self._fns["forward"] = jax.jit(self._block_forward)
            return
        self._fns["forward"] = jax.jit(
            lambda params, ids: self.module.apply(
                {"params": self._dequant(params)}, ids))

    def _block_forward(self, params, ids):
        """``forward`` of a model that generates by diffusion over blocks of
        ``B``, under the same contract: row ``p`` is what token ``p + 1`` is
        chosen from given ``ids[:p + 1]``. For this model that is the logits
        AT position ``p + 1`` with ``p + 1`` to the end of its block masked
        and everything before it clean (the order ``sequential`` unmasks in).
        One forward gives every row: the family's training layout, a clean
        copy of the sequence beside ``B`` masked copies under one mask. Copy
        ``r`` has the places ``>= r`` of every block masked; a query of a
        masked copy sees the blocks before its own in the CLEAN copy and its
        own block in its own copy; the clean copy is block-causal. Row ``p``
        is read from copy ``(p + 1) % B`` at position ``p + 1``."""
        cfg = self.model_config
        B = cfg.gen_block_length
        b, t = ids.shape
        tp = (t // B + 1) * B                      # room for position t
        pos = np.arange(tp)
        clean = jnp.pad(ids, ((0, 0), (0, tp - t)), constant_values=cfg.mask_token_id)
        copies = [clean] + [jnp.where((pos % B >= r)[None], cfg.mask_token_id, clean)
                            for r in range(B)]
        copy = np.repeat(np.arange(B + 1), tp)     # which copy a place of the row is in
        block = np.tile(pos // B, B + 1)
        mask = ((copy[None, :] == 0) & (block[None, :] < block[:, None])) | \
            ((copy[None, :] == copy[:, None]) & (block[None, :] == block[:, None]))
        want = np.arange(1, t + 1)
        rows = (1 + want % B) * tp + want          # copy (p + 1) % B, position p + 1
        return self.module.apply(
            {"params": self._dequant(params)}, jnp.concatenate(copies, axis=1),
            positions=jnp.broadcast_to(jnp.asarray(np.tile(pos, B + 1))[None],
                                       (b, (B + 1) * tp)),
            attn_mask=jnp.asarray(mask),
            logits_positions=jnp.broadcast_to(jnp.asarray(rows)[None], (b, t)))

    def _loop_fns(self, do_sample, temperature, top_k, top_p, gen_cap):
        """Device-resident generation: prefill (first token, synced for TTFT) + ONE compiled
        ``lax.while_loop`` for all remaining tokens — the XLA analogue of CUDA-graph replay
        (reference ``_create_cuda_graph:479``) with zero host round-trips in the decode loop;
        EOS termination is an on-device all-reduce in the loop condition.

        The step bodies live in ``decode_fns`` (``build_prefill``/``build_decode_loop``),
        shared with the serving executor's chunked variant (``build_paged_decode_chunk``) so the
        two decode paths cannot drift."""
        key = ("loop", do_sample, float(temperature), int(top_k), float(top_p), gen_cap)
        if key in self._fns:
            return self._fns[key]
        select = self._select_fn(do_sample, temperature, top_k, top_p)
        prefill_logits = build_prefill(self.module, self._dequant,
                                       overlap=self.comm_overlap)

        def prefill(params, ids, caches, lens0, rng):
            # ids may be right-padded: next-token logits are computed ONLY at each
            # sequence's last *valid* position (logits_positions skips the other
            # t-1 rows of the huge head matmul — a 250k-vocab 7B prompt's TTFT is
            # dominated by it otherwise)
            logits, new_caches = prefill_logits(params, ids, caches, lens0)
            return select(logits, rng), new_caches, lens0

        decode_loop = build_decode_loop(self.module, self._dequant, select, gen_cap,
                                        overlap=self.comm_overlap)

        # The loop's caches are donated and come back (``generate`` drops them): the
        # loop then updates its argument's buffers in place instead of carrying a
        # second copy of them. No donation on prefill (it rebuilds the cache buffers,
        # pad-write) nor on the block loop, which returns no caches to alias.
        loop_jit = jax.jit(decode_loop, donate_argnums=(2,))
        if self.model_config.gen_block_length:
            # the loop the serving chunk's forwards are: the same body
            loop_jit = jax.jit(build_block_decode_loop(
                self.module, self._dequant,
                make_slot_select_fn(do_sample, temperature, top_k, top_p), gen_cap,
                overlap=self.comm_overlap))
        fns = (jax.jit(prefill), loop_jit)
        self._fns[key] = fns
        return fns

    def _select_fn(self, do_sample, temperature, top_k, top_p):
        """Token-selection closure shared by the generation paths."""
        return make_select_fn(do_sample, temperature, top_k, top_p)

    # ------------------------------------------------------------------ API
    def set_monitor(self, monitor):
        """Attach a :class:`~deepspeed_tpu.monitor.MonitorMaster`; every ``generate``
        then emits ``inference/ttft_ms``, ``inference/tpot_ms`` and
        ``inference/decode_tokens_per_sec`` events (step = generate-call index).
        A weight-quantized engine also emits its quantization audit once on
        attach: matrix decisions and the modeled weight-stream reduction."""
        self._monitor = monitor
        audit = getattr(self, "quant_audit", None)
        if audit:
            rep = self.weight_stream_report()
            n_q = sum(1 for e in audit if e["decision"] == "quantized")
            events = [
                ("inference/weight_quant/bits", float(self._wq.bits), 0),
                ("inference/weight_quant/matrices_quantized", float(n_q), 0),
                ("inference/weight_quant/matrices_kept_fp",
                 float(len(audit) - n_q), 0),
                ("inference/weight_quant/modeled_step_bytes",
                 float(rep["modeled_step_bytes"]), 0),
                ("inference/weight_quant/reduction_vs_bf16",
                 float(rep["reduction_total"]), 0),
            ]
            obs_record_events(events)    # registry: independent of monitor
            if monitor is not None and getattr(monitor, "enabled", False):
                monitor.write_events(events)
        return self

    def _activate(self):
        # engines may coexist (e.g. tp=1 and tp=4); tracing consults the global mesh, so
        # re-assert ours before any compiled-fn call
        set_global_mesh(self.mesh_spec)
        set_overlap_config(self.comm_overlap)

    def forward(self, input_ids, *args, **kwargs):
        """Full forward logits (reference ``InferenceEngine.forward:541``)."""
        self._activate()
        ids = jnp.asarray(input_ids)
        if "forward" not in self._fns:
            self._build_fns()
        return self._fns["forward"](self.params, ids)

    __call__ = forward

    def generate(self, input_ids, max_new_tokens: int = 32, do_sample: bool = False,
                 temperature: float = 1.0, top_k: int = 0, top_p: float = 1.0,
                 eos_token_id: Optional[int] = None, seed: int = 0,
                 attention_mask=None, prompt_lengths=None, **kwargs):
        """Greedy/sampled generation, fully device-resident (reference ``_generate:571``
        guard + HF-style knobs). Returns (b, t+generated) tokens.

        The decode loop is ONE compiled ``lax.while_loop`` dispatch — no per-token host
        round-trips; EOS termination happens on device. TTFT (``self.ttft``) is measured by
        host-syncing the prefill's first token.

        Unequal-length prompts: pass ``attention_mask`` (HF-style 0/1, must be
        right-padded) or ``prompt_lengths``; positions, the prefill's next-token read and
        the KV append point are then per-sequence (generated tokens overwrite pad slots).
        """
        if kwargs.get("num_beams", 1) != 1:
            raise NotImplementedError("beam search is not supported (reference parity: "
                                      "DeepSpeed inference rejects num_beams > 1)")
        self._activate()
        ids = np.asarray(input_ids)
        b, t = ids.shape
        if max_new_tokens <= 0:
            return ids

        if attention_mask is not None:
            am = np.asarray(attention_mask).astype(bool)
            lens_np = am.sum(axis=1).astype(np.int32)
            expect = np.arange(t)[None, :] < lens_np[:, None]
            if not np.array_equal(am, expect):
                raise ValueError("attention_mask must be right-padded (1s then 0s); "
                                 "left-padded prompts are not supported")
            if (lens_np < 1).any():
                raise ValueError("attention_mask rows must contain at least one valid token")
        elif prompt_lengths is not None:
            lens_np = np.asarray(prompt_lengths, dtype=np.int32)
            if lens_np.shape != (b,) or (lens_np < 1).any() or (lens_np > t).any():
                raise ValueError(f"prompt_lengths must be (b,) in [1, {t}]")
        else:
            lens_np = np.full((b,), t, dtype=np.int32)

        block = int(self.model_config.gen_block_length)
        cap = max(self._config.max_out_tokens, t + max_new_tokens)
        if block:
            cap = -(-cap // block) * block     # a last block is written whole
        # buffer sized by the prompt-independent cap so the decode loop compiles ONCE per
        # (cap, sampling config, batch) — varying prompt lengths only recompile prefill
        gen_cap = cap
        prefill, decode_loop = self._loop_fns(do_sample, temperature, top_k, top_p,
                                              gen_cap)

        caches = init_cache(
            self.model_config, b,
            block_view_rows(self.model_config, cap) if block else cap, dtype=self.dtype)
        lens0 = jnp.asarray(lens_np)
        rng = jax.random.PRNGKey(seed)
        ids_dev = jnp.asarray(ids)
        prefill_key = jax.random.fold_in(rng, 0)
        # Force the argument prep (H2D transfer of ids, cache zero-fill, key folds)
        # to COMPLETE before the TTFT clock starts: one tiny fetch depending on all
        # of them. Otherwise those async dispatches execute inside the timed region
        # and TTFT books host→device transfer latency as prefill time.
        if "touch" not in self._fns:
            self._fns["touch"] = jax.jit(
                lambda i, k, c: i[0, 0] + k[0].astype(i.dtype)
                + sum(leaf[(0,) * leaf.ndim] for leaf in jax.tree_util.tree_leaves(c)
                      ).astype(i.dtype))
        np.asarray(self._fns["touch"](ids_dev, prefill_key, caches))
        t0 = time.perf_counter()
        tok0, caches, lens = prefill(self.params, ids_dev, caches, lens0,
                                     prefill_key)
        tok0_np = np.asarray(tok0)                      # host sync: honest TTFT
        self.ttft = time.perf_counter() - t0

        eos = np.int32(-1 if eos_token_id is None else eos_token_id)
        rows = b if do_sample else max(b, int(self.model_config.greedy_decode_rows or 0))
        if block:
            return self._generate_blocks(ids, lens_np, caches, decode_loop,
                                         max_new_tokens, eos, seed, rows)
        if rows > b:
            # the configuration asks for greedy decodes at a fixed row count
            # (``CausalLMConfig.greedy_decode_rows``, there is why): the rows
            # added hold one pad token each (finished at once where there is
            # an EOS) and are cut off below. Sampling draws one key a batch,
            # so it keeps its own batch.
            pad = rows - b
            tok0 = jnp.pad(tok0, ((0, pad), (0, 0)), constant_values=max(int(eos), 0))
            caches = jax.tree_util.tree_map(
                lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)), caches)
            lens = jnp.pad(lens, (0, pad))
        # cache room is guaranteed: cap >= t + max_new_tokens, and the last appended KV
        # lands at position t + max_new_tokens - 2 < cap
        t1 = time.perf_counter()
        buf, n, _ = decode_loop(self.params, tok0, caches, lens,
                                np.int32(max_new_tokens), eos, rng)
        n = int(n)
        gen = np.asarray(buf)[:b, :n]                   # host sync ends the decode clock
        decode_time = time.perf_counter() - t1
        # TPOT counts only loop-produced tokens (the first token is TTFT's);
        # decode_tps is batch-aggregate throughput of the same window
        if n > 1 and decode_time > 0:
            self.tpot = decode_time / (n - 1)
            self.decode_tps = b * (n - 1) / decode_time
        else:
            self.tpot = None
            self.decode_tps = None
        self._gen_count += 1
        events = [("inference/ttft_ms", self.ttft * 1e3, self._gen_count)]
        if self.tpot is not None:
            events += [("inference/tpot_ms", self.tpot * 1e3, self._gen_count),
                       ("inference/decode_tokens_per_sec", self.decode_tps,
                        self._gen_count)]
        obs_record_events(events)        # registry: independent of monitor
        if self._monitor is not None and getattr(self._monitor, "enabled", False):
            self._monitor.write_events(events)
        return np.concatenate([ids, gen], axis=1)

    def _generate_blocks(self, ids, lens_np, caches, decode_loop, max_new_tokens,
                         eos, seed, rows):
        """``generate`` of a model that generates by diffusion over blocks,
        after the prefill: the prompt's whole blocks are committed, the
        tokens left open the first block, and ONE compiled loop runs the
        forwards the serving chunk runs (``decode_fns._block_body``) until
        every row has its tokens (``self.block_forwards``: how many). The
        token the prefill selected is no token of this model and is dropped."""
        cfg = self.model_config
        b, B = ids.shape[0], cfg.gen_block_length
        whole = lens_np // B * B
        blk = np.zeros((rows, B), np.int32)
        masked = np.ones((rows, B), bool)
        skip = np.zeros(rows, np.int32)
        lens = np.zeros(rows, np.int32)
        remaining = np.zeros(rows, np.int32)
        for i in range(b):
            blk[i], masked[i], skip[i] = open_block(cfg, ids[i, whole[i]:lens_np[i]])
        lens[:b], remaining[:b] = whole, max_new_tokens
        if rows > b:     # rows that hold nothing (``greedy_decode_rows``)
            caches = jax.tree_util.tree_map(
                lambda a: jnp.pad(a, ((0, rows - b),) + ((0, 0),) * (a.ndim - 1)),
                caches)
        t1 = time.perf_counter()
        buf, steps, n = decode_loop(
            self.params, blk, masked, skip, caches, lens, remaining,
            np.full(rows, eos, np.int32), seed + np.arange(rows, dtype=np.int32),
            jax.random.PRNGKey(seed))
        gen = np.asarray(buf)[:b, :max_new_tokens]
        self.block_forwards = int(n)
        if eos >= 0:     # as the token loop: stop at the longest row's end
            gen = gen[:, :max(1, int(np.asarray(steps)[:b].max()))]
        decode_time = time.perf_counter() - t1
        self.tpot = decode_time / max(1, gen.shape[1])
        self.decode_tps = b * gen.shape[1] / decode_time if decode_time > 0 else None
        self._gen_count += 1
        return np.concatenate([ids, gen], axis=1)

    # ------------------------------------------------------------------ checkpoints
    def load_checkpoint(self, ckpt_dir: str, tag: Optional[str] = None):
        """Load params saved by the training engine (orbax; re-sharded onto this mesh) —
        the reference's ``_load_checkpoint:392`` sharded-load path."""
        from ..config.config import CheckpointConfig
        from ..runtime.checkpoint_engine.checkpoint_engine import make_checkpoint_engine
        eng = make_checkpoint_engine(CheckpointConfig())
        if tag is None:
            latest = os.path.join(ckpt_dir, "latest")
            tag = open(latest).read().strip() if os.path.isfile(latest) else None
        path = os.path.join(ckpt_dir, tag) if tag else ckpt_dir
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh_spec.mesh, s), self._param_specs,
            is_leaf=lambda x: isinstance(x, P))
        # checkpoints hold fp params: restore against the pre-quantization template, then
        # re-run placement (cast + optional int8 quantization + sharding)
        restored = eng.load_subtree(os.path.join(path, "state"), "params",
                                    template=self._raw_template, shardings=shardings)
        self.params = self._place_params(restored)
        self._fns.clear()                       # param tree structure may have changed
        logger.info(f"inference params loaded from {path}")
