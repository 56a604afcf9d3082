"""Training engine.

TPU-native re-design of reference ``deepspeed/runtime/engine.py`` (``DeepSpeedEngine:190``).
Where the reference wraps an eager nn.Module with autograd hooks, streams, and flat buffers,
this engine compiles ONE train step under ``jax.jit`` over a named device mesh:

- microbatch gradient accumulation is a ``lax.scan`` inside the step (reference: the
  forward/backward/step loop with ``is_gradient_accumulation_boundary``);
- ZeRO stages are sharding specs on the state pytree (see ``runtime/zero/partition.py``) —
  XLA inserts and overlaps reduce-scatter/all-gather;
- fp16 dynamic loss scaling and overflow-skip run inside the step (reference
  ``fp16/loss_scaler.py`` + ``CheckOverflow``), as data-parallel-free device arithmetic;
- parameters are materialised *already sharded* by jitting ``init`` with output shardings —
  the equivalent of ``zero.Init`` (``zero/partition_parameters.py:539``) without intercepting
  constructors.

The eager-looking ``forward()/backward()/step()`` triple is preserved for source compatibility
with reference training loops; ``train_batch()`` is the fused fast path.
"""

import os
import signal
import threading
import time
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import comm as dist
from ..config.config import DeepSpeedConfig
from ..models.base import Model
from ..ops.adagrad.cpu_adagrad import adagrad
from ..ops.adam.fused_adam import fused_adam
from ..ops.lamb.fused_lamb import fused_lamb
from ..ops.optimizer import Optimizer, from_optax
from ..parallel.mesh import (AXIS_DATA, MeshSpec, get_global_mesh,
                             set_global_mesh)
from ..observability import profiler as obs_profiler
from ..observability.metrics import record_events as obs_record_events
from ..observability.trace import CAT_TRAIN, get_tracer, scope
from ..parallel.overlap import resolve_overlap_config, set_overlap_config
from ..utils.comms_logging import (collective_spans, record_collective,
                                   spans_overlap_ratio, spans_total_bytes)
from ..utils.device import PEAKS
from ..utils.fault_injection import fault_point
from ..utils.logging import log_dist, logger
from ..utils.timer import (BACKWARD_GLOBAL_TIMER, FORWARD_GLOBAL_TIMER, STEP_GLOBAL_TIMER,
                           SynchronizedWallClockTimer, ThroughputTimer, TRAIN_BATCH_TIMER)
from .checkpoint_engine.checkpoint_engine import (
    CheckpointCorruptionError, LATEST_FILE, find_latest_committed_tag,
    is_committed_tag, make_checkpoint_engine, validate_manifest,
    write_latest_pointer)
from .dataloader import DeepSpeedDataLoader, RepeatingLoader
from .fp16.loss_scaler import DynamicLossScaler, LossScaleState, create_loss_scaler
from .lr_schedules import get_lr_scheduler
from .utils import (clip_by_global_norm, count_parameters, global_norm, tree_cast,
                    tree_zeros_like)
from .zero.partition import (grad_accum_specs, optimizer_state_specs, param_specs,
                             to_shardings)


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    scaler: LossScaleState
    global_step: jnp.ndarray
    skipped_steps: jnp.ndarray


def _batch_tokens(batch) -> int:
    """Modeled token count of one global batch: element count of the leading
    array leaf (the input ids for LM batches; labels/masks share the shape)."""
    try:
        leaves = jax.tree_util.tree_leaves(batch)
        return int(np.prod(np.shape(leaves[0]))) if leaves else 0
    except Exception:                                  # pragma: no cover
        return 0


class DeepSpeedEngine:
    """See module docstring. Public surface mirrors reference ``DeepSpeedEngine``."""

    def __init__(self, args=None, model: Optional[Model] = None, optimizer=None,
                 model_parameters=None, training_data=None, lr_scheduler=None, mpu=None,
                 collate_fn=None, config=None, dont_change_device: bool = False,
                 mesh_spec: Optional[MeshSpec] = None, seed: int = 42):
        if not (model is not None):
            raise AssertionError("deepspeed_tpu.initialize requires a Model")
        if not (isinstance(model, Model)):
            raise AssertionError("model must be deepspeed_tpu.models.Model (see models.base.from_flax)")
        with get_tracer().phase("setup.engine_init"):
            self._init(args, model, optimizer, training_data, lr_scheduler,
                       mpu, collate_fn, config, mesh_spec, seed)

    def _init(self, args, model, optimizer, training_data, lr_scheduler, mpu,
              collate_fn, config, mesh_spec, seed):
        dist.init_distributed()
        self.module = model
        self.collate_fn = collate_fn
        self.mpu = mpu
        self.args = args
        self._seed = seed

        # ---- config + mesh (reference _configure_with_arguments:990) ------------
        self._config = (config if isinstance(config, DeepSpeedConfig)
                        else DeepSpeedConfig(config))
        self.zero_stage = self._config.zero_config.stage
        with get_tracer().phase("setup.mesh"):
            self.mesh_spec = mesh_spec or MeshSpec.from_config(
                self._config.mesh, zero_stage=self.zero_stage)
            set_global_mesh(self.mesh_spec)
        self._config.resolve_batch_config(self.mesh_spec.dp_world_size)
        # comm-compute overlap: installed like the mesh so model traces this
        # engine initiates see its setting (chunked TP matmuls / MoE a2a
        # pipeline); the quantized DP grad sync is gated separately below
        self.comm_overlap = resolve_overlap_config(self._config.comm_overlap)
        set_overlap_config(self.comm_overlap)
        # this engine's own trace-time span snapshot (the module accumulator
        # is process-global; other engines' traces land in it too)
        self._comm_spans = {}

        # ---- precision policy ---------------------------------------------------
        if self._config.fp16.enabled:
            self.compute_dtype = jnp.float16
        elif self._config.bf16.enabled:
            self.compute_dtype = jnp.bfloat16
        else:
            self.compute_dtype = jnp.float32
        self.loss_scaler, scaler_state0 = create_loss_scaler(self._config.fp16)

        # ---- ZeRO-Offload gate (reference stage_1_and_2.py:130 cpu_offload) -----
        off_cfg = self._config.zero_config.offload_optimizer
        self.offload_enabled = bool(off_cfg is not None and
                                    off_cfg.device not in (None, "none"))
        self._offload_tier = None
        # multi-process runs use per-process partitioned masters (see
        # zero/offload.py OffloadOptimizerTier._partitioned) — no world-size gate
        # ---- ZeRO-3 parameter offload (reference partition_parameters.py:539,
        # partitioned_param_coordinator.py:239) — host-resident params streamed per
        # model segment; implies the optimizer tier (host masters own the state)
        op_cfg = self._config.zero_config.offload_param
        self.param_offload_enabled = bool(op_cfg is not None and
                                          op_cfg.device not in (None, "none"))
        self._param_offload = None
        if self.param_offload_enabled:
            if self.zero_stage != 3:
                raise ValueError("zero_optimization.offload_param requires stage 3 "
                                 f"(got stage {self.zero_stage})")
            if model.segments is None:
                raise ValueError(
                    "offload_param requires a segmented model (Model.segments — see "
                    "models.causal_lm.causal_lm_segments); this model has none")
            # multi-process runs partition masters per process along the gradient
            # layout (ParamOffloadCoordinator._partitioned) — no world-size gate
            self.offload_enabled = False  # coordinator owns the optimizer tier
        if self._config.sparse_gradients_enabled:
            logger.warning(
                "sparse_gradients is a no-op on TPU: XLA gradients (including "
                "embedding grads) are dense by construction; the flag is accepted "
                "for config compatibility only")

        # ---- quantized DP grad sync (needs the offload gates above) -------------
        self._quantized_dp = self._quantized_dp_regime()

        # ---- optimizer (reference _configure_optimizer:1261) --------------------
        self.optimizer = self._configure_optimizer(optimizer)
        self.lr_scheduler = self._configure_lr_scheduler(lr_scheduler)

        # ---- sharded state materialisation (zero.Init equivalent) ---------------
        self._build_state(scaler_state0, seed)

        # ---- data ----------------------------------------------------------------
        self.training_dataloader = self._configure_dataloader(training_data)

        # ---- observability -------------------------------------------------------
        self.timers = SynchronizedWallClockTimer()
        self.tput_timer = ThroughputTimer(
            batch_size=self.train_batch_size(),
            steps_per_output=self._config.steps_per_print)
        if model.flops_per_sample:
            self.tput_timer.flops_per_sample = model.flops_per_sample
        self.monitor = self._configure_monitor()
        self.checkpoint_engine = make_checkpoint_engine(self._config.checkpoint_config)
        self.curriculum_scheduler = self._configure_curriculum()
        pld_cfg = self._config.progressive_layer_drop
        self.progressive_layer_drop = None
        self._pld_in_loss = False
        if pld_cfg.get("enabled", False):
            from .progressive_layer_drop import ProgressiveLayerDrop
            self.progressive_layer_drop = ProgressiveLayerDrop(
                theta=pld_cfg.get("theta", 0.5), gamma=pld_cfg.get("gamma", 0.001))
            # theta reaches the compiled step only if the model opts in by accepting
            # a pld_theta kwarg in its loss_fn (and applies layer_drop with it)
            import inspect
            self._pld_in_loss = "pld_theta" in inspect.signature(
                self.module.loss_fn).parameters
            if not self._pld_in_loss:
                logger.warning(
                    "progressive_layer_drop enabled but the model's loss_fn does "
                    "not accept pld_theta — theta is scheduled but layers are NOT "
                    "dropped (wrap blocks with "
                    "runtime.progressive_layer_drop.layer_drop and add the kwarg)")

        # ---- step bookkeeping ----------------------------------------------------
        self.micro_steps = 0
        self._host_steps = 0   # host mirror of state.global_step (see train_batch)
        self._grad_acc = None
        self._cached_grads = None
        self._cached_loss = None
        self._last_metrics: Dict[str, Any] = {}
        self._fns: Dict[str, Any] = {}

        n_params = (self._param_offload.total_params if self.param_offload_enabled
                    else count_parameters(self.state.params))
        log_dist(
            f"engine ready: model={model.name} params={n_params:,} "
            f"zero_stage={self.zero_stage} dtype={self.compute_dtype.__name__} "
            f"mesh={self.mesh_spec.axis_sizes} "
            f"batch={self.train_batch_size()}(micro={self.train_micro_batch_size_per_gpu()}"
            f"×gas={self.gradient_accumulation_steps()}×dp={self.mesh_spec.dp_world_size})",
            ranks=[0])

    # ------------------------------------------------------------------ config
    def _parse_optimizer_config(self) -> Dict[str, Any]:
        """Normalised optimizer hyperparams from the config block (shared by the in-graph
        and the host-offloaded paths); parsed once and cached."""
        cached = getattr(self, "_opt_cfg_cache", None)
        if cached is not None:
            return cached
        name = self._config.optimizer_name or "adam"
        params = dict(self._config.optimizer_params)
        self._base_lr = params.pop("lr", 1e-3)
        out = {
            "name": name,
            "betas": tuple(params.pop("betas", (0.9, 0.999))),
            "eps": params.pop("eps", 1e-10 if name == "adagrad" else 1e-8),
            "weight_decay": params.pop("weight_decay", 0.0),
            # torch-style flag accepted in reference adam params
            "adam_w_mode": params.pop("adam_w_mode", name == "adamw") or name == "adamw",
            "bias_correction": params.pop("bias_correction", True),
            "max_coeff": params.pop("max_coeff", 10.0),
            "min_coeff": params.pop("min_coeff", 0.01),
        }
        params.pop("torch_adam", None)
        out["extra"] = params  # optimizer-specific keys (freeze_step, ...)
        self._opt_cfg_cache = out
        return out

    def _configure_optimizer(self, optimizer) -> Optional[Optimizer]:
        if optimizer is not None:
            if self.offload_enabled or self.param_offload_enabled:
                raise ValueError(
                    "zero_optimization offload tiers require a config-declared "
                    "optimizer (adam/adamw/adagrad), not a user optimizer object")
            if isinstance(optimizer, Optimizer):
                return optimizer
            if hasattr(optimizer, "init") and hasattr(optimizer, "update"):
                return from_optax(optimizer)
            raise TypeError(f"Unsupported optimizer object: {optimizer!r}")
        oc = self._parse_optimizer_config()
        name = oc["name"]
        if self.offload_enabled or self.param_offload_enabled:
            if name not in ("adam", "adamw", "fusedadam", "adagrad"):
                raise ValueError(f"offload tiers support adam/adamw/adagrad, "
                                 f"got {name!r}")
            return None  # host tier built in _build_state; no in-graph opt state
        if name in ("adam", "adamw", "fusedadam"):
            return fused_adam(betas=oc["betas"], eps=oc["eps"],
                              weight_decay=oc["weight_decay"],
                              adam_w_mode=oc["adam_w_mode"],
                              bias_correction=oc["bias_correction"])
        if name in ("lamb", "fusedlamb"):
            return fused_lamb(betas=oc["betas"], eps=oc["eps"],
                              weight_decay=oc["weight_decay"],
                              max_coeff=oc["max_coeff"], min_coeff=oc["min_coeff"])
        if name in ("onebitadam", "zerooneadam", "onebitlamb"):
            from .fp16.onebit import onebit_adam, onebit_lamb, zero_one_adam
            extra = oc["extra"]
            if name == "onebitadam":
                return onebit_adam(betas=oc["betas"], eps=oc["eps"],
                                   weight_decay=oc["weight_decay"],
                                   freeze_step=extra.get("freeze_step", 100),
                                   adam_w_mode=oc["adam_w_mode"])
            if name == "onebitlamb":
                return onebit_lamb(betas=oc["betas"], eps=oc["eps"],
                                   weight_decay=oc["weight_decay"],
                                   freeze_step=extra.get("freeze_step", 100),
                                   max_coeff=oc["max_coeff"],
                                   min_coeff=oc["min_coeff"])
            return zero_one_adam(
                betas=oc["betas"], eps=oc["eps"],
                weight_decay=oc["weight_decay"],
                var_freeze_step=extra.get("var_freeze_step", 100000),
                var_update_scaler=extra.get("var_update_scaler", 16),
                adam_w_mode=oc["adam_w_mode"])
        if name == "adagrad":
            return adagrad(eps=oc["eps"], weight_decay=oc["weight_decay"])
        raise ValueError(f"Unknown optimizer {name!r} "
                         f"(supported: adam, adamw, lamb, adagrad, or pass an Optimizer)")

    def _configure_lr_scheduler(self, lr_scheduler):
        if lr_scheduler is not None:
            return lr_scheduler
        if self._config.scheduler_name:
            return get_lr_scheduler(self._config.scheduler_name,
                                    self._config.scheduler_params)
        return None

    def _configure_monitor(self):
        from ..monitor.monitor import MonitorMaster
        monitor = MonitorMaster(self._config.monitor_config)
        if self._config.monitor_config.enabled and not monitor.enabled \
                and dist.get_rank() == 0:
            log_dist("monitor enabled in config but no backend initialised "
                     "(see warnings above)", ranks=[0])
        return monitor

    def _configure_curriculum(self):
        """Legacy ``curriculum_learning`` block and the data-efficiency
        ``data_sampling.curriculum_learning`` block both produce one scheduler
        (reference ``engine.py`` curriculum_scheduler_legacy + data-efficiency wiring).
        The difficulty value is host state the data pipeline reads; ``train_batch``
        advances it each step."""
        cfg = None
        if self._config.curriculum_enabled_legacy:
            cfg = {k: v for k, v in self._config.curriculum_params_legacy.items()
                   if k != "enabled"}
        else:
            de = self._config.data_efficiency_config or {}
            cl = de.get("data_sampling", {}).get("curriculum_learning", {})
            if cl.get("enabled", False):
                cfg = {k: v for k, v in cl.items() if k != "enabled"}
        if cfg is None:
            return None
        from .data_pipeline.curriculum_scheduler import CurriculumScheduler
        return CurriculumScheduler(cfg)

    def get_data_difficulty(self) -> Optional[int]:
        """Current curriculum difficulty (None when curriculum is off)."""
        if self.curriculum_scheduler is None:
            return None
        return self.curriculum_scheduler.get_current_difficulty()

    def _configure_dataloader(self, training_data):
        if training_data is None:
            return None
        if hasattr(training_data, "__iter__") and not hasattr(training_data, "__getitem__"):
            return RepeatingLoader(training_data)
        local_batch = (self.train_micro_batch_size_per_gpu() *
                       max(1, self.mesh_spec.dp_world_size // dist.get_world_size()))
        return DeepSpeedDataLoader(
            training_data, batch_size=local_batch,
            num_replicas=dist.get_world_size(), rank=dist.get_rank(),
            collate_fn=self.collate_fn, drop_last=self._config.dataloader_drop_last)

    # ------------------------------------------------------------ state build
    def _build_state(self, scaler_state0: LossScaleState, seed: int):
        mesh = self.mesh_spec
        rng = jax.random.PRNGKey(seed)
        self._base_rng = rng

        if self.param_offload_enabled:
            self._build_param_offload_state(scaler_state0, rng)
            return

        abstract_params = jax.eval_shape(self.module.init_fn, rng)
        # compression scheduler (reference init_compression wiring in engine __init__)
        self._compression = None
        if self._config.compression_config:
            from ..compression.compress import init_compression
            sched = init_compression(abstract_params,
                                     {"compression_training":
                                      self._config.compression_config})
            if sched.active:
                self._compression = sched
        persist = self._config.zero_config.param_persistence_threshold
        self._param_spec_tree = param_specs(abstract_params, mesh, self.zero_stage,
                                            base_specs=self.module.param_specs,
                                            persistence_threshold=persist)
        self._param_shardings = to_shardings(self._param_spec_tree, mesh)
        # zero.Init equivalent: init jitted with sharded outputs — parameters are born
        # partitioned, never materialised replicated (partition_parameters.py:539).
        with get_tracer().phase("setup.init_params"):
            params = jax.jit(self.module.init_fn,
                             out_shardings=self._param_shardings)(rng)

        self._grad_spec_tree = grad_accum_specs(abstract_params, mesh, self.zero_stage,
                                                param_base_specs=self.module.param_specs)
        self._grad_shardings = to_shardings(self._grad_spec_tree, mesh)

        if self.offload_enabled:
            # Host tier owns fp32 masters + moments; HBM keeps only compute-dtype params.
            from .zero.offload import OffloadOptimizerTier
            oc = self._parse_optimizer_config()
            kind = "adagrad" if oc["name"] == "adagrad" else "adam"
            off_cfg = self._config.zero_config.offload_optimizer
            nvme_path = None
            if off_cfg.device == "nvme":
                if not off_cfg.nvme_path:
                    raise ValueError(
                        "offload_optimizer.device=nvme requires nvme_path")
                if kind != "adam":
                    raise ValueError("nvme offload supports adam/adamw only")
                nvme_path = off_cfg.nvme_path
            aio = self._config.aio_config
            self._offload_tier = OffloadOptimizerTier(
                params, self._param_shardings, self.compute_dtype, kind=kind,
                betas=oc["betas"], eps=oc["eps"], weight_decay=oc["weight_decay"],
                adam_w_mode=oc["adam_w_mode"], bias_correction=oc["bias_correction"],
                nvme_path=nvme_path,
                aio_config={"thread_count": aio.thread_count,
                            "block_size": aio.block_size,
                            "queue_depth": aio.queue_depth},
                grad_shardings=self._grad_shardings)
            del params
            params = self._offload_tier.initial_device_params()
            opt_state = ()
            self._opt_shardings = ()
        else:
            abstract_opt = jax.eval_shape(self.optimizer.init, abstract_params)
            self._opt_spec_tree = optimizer_state_specs(
                abstract_opt, mesh, self.zero_stage,
                abstract_params=abstract_params, param_spec_tree=self._param_spec_tree)
            self._opt_shardings = to_shardings(self._opt_spec_tree, mesh)
            with get_tracer().phase("setup.init_optimizer"):
                opt_state = jax.jit(self.optimizer.init,
                                    out_shardings=self._opt_shardings)(params)

        repl = mesh.replicated()
        self._scaler_shardings = jax.tree_util.tree_map(lambda _: repl, scaler_state0)
        with get_tracer().phase("setup.place_state"):
            self.state = TrainState(
                params=params,
                opt_state=opt_state,
                scaler=jax.device_put(scaler_state0, repl),
                global_step=jax.device_put(jnp.int32(0), repl),
                skipped_steps=jax.device_put(jnp.int32(0), repl),
            )
        self._state_shardings = TrainState(
            params=self._param_shardings,
            opt_state=self._opt_shardings,
            scaler=self._scaler_shardings,
            global_step=repl,
            skipped_steps=repl,
        )

    def _build_param_offload_state(self, scaler_state0: LossScaleState, rng):
        """ZeRO-3 param offload: no resident device state at all — the coordinator owns
        host masters, the optimizer, and the loss scaler. ``self.state`` is None in this
        mode; step/scale bookkeeping lives on host."""
        from .zero.param_offload import ParamOffloadCoordinator
        # compression scheduler from ABSTRACT params (no resident tree exists)
        self._compression = None
        if self._config.compression_config:
            from ..compression.compress import init_compression
            abstract_params = jax.eval_shape(self.module.init_fn, rng)
            sched = init_compression(abstract_params,
                                     {"compression_training":
                                      self._config.compression_config})
            if sched.active:
                self._compression = sched
        # QAT composes via the coordinator's push transform: every streamed key is
        # quantized on device right after its H2D push; grads w.r.t. the quantized
        # values update the fp32 masters (straight-through estimator — same
        # numerics as the resident engine's in-loss qat)
        qat_fn = None
        if self._compression is not None:
            comp = self._compression

            def qat_fn(key, tree, step):
                # per-key mini-tree {key: subtree} reproduces the full tree's leaf
                # paths, so the scheduler's path-matched plans apply identically
                return comp.qat({key: tree}, jnp.int32(step))[key]
        oc = self._parse_optimizer_config()
        kind = "adagrad" if oc["name"] == "adagrad" else "adam"
        op_cfg = self._config.zero_config.offload_param
        off_opt = self._config.zero_config.offload_optimizer
        nvme_path = None
        nvme_param_path = None
        # full ZeRO-Infinity: parameter masters (+ gradient accumulators) stream
        # from NVMe per model segment (reference partitioned_param_swapper.py:35);
        # implies the moment store on disk too
        if op_cfg.device == "nvme":
            if not op_cfg.nvme_path:
                raise ValueError("offload_param device=nvme requires nvme_path")
            if kind != "adam":
                raise ValueError("nvme offload supports adam/adamw only")
            nvme_param_path = op_cfg.nvme_path
        if off_opt is not None and off_opt.device == "nvme":
            if not off_opt.nvme_path:
                raise ValueError("offload_optimizer device=nvme requires nvme_path")
            if kind != "adam":
                raise ValueError("nvme offload supports adam/adamw only")
            nvme_path = off_opt.nvme_path
        aio = self._config.aio_config
        mesh = self.mesh_spec if self.mesh_spec.mesh.size > 1 else None
        self._param_offload = ParamOffloadCoordinator(
            self.module.segments, rng, self.compute_dtype, kind=kind,
            betas=oc["betas"], eps=oc["eps"], weight_decay=oc["weight_decay"],
            adam_w_mode=oc["adam_w_mode"], bias_correction=oc["bias_correction"],
            gradient_clipping=self._config.gradient_clipping or 0.0,
            fp16_enabled=self._config.fp16.enabled,
            loss_scaler=self.loss_scaler, scaler_state=scaler_state0,
            qat_fn=qat_fn,
            nvme_path=nvme_path, nvme_param_path=nvme_param_path,
            aio_config={"thread_count": aio.thread_count,
                        "block_size": aio.block_size,
                        "queue_depth": aio.queue_depth},
            mesh=mesh)
        self.state = None
        self._state_shardings = None

    # --------------------------------------------------------------- internals
    def _loss_and_scaled_grads(self, params, scale, batch, rng, step=None,
                               pld_theta=None):
        """value_and_grad in compute dtype against fp32 masters; loss scaled pre-diff.
        ``step`` (traced) gates the compression scheduler's QAT transforms;
        ``pld_theta`` (traced) reaches opt-in models (see ``_pld_in_loss``)."""

        def f(p):
            with scope("param.cast"):
                p = tree_cast(p, self.compute_dtype)
                if self._compression is not None and step is not None:
                    p = self._compression.qat(p, step)
            kwargs = {}
            if self._pld_in_loss and pld_theta is not None:
                kwargs["pld_theta"] = pld_theta
            loss = self.module.loss_fn(p, batch, rng, **kwargs)
            if isinstance(loss, tuple):
                loss = loss[0]
            with scope("loss"):
                return loss * scale.astype(loss.dtype), loss

        (scaled, loss), grads = jax.value_and_grad(f, has_aux=True)(params)
        return loss, grads

    def _unscale_clip_and_check(self, state: TrainState, grads_acc, n_micro):
        """Shared device-side tail of both update paths: unscale by loss-scale × n_micro,
        prescale, global-norm overflow check, clip. Returns (grads, norm, overflow)."""
        scale = state.scaler.cur_scale
        with scope("grad.norm_clip"):
            grads = jax.tree_util.tree_map(
                lambda g: g / (scale * np.float32(n_micro)), grads_acc)
            if self._config.prescale_gradients:
                grads = jax.tree_util.tree_map(
                    lambda g: g / np.float32(self._config.gradient_predivide_factor),
                    grads)
            norm = global_norm(grads)
            if self._config.fp16.enabled:
                overflow = jnp.logical_not(jnp.isfinite(norm))
            else:
                overflow = jnp.array(False)
            clip = self._config.gradient_clipping
            if clip and clip > 0:
                safe_norm = jnp.where(jnp.isfinite(norm), norm, 1.0)
                grads = clip_by_global_norm(grads, clip, norm=safe_norm)
        return grads, norm, overflow

    def _apply_update(self, state: TrainState, grads_acc, lr, n_micro):
        """Unscale, clip, overflow-guard, optimizer update, scaler update."""
        scale = state.scaler.cur_scale
        grads, norm, overflow = self._unscale_clip_and_check(state, grads_acc, n_micro)
        with scope("optimizer.update"):
            new_params, new_opt = self.optimizer.update(
                grads, state.opt_state, state.params, jnp.float32(lr))
            keep_old = lambda old, new: jnp.where(overflow, old, new)
            new_params = jax.tree_util.tree_map(keep_old, state.params, new_params)
            new_opt = jax.tree_util.tree_map(keep_old, state.opt_state, new_opt)
            new_scaler = self.loss_scaler.update(state.scaler, overflow)
        new_state = TrainState(
            params=new_params,
            opt_state=new_opt,
            scaler=new_scaler,
            global_step=state.global_step + 1,
            skipped_steps=state.skipped_steps + overflow.astype(jnp.int32),
        )
        metrics = {"grad_norm": norm, "overflow": overflow, "loss_scale": scale}
        return new_state, metrics

    def _finalize_grads_offload(self, state: TrainState, grads_acc, n_micro):
        """Offload-mode device-side tail: unscale, clip, overflow-check, scaler update.
        The optimizer update itself happens on host (see ``zero/offload.py``)."""
        scale = state.scaler.cur_scale
        grads, norm, overflow = self._unscale_clip_and_check(state, grads_acc, n_micro)
        new_scaler = self.loss_scaler.update(state.scaler, overflow)
        new_state = state._replace(
            scaler=new_scaler,
            global_step=state.global_step + 1,
            skipped_steps=state.skipped_steps + overflow.astype(jnp.int32))
        # D2H transfer dtype: bf16 halves the bytes and keeps fp32's exponent range, so
        # it is safe for unscaled grads; fp16's 5-bit exponent would flush exactly the
        # small-gradient range loss scaling exists to protect, so fp16 runs ship fp32.
        transfer_dtype = jnp.bfloat16 if self.compute_dtype == jnp.bfloat16 \
            else jnp.float32
        grads_out = tree_cast(grads, transfer_dtype)
        metrics = {"grad_norm": norm, "overflow": overflow, "loss_scale": scale}
        return new_state, grads_out, metrics

    def _build_train_step(self):
        """Fused whole-batch step: scan over gas microbatches, then update."""
        if self._quantized_dp:
            return self._build_train_step_quantized()
        gas = self.gradient_accumulation_steps()
        grad_shardings = self._grad_shardings

        def accumulate(state: TrainState, batch, pld_theta):
            step_rng = jax.random.fold_in(self._base_rng, state.global_step)

            def micro(acc, xs):
                mb, idx = xs
                rng = jax.random.fold_in(step_rng, idx)
                loss, grads = self._loss_and_scaled_grads(
                    state.params, state.scaler.cur_scale, mb, rng,
                    step=state.global_step, pld_theta=pld_theta)
                with scope("grad.accum"):
                    acc = jax.tree_util.tree_map(jnp.add, acc, grads)
                    acc = jax.lax.with_sharding_constraint(acc, grad_shardings)
                return acc, loss

            with scope("grad.accum"):
                acc0 = jax.lax.with_sharding_constraint(
                    tree_zeros_like(state.params, jnp.float32), grad_shardings)
            return jax.lax.scan(micro, acc0, (batch, jnp.arange(gas)))

        if self.offload_enabled:
            def train_step_offload(state: TrainState, batch, pld_theta):
                acc, losses = accumulate(state, batch, pld_theta)
                new_state, grads_out, metrics = self._finalize_grads_offload(
                    state, acc, gas)
                metrics["loss"] = jnp.mean(losses)
                return new_state, grads_out, metrics

            self._fns["train_step"] = jax.jit(
                train_step_offload, donate_argnums=(0,),
                out_shardings=(self._state_shardings, self._grad_shardings, None))
            return

        def train_step(state: TrainState, batch, lr, pld_theta):
            acc, losses = accumulate(state, batch, pld_theta)
            new_state, metrics = self._apply_update(state, acc, lr, gas)
            metrics["loss"] = jnp.mean(losses)
            return new_state, metrics

        jitted = jax.jit(train_step, donate_argnums=(0,),
                         out_shardings=(self._state_shardings, None))
        self._fns["train_step"] = jitted

    # --------------------------------------------- quantized DP gradient sync
    def _quantized_dp_regime(self) -> bool:
        """EQuARX-style int8 DP grad sync is wired for the plain-DP regime only
        (the same regime the reference's 1-bit optimizers target: replicated
        params, gradient allreduce over the data axis). Anything else keeps
        the full-precision XLA psum; a config that asks for more warns."""
        co = self.comm_overlap
        if not (co.enabled and co.quantized_allreduce):
            return False
        mesh = self.mesh_spec
        blockers = []
        if self.zero_stage != 0:
            blockers.append(f"zero_stage={self.zero_stage} (grads are sharded, "
                            "not replicated — XLA's reduce-scatter already "
                            "moves 1/W of the volume)")
        if self.offload_enabled or self.param_offload_enabled:
            blockers.append("offload tiers own the gradient pipeline")
        if mesh.size(AXIS_DATA) <= 1:
            blockers.append("no data axis > 1")
        others = [ax for ax in ("pipe", "fsdp", "expert", "seq", "tensor")
                  if mesh.size(ax) > 1]
        if others:
            blockers.append(f"non-DP mesh axes active: {others}")
        if blockers:
            logger.warning("comm_overlap.quantized_allreduce requested but "
                           "disabled: " + "; ".join(blockers))
            return False
        return True

    def _init_qar_residual(self):
        """Per-worker error-feedback residual: ``(W, *param.shape)`` fp32,
        sharded over the data axis (one fp32 copy per device). Optimizer-state
        adjacent but deliberately NOT in ``TrainState`` (and not checkpointed):
        restores reset it to zero, which costs one step of feedback — benign
        (documented in docs/FEATURES.md)."""
        mesh = self.mesh_spec
        W = mesh.size(AXIS_DATA)

        def shard_for(leaf):
            return mesh.sharding(P(AXIS_DATA, *([None] * leaf.ndim)))

        shardings = jax.tree_util.tree_map(shard_for, self.state.params)

        def zeros():
            return jax.tree_util.tree_map(
                lambda p: jnp.zeros((W,) + p.shape, jnp.float32),
                self.state.params)

        return jax.jit(zeros, out_shardings=shardings)(), shardings

    def _build_train_step_quantized(self):
        """Fused step with int8 blockwise-scaled DP gradient sync.

        The microbatch scan + grad computation runs INSIDE a ``shard_map``
        manual over the data axis, so gradients stay LOCAL (per-shard batch
        mean) instead of being full-precision-psummed by GSPMD; the exchange
        is ``comm.compressed.quantized_allreduce`` — int8 payload + per-block
        scales + error feedback, ~3.9x less wire volume. Semantics: the synced
        gradient is the mean of shard means (exactly torch-DDP/reference DP
        averaging; equal to the global mean when shards hold equal valid-token
        counts).
        """
        from ..comm.compressed import quantized_allreduce
        from ..utils.jax_compat import shard_map
        gas = self.gradient_accumulation_steps()
        mesh = self.mesh_spec
        W = mesh.size(AXIS_DATA)
        block = self.comm_overlap.quant_block
        self._qar_residual, self._qar_shardings = self._init_qar_residual()
        n_elems = sum(int(np.prod(l.shape))
                      for l in jax.tree_util.tree_leaves(self.state.params))
        # per-worker on-wire: two 8-bit phases (a2a reduce-scatter + requantized
        # gather), each (W-1)/W of payload + block scales
        record_collective(
            "dp.grad_sync", "quantized_allreduce",
            2 * (W - 1) * (n_elems + 4 * ((n_elems + block - 1) // block)) // W,
            W, overlapped=False)

        def local_sync(params, scale, batch, step_key, step, theta, residual):
            # trace-time: hide the global mesh so model internals take their
            # local (non-GSPMD, non-shard_map) paths inside this manual region
            prev = get_global_mesh()
            set_global_mesh(None)
            try:
                # dropout/gating noise must stay i.i.d. across the batch: the
                # baseline path draws one mask over the GLOBAL batch, so the
                # local draw here must be per-shard-keyed or every DP shard
                # repeats the same mask at local-batch shape
                shard_key = jax.random.fold_in(
                    step_key, jax.lax.axis_index(AXIS_DATA))

                def micro(acc, xs):
                    mb, idx = xs
                    rng = jax.random.fold_in(shard_key, idx)
                    loss, grads = self._loss_and_scaled_grads(
                        params, scale, mb, rng, step=step, pld_theta=theta)
                    return jax.tree_util.tree_map(jnp.add, acc, grads), loss

                acc0 = tree_zeros_like(params, jnp.float32)
                acc, losses = jax.lax.scan(micro, acc0, (batch, jnp.arange(gas)))
            finally:
                set_global_mesh(prev)
            denom = scale * np.float32(gas)
            if self._config.prescale_gradients:
                denom = denom * np.float32(self._config.gradient_predivide_factor)
            g = jax.tree_util.tree_map(lambda v: v / denom, acc)
            flat_g, treedef = jax.tree_util.tree_flatten(g)
            flat_r = jax.tree_util.tree_leaves(residual)
            finite = jnp.array(True)
            for leaf in flat_g:
                finite = jnp.logical_and(finite, jnp.all(jnp.isfinite(leaf)))
            # One fused collective over the concatenated gradient: per-leaf
            # dispatch would pad every bias/LN leaf up to block*W and issue
            # hundreds of tiny sequential collectives — more wire than the
            # fp32 ring it replaces. Concatenating amortizes the pad to a
            # single <= block*W tail and keeps the 3.9x volume win.
            sizes = [int(np.prod(l.shape)) for l in flat_g]
            bounds = np.cumsum([0] + sizes)
            g_cat = jnp.concatenate([l.reshape(-1) for l in flat_g])
            r_cat = jnp.concatenate([rl[0].reshape(-1) for rl in flat_r])
            s_cat, res_cat = quantized_allreduce(
                g_cat, r_cat, AXIS_DATA, block=block)
            synced = [s_cat[bounds[i]:bounds[i + 1]].reshape(l.shape)
                      for i, l in enumerate(flat_g)]
            new_res = [res_cat[bounds[i]:bounds[i + 1]].reshape(
                           (1,) + l.shape)
                       for i, l in enumerate(flat_g)]
            g_sync = jax.tree_util.tree_unflatten(treedef, synced)
            residual_out = jax.tree_util.tree_unflatten(treedef, new_res)
            loss_mean = jax.lax.psum(jnp.mean(losses), AXIS_DATA) / np.float32(W)
            overflow = jax.lax.pmax(
                jnp.logical_not(finite).astype(jnp.int32), AXIS_DATA)
            return g_sync, residual_out, loss_mean, overflow

        repl = P()

        def train_step(state: TrainState, batch, lr, theta, residual):
            params_spec = jax.tree_util.tree_map(lambda _: repl, state.params)
            batch_spec = jax.tree_util.tree_map(
                lambda leaf: P(None, AXIS_DATA, *([None] * (leaf.ndim - 2))),
                batch)
            res_spec = jax.tree_util.tree_map(
                lambda leaf: P(AXIS_DATA, *([None] * (leaf.ndim - 1))), residual)
            step_key = jax.random.fold_in(self._base_rng, state.global_step)
            mapped = shard_map(
                local_sync, mesh=mesh.mesh, axis_names={AXIS_DATA},
                in_specs=(params_spec, repl, batch_spec, repl, repl, repl,
                          res_spec),
                out_specs=(params_spec, res_spec, repl, repl),
                check_vma=False)
            g_sync, new_residual, loss_mean, overflow_q = mapped(
                state.params, state.scaler.cur_scale, batch, step_key,
                state.global_step, theta, residual)
            # tail matches _apply_update, with grads already unscaled/averaged.
            # Unlike the full-precision path (where a NaN grad propagates into
            # params and is VISIBLE), quantized_allreduce zeroes non-finite
            # values before the int8 cast — so the overflow flag must gate the
            # update at every precision, not just under fp16 loss scaling, or
            # a bf16/fp32 overflow step would be silently applied as zeros.
            with scope("grad.norm_clip"):
                norm = global_norm(g_sync)
                overflow = jnp.logical_or(overflow_q > 0,
                                          jnp.logical_not(jnp.isfinite(norm)))
                clip = self._config.gradient_clipping
                if clip and clip > 0:
                    safe_norm = jnp.where(jnp.isfinite(norm), norm, 1.0)
                    g_sync = clip_by_global_norm(g_sync, clip, norm=safe_norm)
            with scope("optimizer.update"):
                new_params, new_opt = self.optimizer.update(
                    g_sync, state.opt_state, state.params, jnp.float32(lr))
                keep_old = lambda old, new: jnp.where(overflow, old, new)
                new_params = jax.tree_util.tree_map(keep_old, state.params, new_params)
                new_opt = jax.tree_util.tree_map(keep_old, state.opt_state, new_opt)
                # EF contract assumes the transmitted grad was CONSUMED; a skipped
                # step discards it, so committing the new residual would inject a
                # phantom correction into step k+1 — keep the pre-step residual
                new_residual = jax.tree_util.tree_map(keep_old, residual,
                                                      new_residual)
            new_state = TrainState(
                params=new_params, opt_state=new_opt,
                scaler=self.loss_scaler.update(state.scaler, overflow),
                global_step=state.global_step + 1,
                skipped_steps=state.skipped_steps + overflow.astype(jnp.int32))
            metrics = {"loss": loss_mean, "grad_norm": norm,
                       "overflow": overflow,
                       "loss_scale": state.scaler.cur_scale}
            return new_state, metrics, new_residual

        self._fns["train_step"] = jax.jit(
            train_step, donate_argnums=(0, 4),
            out_shardings=(self._state_shardings, None, self._qar_shardings))

    def _build_micro_fns(self):
        """Eager-compatible forward/backward/step path (reference API)."""
        grad_shardings = self._grad_shardings

        def fwd_bwd(params, scale, batch, rng, step, pld_theta):
            loss, grads = self._loss_and_scaled_grads(params, scale, batch, rng,
                                                      step=step,
                                                      pld_theta=pld_theta)
            # fp32 accumulation regardless of param dtype (the fused path's acc0 is fp32;
            # bf16/fp16 accumulation across microbatches would drop small contributions)
            grads = tree_cast(grads, jnp.float32)
            grads = jax.lax.with_sharding_constraint(grads, grad_shardings)
            return loss, grads

        self._fns["fwd_bwd"] = jax.jit(fwd_bwd, out_shardings=(None, grad_shardings))
        self._fns["acc_add"] = jax.jit(
            lambda acc, g: jax.tree_util.tree_map(jnp.add, acc, g),
            donate_argnums=(0,), out_shardings=grad_shardings)

        if self.offload_enabled:
            self._fns["finalize_offload"] = jax.jit(
                self._finalize_grads_offload, static_argnums=(2,), donate_argnums=(0,),
                out_shardings=(self._state_shardings, self._grad_shardings, None))
        else:
            def apply_step(state, acc, lr, n_micro):
                return self._apply_update(state, acc, lr, n_micro)

            self._fns["apply_step"] = jax.jit(
                apply_step, static_argnums=(3,), donate_argnums=(0,),
                out_shardings=(self._state_shardings, None))

        def eval_step(params, batch, rng):
            loss = self.module.loss_fn(tree_cast(params, self.compute_dtype), batch, rng)
            return loss[0] if isinstance(loss, tuple) else loss

        self._fns["eval_step"] = jax.jit(eval_step)

    # ------------------------------------------------------------- data plumbing
    def _globalize(self, local_batch, leading_gas: bool = False):
        """Assemble process-local numpy batch into globally-sharded jax.Arrays."""
        mesh = self.mesh_spec

        def one(leaf):
            leaf = np.asarray(leaf)
            batch_axes = tuple(ax for ax in ("data", "fsdp", "expert")
                               if mesh.size(ax) > 1) or None
            if leading_gas:
                spec = [None, batch_axes] + [None] * (leaf.ndim - 2)
            else:
                spec = [batch_axes] + [None] * (leaf.ndim - 1)
            sharding = NamedSharding(mesh.mesh, P(*spec))
            if dist.get_world_size() == 1:
                return jax.device_put(leaf, sharding)
            return jax.make_array_from_process_local_data(sharding, leaf)

        return jax.tree_util.tree_map(one, local_batch)

    def _reshape_for_gas(self, batch):
        gas = self.gradient_accumulation_steps()

        def one(leaf):
            leaf = np.asarray(leaf)
            if not (leaf.shape[0] % gas == 0):
                raise AssertionError(f"train_batch leading dim {leaf.shape[0]} not divisible by "
                 f"gradient_accumulation_steps {gas}")
            return leaf.reshape(gas, leaf.shape[0] // gas, *leaf.shape[1:])

        return jax.tree_util.tree_map(one, batch)

    def _dispatch_step(self, jitted, gbatch, lr, theta):
        """Call the compiled step in the engine's regime; returns its metrics
        (device values: nothing is fetched)."""
        if self.offload_enabled:
            self.state, grads, metrics = jitted(self.state, gbatch, theta)
            self._host_optimizer_step(grads, lr, metrics)
        elif self._quantized_dp:
            self.state, metrics, self._qar_residual = jitted(
                self.state, gbatch, lr, theta, self._qar_residual)
        else:
            self.state, metrics = jitted(self.state, gbatch, lr, theta)
        return metrics

    # ------------------------------------------------------------------- API
    def train_batch(self, batch=None, data_iter=None):
        """Process one full global batch (gas microbatches) and take an optimizer step.

        Mirrors ``PipelineEngine.train_batch`` (reference ``pipe/engine.py:295``) as the fused
        path for the base engine.
        """
        if batch is None:
            if data_iter is not None:
                batch = next(data_iter)
            elif self.training_dataloader is not None:
                batch = self._next_train_batch()
            else:
                raise ValueError("train_batch needs batch=, data_iter=, or training_data")
        # jitted steps trace LAZILY (at first call, not at jit()): another
        # engine constructed since __init__ may have swapped the global mesh /
        # overlap config, so re-assert ours before anything can trace — same
        # defense InferenceEngine applies in its compiled-fn dispatch
        set_global_mesh(self.mesh_spec)
        set_overlap_config(self.comm_overlap)
        if self.param_offload_enabled:
            return self._train_batch_param_offload(batch)
        first_trace = "train_step" not in self._fns
        if first_trace:
            # isolate this engine's span capture: build-time records
            # (dp.grad_sync) land during _build_train_step, trace-time records
            # (RowParallelDense / MoE exchange) during the first jitted call
            collective_spans.reset()
            self._build_train_step()
        jitted = self._fns["train_step"]
        tracer = get_tracer()
        # a span covers what the HOST did in this call; the device time of
        # step n is joined from a profiler trace by the ``step`` attribute.
        # Nothing here waits for the device, tracer on or off.
        with tracer.span("train_step", cat=CAT_TRAIN,
                         step=self._host_steps + 1) as step_span:
            with tracer.span("train.host_batch"):
                local = self._reshape_for_gas(batch)
                gbatch = self._globalize(local, leading_gas=True)

            fp_cfg = self._config.flops_profiler
            if fp_cfg.enabled and self._host_steps + 1 == fp_cfg.profile_step:
                self._run_flops_profiler(gbatch)

            self.tput_timer.start()
            self.timers(TRAIN_BATCH_TIMER).start()
            lr = np.float32(self.get_lr_value())
            theta = np.float32(self.progressive_layer_drop.get_theta()
                               if self.progressive_layer_drop is not None
                               else 1.0)
            self._step_t0 = time.perf_counter()
            self._last_step_tokens = _batch_tokens(batch)
            with tracer.span("train.dispatch"):
                if first_trace:
                    # the first call is python tracing + lowering + compile
                    # (or a cache load), as the host sees it
                    with tracer.phase("setup.build_train_step"):
                        metrics = self._dispatch_step(jitted, gbatch, lr, theta)
                    self._comm_spans = collective_spans.summary()
                else:
                    metrics = self._dispatch_step(jitted, gbatch, lr, theta)
            if spans_total_bytes(self._comm_spans):
                # grad sync is XLA-scheduled inside the step: host wall-time
                # can't split it out, so the trace-time byte accounting rides
                # the step's span as MODELED attributes
                step_span.set(
                    bytes_on_wire=spans_total_bytes(self._comm_spans),
                    overlap_ratio=spans_overlap_ratio(self._comm_spans))
            with tracer.span("train.bookkeeping"):
                obs_profiler.tick("train_step")
                self.timers(TRAIN_BATCH_TIMER).stop(sync=False)
                self.tput_timer.stop(global_step=True)

                # Host-side step mirror: the device counter (state.global_step)
                # is exact but reading it forces a device sync per step; cadence
                # decisions (print/monitor) use this mirror so the hot path never
                # stalls the async dispatch queue. (Under fp16 overflow-skip the
                # two can drift by the number of skipped steps; exact value
                # remains at .global_steps.)
                self._host_steps += 1
                self.micro_steps += self.gradient_accumulation_steps()
                if self.lr_scheduler is not None:
                    self.lr_scheduler.step()
                if self.curriculum_scheduler is not None:
                    self.curriculum_scheduler.update_difficulty(self._host_steps)
                if self.progressive_layer_drop is not None:
                    self.progressive_layer_drop.update_state(self._host_steps)
                self._last_metrics = metrics
                self._write_monitor_events(metrics)
        if self._host_steps % self._config.steps_per_print == 0:
            # lint: host-sync-ok (steps_per_print-gated: syncs only on print steps)
            log_dist(f"step={self._host_steps} loss={float(metrics['loss']):.4f} "
                     f"lr={float(lr):.3e} loss_scale={float(metrics['loss_scale']):.0f}",
                     ranks=[0])
            if self._config.wall_clock_breakdown:
                # reference engine.py wall_clock_breakdown: per-phase timer means each
                # print interval (the fused path has one phase; the eager path adds
                # fwd/bwd/step)
                names = [n for n in (TRAIN_BATCH_TIMER, FORWARD_GLOBAL_TIMER,
                                     BACKWARD_GLOBAL_TIMER, STEP_GLOBAL_TIMER)
                         if self.timers.has_timer(n)]
                self.timers.log(names)
        return metrics["loss"]

    def _train_batch_param_offload(self, batch):
        """Streamed whole-batch step (ZeRO-3 param offload): the coordinator runs the
        per-segment fwd/bwd stream and the host optimizer; no fused jitted step exists
        because the full parameter tree is never device-resident."""
        gas = self.gradient_accumulation_steps()
        local = self._reshape_for_gas(batch)
        micros = [self._globalize(jax.tree_util.tree_map(lambda l: l[i], local))
                  for i in range(gas)]
        fp_cfg = self._config.flops_profiler
        if fp_cfg.enabled and self._host_steps + 1 == fp_cfg.profile_step:
            self._run_flops_profiler_offload(micros[0])
        self.tput_timer.start()
        self.timers(TRAIN_BATCH_TIMER).start()
        self._step_t0 = time.perf_counter()
        self._last_step_tokens = _batch_tokens(batch)
        lr = np.float32(self.get_lr_value())
        rng = jax.random.fold_in(self._base_rng, self._host_steps)
        # the streamed step is host-synchronous: its span covers the work
        with get_tracer().span("train_step", cat=CAT_TRAIN,
                               step=self._host_steps + 1, offload=1):
            metrics = self._param_offload.train_step(micros, lr=float(lr),
                                                     rng=rng)
        obs_profiler.tick("train_step")
        self.timers(TRAIN_BATCH_TIMER).stop(sync=False)
        self.tput_timer.stop(global_step=True)
        self._host_steps += 1
        self.micro_steps += gas
        if self.lr_scheduler is not None:
            self.lr_scheduler.step()
        if self.curriculum_scheduler is not None:
            self.curriculum_scheduler.update_difficulty(self._host_steps)
        if self.progressive_layer_drop is not None:
            self.progressive_layer_drop.update_state(self._host_steps)
        self._last_metrics = metrics
        self._write_monitor_events(metrics)
        if self._host_steps % self._config.steps_per_print == 0:
            log_dist(f"step={self._host_steps} loss={metrics['loss']:.4f} "
                     f"lr={float(lr):.3e} "
                     f"loss_scale={metrics['loss_scale']:.0f}", ranks=[0])
        return metrics["loss"]

    def _host_optimizer_step(self, grads, lr, metrics):
        """Offload mode: host Adam on fp32 masters, push compute-dtype params H2D.
        The overflow read only syncs under fp16 (the offload path is host-synchronous at
        the grad fetch anyway)."""
        skip = bool(metrics["overflow"]) if self._config.fp16.enabled else False
        new_params = self._offload_tier.step(grads, lr=float(lr), skip=skip)
        if new_params is not None:
            self.state = self.state._replace(params=new_params)

    def _run_flops_profiler_offload(self, micro):
        """Flops profile of the STREAMED step (offload_param): trace the composed
        per-segment fwd+bwd of one microbatch over ABSTRACT parameters — no
        full-model device materialisation, same jaxpr/XLA accounting as the fused
        path's profile."""
        from ..profiling.flops_profiler import FlopsProfiler
        co = self._param_offload
        profiler = FlopsProfiler(self._config.flops_profiler)

        def abs_key(key):
            leaves = [jax.ShapeDtypeStruct(s, self.compute_dtype)
                      for s in co.key_shapes[key]]
            return jax.tree_util.tree_unflatten(co.key_treedef[key], leaves)

        params_t = tuple(tuple(abs_key(k) for k in seg.param_keys)
                         for seg in co.segments)
        G = len(co.segments)

        def step_fn(seg_params, batch, rng):
            xs = [None] * G
            x = None
            for g in range(G - 1):
                srng = jax.random.fold_in(rng, g)
                if co.segments[g].kind == "first":
                    x = co._fwd(g)(seg_params[g], batch, srng)
                else:
                    xs[g] = x
                    x = co._fwd(g)(seg_params[g], x, batch, srng)
            xs[G - 1] = x
            gout, loss = None, None
            grads = []
            for g in range(G - 1, -1, -1):
                srng = jax.random.fold_in(rng, g)
                seg = co.segments[g]
                if seg.kind == "last":
                    loss, gp, gout = co._bwd(g)(seg_params[g], xs[g], batch,
                                                srng, jnp.float32(1.0))
                elif seg.kind == "mid":
                    gp, gout = co._bwd(g)(seg_params[g], xs[g], batch, srng,
                                          gout)
                else:
                    gp = co._bwd(g)(seg_params[g], batch, srng, gout)
                grads.append(gp)
            return loss, grads

        try:
            profiler.profile_step(step_fn, params_t, micro,
                                  jax.random.PRNGKey(0),
                                  depth=self._config.flops_profiler.module_depth
                                  if self._config.flops_profiler.module_depth >= 0
                                  else 2)
            sps = self.tput_timer.avg_samples_per_sec() or None
            tput = (sps / self.train_batch_size()) if sps else None
            profiler.print_model_profile(throughput_per_sec=tput)
            self.flops_profiler = profiler
        except Exception as e:
            log_dist(f"flops profiler failed: {e}", ranks=[0])

    def _run_flops_profiler(self, gbatch):
        """One-shot train-step profile at ``flops_profiler.profile_step``
        (reference ``engine.py:1791-1800`` wiring)."""
        from ..profiling.flops_profiler import FlopsProfiler
        profiler = FlopsProfiler(self._config.flops_profiler)
        lr = np.float32(self.get_lr_value())

        def step_fn(state, batch):
            jitted = self._fns["train_step"]
            theta = np.float32(1.0)
            if self.offload_enabled:
                return jitted(state, batch, theta)
            if self._quantized_dp:
                return jitted(state, batch, lr, theta, self._qar_residual)
            return jitted(state, batch, lr, theta)

        try:
            profiler.profile_step(lambda s, b: step_fn(s, b), self.state, gbatch,
                                  depth=self._config.flops_profiler.module_depth
                                  if self._config.flops_profiler.module_depth >= 0 else 2)
            sps = self.tput_timer.avg_samples_per_sec() or None
            tput = (sps / self.train_batch_size()) if sps else None
            profiler.print_model_profile(throughput_per_sec=tput)
            self.flops_profiler = profiler
        except Exception as e:
            log_dist(f"flops profiler failed: {e}", ranks=[0])

    def _next_train_batch(self):
        if not hasattr(self, "_train_iter") or self._train_iter is None:
            loader = self.training_dataloader
            self._train_iter = loader if hasattr(loader, "__next__") \
                else iter(RepeatingLoader(loader))
        gas = self.gradient_accumulation_steps()
        micros = [next(self._train_iter) for _ in range(gas)]
        return jax.tree_util.tree_map(lambda *xs: np.concatenate(xs, axis=0), *micros)

    def forward(self, batch):
        """Compute loss for one microbatch; gradients are computed alongside and cached
        (JAX cannot split forward from backward), to be consumed by ``backward()``."""
        if self.param_offload_enabled:
            raise NotImplementedError(
                "the eager forward()/backward()/step() triple is unavailable under "
                "offload_param (no resident parameter tree) — use train_batch()")
        # re-assert trace environment (see train_batch): fwd_bwd traces on
        # first call and must see THIS engine's mesh + overlap setting
        set_global_mesh(self.mesh_spec)
        set_overlap_config(self.comm_overlap)
        first_trace = "fwd_bwd" not in self._fns
        if first_trace:
            collective_spans.reset()
            self._build_micro_fns()
        self.timers(FORWARD_GLOBAL_TIMER).start()
        gb = self._globalize(batch)
        rng = jax.random.fold_in(
            jax.random.fold_in(self._base_rng, self.state.global_step), self.micro_steps)
        theta = np.float32(self.progressive_layer_drop.get_theta()
                           if self.progressive_layer_drop is not None else 1.0)
        loss, grads = self._fns["fwd_bwd"](self.state.params,
                                           self.state.scaler.cur_scale,
                                           gb, rng, self.state.global_step, theta)
        if first_trace:
            self._comm_spans = collective_spans.summary()
        self._cached_grads = grads
        self._cached_loss = loss
        self.timers(FORWARD_GLOBAL_TIMER).stop()
        return loss

    __call__ = forward

    def backward(self, loss=None, allreduce_gradients: bool = True):
        """Fold the cached microbatch gradients into the accumulator.

        Reference semantics: ``engine.backward(loss)`` (``engine.py:1932``). The reduction
        across data-parallel devices happens inside XLA when the accumulator's sharded spec
        forces it (stage >= 2) or at update time (psum via replicated spec).
        """
        if not (self._cached_grads is not None):
            raise AssertionError("backward() called before forward()")
        if loss is not None and loss is not self._cached_loss \
                and not getattr(self, "_loss_mismatch_warned", False):
            # the cached grads differentiate the loss forward() computed — a
            # transformed/recomputed loss here would be silently ignored (JAX
            # cannot re-run autograd from a detached scalar, unlike torch)
            logger.warning(
                "backward(loss) received a different object than forward() "
                "returned; gradients correspond to forward()'s loss — any "
                "transformation applied in between does NOT reach the "
                "gradients. Fold scaling/additions into the model's loss_fn.")
            self._loss_mismatch_warned = True
        if self._grad_acc is None:
            self._grad_acc = self._cached_grads
        else:
            self._grad_acc = self._fns["acc_add"](self._grad_acc, self._cached_grads)
        self._cached_grads = None
        self._cached_loss = None
        return loss

    def is_gradient_accumulation_boundary(self) -> bool:
        """Reference ``engine.py:is_gradient_accumulation_boundary``."""
        return (self.micro_steps + 1) % self.gradient_accumulation_steps() == 0

    def step(self):
        """Optimizer step at gradient-accumulation boundaries (no-op otherwise).

        Reference ``engine.py:2143 step`` / ``_take_model_step:2075``.
        """
        if "fwd_bwd" not in self._fns:
            self._build_micro_fns()
        take_step = self.is_gradient_accumulation_boundary()
        self.micro_steps += 1
        if not take_step:
            return
        if not (self._grad_acc is not None):
            raise AssertionError("step() called with no accumulated gradients")
        self.timers(STEP_GLOBAL_TIMER).start()
        lr = np.float32(self.get_lr_value())
        if self.offload_enabled:
            self.state, grads, metrics = self._fns["finalize_offload"](
                self.state, self._grad_acc, self.gradient_accumulation_steps())
            self._host_optimizer_step(grads, lr, metrics)
        else:
            self.state, metrics = self._fns["apply_step"](
                self.state, self._grad_acc, lr, self.gradient_accumulation_steps())
        self._grad_acc = None
        self._host_steps += 1
        if self.lr_scheduler is not None:
            self.lr_scheduler.step()
        if self.curriculum_scheduler is not None:
            self.curriculum_scheduler.update_difficulty(self._host_steps)
        if self.progressive_layer_drop is not None:
            self.progressive_layer_drop.update_state(self._host_steps)
        self._last_metrics = metrics
        self.timers(STEP_GLOBAL_TIMER).stop(sync=False)
        self._write_monitor_events(metrics)

    def eval_batch(self, batch):
        gb = self._globalize(batch)
        # dedicated eval rng stream, disjoint from the train stream by construction: train
        # keys derive from fold_in(_base_rng, global_step) with global_step a non-negative
        # int32, so folding -1 (0xFFFFFFFF as uint32, outside that range) roots a branch no
        # train step can reach
        self._eval_calls = getattr(self, "_eval_calls", 0) + 1
        rng = jax.random.fold_in(jax.random.fold_in(self._base_rng, 0xFFFFFFFF), self._eval_calls)
        if self.param_offload_enabled:
            return self._param_offload.eval_loss(gb, rng)
        if "eval_step" not in self._fns:
            self._build_micro_fns()
        return self._fns["eval_step"](self.state.params, gb, rng)

    def set_monitor(self, monitor):
        """Attach/replace the MonitorMaster at runtime (mirrors
        ``InferenceEngine.set_monitor``); per-step ``Train/*`` events — loss,
        lr, step time, tokens/sec, and (when the flops profiler has run)
        modeled MFU — flow to it and to the observability registry."""
        self.monitor = monitor
        return self

    def _modeled_mfu(self, step_time_s: float) -> Optional[float]:
        """Modeled model-flops utilization: profiled step flops / step wall
        time / aggregate peak. Needs both a flops-profiler result (run the
        profiler via ``flops_profiler.profile_step``) and a per-chip peak —
        ``flops_profiler.peak_tflops`` in config, or the published-peaks table
        (``utils.device.PEAKS``); a device kind it does not hold — a CPU host —
        skips the mfu event rather than publish a made-up number. The profiled
        flops cover the whole GLOBAL-batch step, so the peak is per-chip ×
        device count."""
        prof = getattr(self, "flops_profiler", None)
        if prof is None or prof.result is None or step_time_s <= 0:
            return None
        peak_tflops = self._config.flops_profiler.peak_tflops
        if peak_tflops is None:
            peak_tflops = PEAKS.get(jax.devices()[0].device_kind,
                                    {}).get("bf16_tflops")
        if not peak_tflops:
            return None
        achieved = prof.result.total_flops / step_time_s / 1e12
        return achieved / (float(peak_tflops) * jax.device_count())

    def _write_monitor_events(self, metrics):
        # Train/* export (monitor AND registry) is gated on an enabled monitor
        # ON PURPOSE, unlike the inference engine's unconditional registry
        # records: building these events calls float(loss) — a per-step device
        # sync that stalls the async dispatch queue. generate() already syncs
        # for TTFT so its records are free; a monitor-less training loop must
        # stay fully pipelined. To export Train/* to the registry alone,
        # attach any cheap backend (jsonl) or engine.set_monitor(...).
        if self.monitor is None or not getattr(self.monitor, "enabled", False):
            return
        step = self._host_steps
        # lint: host-sync-ok (the documented Train/* monitor-gated sync: the
        # guard above returns unless a monitor is attached)
        events = [("Train/Samples/train_loss", float(metrics.get("loss", 0.0)), step),
                  ("Train/Samples/lr", self.get_lr_value(), step)]
        if self._config.fp16.enabled:
            # lint: host-sync-ok (monitor-gated, same guard)
            events.append(("Train/Samples/loss_scale",
                           float(metrics["loss_scale"]), step))
        if spans_total_bytes(self._comm_spans):
            # per-trace bytes-on-wire estimates from the decomposed-collective
            # call sites, snapshotted at THIS engine's first trace (the global
            # accumulator blends every engine's traces in the process)
            # lint: host-sync-ok (host-side span math, no device value)
            events.append(("Train/Comm/bytes_on_wire",
                           float(spans_total_bytes(self._comm_spans)), step))
            events.append(("Train/Comm/overlap_ratio",
                           spans_overlap_ratio(self._comm_spans), step))
        # step wall time, honest: the float(loss) above already forced the
        # device sync, so the clock covers the whole step, not the dispatch
        t0 = getattr(self, "_step_t0", None)
        if t0 is not None:
            step_time = time.perf_counter() - t0
            self._step_t0 = None
            events.append(("Train/step_time_ms", step_time * 1e3, step))
            tokens = getattr(self, "_last_step_tokens", 0)
            if tokens and step_time > 0:
                events.append(("Train/tokens_per_sec", tokens / step_time,
                               step))
            mfu = self._modeled_mfu(step_time)
            if mfu is not None:
                events.append(("Train/mfu", mfu, step))
        obs_record_events(events)        # process registry (exposition)
        self.monitor.write_events(events)

    # ------------------------------------------------------------- properties
    @property
    def global_steps(self) -> int:
        if self.state is None:
            return self._host_steps
        return int(self.state.global_step)

    @property
    def skipped_steps(self) -> int:
        if self.state is None:
            return self._param_offload.skipped_steps
        return int(self.state.skipped_steps)

    def get_global_grad_norm(self) -> float:
        return float(self._last_metrics.get("grad_norm", 0.0))

    def loss_scale(self) -> float:
        if self.state is None:
            return self._param_offload._cur_scale()
        return float(self.state.scaler.cur_scale)

    def get_lr_value(self) -> float:
        if self.lr_scheduler is not None:
            lrs = self.lr_scheduler.get_last_lr()
            if self.lr_scheduler.last_batch_iteration < 0:
                self.lr_scheduler.step(0)
                lrs = self.lr_scheduler.get_last_lr()
            return float(lrs[0])
        return float(getattr(self, "_base_lr", 1e-3))

    def get_lr(self):
        return [self.get_lr_value()]

    def train_batch_size(self) -> int:
        return self._config.train_batch_size

    def train_micro_batch_size_per_gpu(self) -> int:
        return self._config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self) -> int:
        return self._config.gradient_accumulation_steps

    def zero_optimization_stage(self) -> int:
        return self.zero_stage

    def get_batch_info(self):
        return (self.train_batch_size(), self.train_micro_batch_size_per_gpu(),
                self.gradient_accumulation_steps())

    # ------------------------------------------------------------ checkpointing
    def save_checkpoint(self, save_dir: str, tag: Optional[str] = None,
                        client_state: Optional[dict] = None, save_latest: bool = True):
        """Reference ``engine.py:3085``. Orbax writes sharded arrays once across hosts; the
        result is re-shardable to any topology (universal checkpoint by construction).

        Crash-consistent: all data is staged into ``<save_dir>/<tag>.tmp`` and
        published by ``commit_tag`` (manifest + fsync + one atomic rename); the
        ``latest`` pointer advances only after the rename lands, so a kill at any
        point leaves the previous committed tag loadable (see
        ``docs/FAULT_TOLERANCE.md``)."""
        tag = tag or f"global_step{self.global_steps}"
        # rank 0 alone reclaims stale staging (a racing reclaim would rmtree
        # peers' in-flight writes on a shared filesystem); peers join the
        # staging dir only after the barrier
        if dist.get_rank() == 0:
            path = self.checkpoint_engine.begin_tag(save_dir, tag)
        else:
            path = self.checkpoint_engine.staging_path(save_dir, tag)
        dist.barrier("ckpt_begin")
        if dist.get_rank() != 0:
            os.makedirs(path, exist_ok=True)
        fault_point("ckpt.save.begin")
        if self.param_offload_enabled:
            # the full model exists only as host fp32 masters — serialize those (plus
            # moments/scaler) as the checkpoint; there is no device state to save
            self._param_offload.save_to(self.checkpoint_engine,
                                        os.path.join(path, "offload_state"))
        else:
            self.checkpoint_engine.save(self.state._asdict(),
                                        os.path.join(path, "state"))
        if self.offload_enabled:
            # host-resident fp32 masters + moments (reference: offloaded optimizer
            # partitions serialize through the same checkpoint, stage_1_and_2.py:2235);
            # the NVMe tier streams its moment files by copy, never through RAM
            self._offload_tier.save_to(self.checkpoint_engine,
                                       os.path.join(path, "offload_state"))
        side = {
            "global_step": self.global_steps,
            "micro_steps": self.micro_steps,
            "skipped_steps": self.skipped_steps,
            "mesh_axis_sizes": self.mesh_spec.axis_sizes,
            "lr_scheduler": (self.lr_scheduler.state_dict()
                             if self.lr_scheduler is not None else None),
            "client_state": client_state or {},
        }
        self.checkpoint_engine.save(side, os.path.join(path, "client_state.pkl"))
        dist.barrier("ckpt_save")
        # non-zero ranks drain their async writes, then a barrier proves every
        # peer's shards are durable BEFORE rank 0 hashes the manifest and
        # renames (commit_tag drains rank 0's own writer internally) — a crash
        # anywhere before the rename leaves 'latest' at the previous durable tag
        if dist.get_rank() != 0:
            self.checkpoint_engine.commit(tag)
        dist.barrier("ckpt_drain")
        with get_tracer().span("checkpoint_commit", cat=CAT_TRAIN,
                               tag=str(tag), step=self._host_steps):
            if dist.get_rank() == 0:
                final = self.checkpoint_engine.commit_tag(save_dir, tag)
            else:
                final = os.path.join(save_dir, str(tag))
            dist.barrier("ckpt_commit")
            if save_latest and dist.get_rank() == 0:
                write_latest_pointer(save_dir, tag)
        return final

    def _resolve_load_tag(self, load_dir: str, tag: Optional[str]):
        """Tag resolution with torn-checkpoint fallback: an explicit ``tag`` is
        trusted (validation still runs at load); otherwise follow ``latest``,
        and when it names a missing/uncommitted tag, fall back to the newest
        committed tag on disk."""
        if tag is not None:
            return str(tag)
        latest_path = os.path.join(load_dir, LATEST_FILE)
        pointed = None
        if os.path.isfile(latest_path):
            with open(latest_path) as f:
                pointed = f.read().strip()
        if pointed and is_committed_tag(load_dir, pointed):
            return pointed
        fallback = find_latest_committed_tag(load_dir, exclude=pointed)
        if fallback is not None:
            if pointed:
                logger.error(
                    f"[ckpt] '{LATEST_FILE}' points at {pointed!r} which is "
                    f"missing or uncommitted — falling back to newest committed "
                    f"tag {fallback!r}")
            return fallback
        if pointed:
            # nothing committed to fall back to: surface the torn tag loudly
            return pointed
        return None

    def load_checkpoint(self, load_dir: str, tag: Optional[str] = None,
                        load_optimizer_states: bool = True,
                        load_lr_scheduler_states: bool = True,
                        load_module_only: bool = False,
                        validate: bool = True):
        """Reference ``engine.py:2725``. Restores into the CURRENT mesh/sharding regardless of
        the topology that wrote the checkpoint (universal-checkpoint semantics).

        Integrity: the tag's SHA-256 manifest is validated before anything is
        restored (``CheckpointCorruptionError`` names the offending shard);
        ``tag=None`` resolves via ``latest`` with automatic fallback to the
        newest *committed* tag when the pointer is torn."""
        resolved = self._resolve_load_tag(load_dir, tag)
        if resolved is None:
            logger.warning(f"No '{LATEST_FILE}' file at {load_dir} and no "
                           "committed tags found; nothing loaded")
            return None, {}
        tag = resolved
        path = os.path.join(load_dir, str(tag))
        if not os.path.isdir(path):
            raise CheckpointCorruptionError(
                f"checkpoint tag {tag!r} does not exist under {load_dir}")
        if validate:
            validate_manifest(path)
        fault_point("ckpt.load.begin")
        if self.param_offload_enabled:
            self._param_offload.load_from(
                self.checkpoint_engine, os.path.join(path, "offload_state"),
                load_optimizer_states=(load_optimizer_states
                                       and not load_module_only))
            side = self.checkpoint_engine.load(os.path.join(path, "client_state.pkl"))
            self._host_steps = side.get("global_step", 0)
            self.micro_steps = side.get("micro_steps", 0)
            self._param_offload._skipped_steps = side.get("skipped_steps", 0)
            # QAT schedule gating resumes where training left off (push_step is
            # the coordinator's train-step mirror)
            self._param_offload.push_step = self._host_steps
            if self.curriculum_scheduler is not None:
                self.curriculum_scheduler.update_difficulty(self._host_steps)
            if self.progressive_layer_drop is not None:
                self.progressive_layer_drop.update_state(self._host_steps)
            if load_lr_scheduler_states and self.lr_scheduler is not None \
                    and side.get("lr_scheduler") is not None:
                self.lr_scheduler.load_state_dict(side["lr_scheduler"])
            log_dist(f"loaded param-offload checkpoint {path} at "
                     f"global_step={self._host_steps}", ranks=[0])
            return path, side.get("client_state", {})
        restored = self.checkpoint_engine.load(
            os.path.join(path, "state"),
            template=self.state._asdict(),
            shardings=self._state_shardings._asdict())
        new_state = TrainState(**restored)
        if load_module_only or not load_optimizer_states:
            new_state = self.state._replace(params=new_state.params,
                                            global_step=new_state.global_step)
        self.state = new_state
        if getattr(self, "_qar_residual", None) is not None:
            # EF residual is per-worker transient state, not checkpointed —
            # restart from zero feedback (one step of extra quantization noise)
            self._qar_residual, self._qar_shardings = self._init_qar_residual()
        if self.offload_enabled:
            off_path = os.path.join(path, "offload_state")
            if load_optimizer_states and not load_module_only \
                    and self._offload_tier.has_checkpoint(off_path):
                self._offload_tier.load_from(self.checkpoint_engine, off_path)
                # device params re-derive from the restored masters (they are the source
                # of truth in offload mode)
                self.state = self.state._replace(
                    params=self._offload_tier.initial_device_params())
            else:
                # module-only / no-opt-state load (or a checkpoint written without the
                # offload tier): masters MUST follow the loaded weights, else the next
                # host step would overwrite them with stale init-time masters
                self._offload_tier.reseed_from_device(self.state.params)
        self._host_steps = int(new_state.global_step)   # resync host mirror (one-off sync)
        if self.curriculum_scheduler is not None:
            # fast-forward difficulty to the resumed step (custom schedules aside,
            # difficulty is a pure function of the step)
            self.curriculum_scheduler.update_difficulty(self._host_steps)
        if self.progressive_layer_drop is not None:
            # theta is likewise a pure function of the step
            self.progressive_layer_drop.update_state(self._host_steps)
        side = self.checkpoint_engine.load(os.path.join(path, "client_state.pkl"))
        self.micro_steps = side.get("micro_steps", 0)
        if load_lr_scheduler_states and self.lr_scheduler is not None \
                and side.get("lr_scheduler") is not None:
            self.lr_scheduler.load_state_dict(side["lr_scheduler"])
        client_state = side.get("client_state", {})
        log_dist(f"loaded checkpoint {path} at global_step={self.global_steps}", ranks=[0])
        return path, client_state


class CheckpointAutoSaver:
    """Preemption-aware automatic checkpointing around a :class:`DeepSpeedEngine`.

    Two triggers (reference: megatron-style ``--save-interval`` + the launcher's
    SIGTERM propagation discipline):

    - every ``interval_steps`` optimizer steps, ``after_step()`` saves a tag;
    - on SIGTERM (scheduler preemption) the handler only sets a flag — the save
      happens at the next ``after_step()`` call, i.e. at a step boundary where
      the engine state is consistent — then a ``preempted`` marker naming the
      tag is written and ``SystemExit(128+SIGTERM)`` is raised so the launcher /
      scheduler restarts the job, which resumes via ``resume()``.

    Usage::

        saver = CheckpointAutoSaver(engine, save_dir, interval_steps=100)
        saver.resume()                     # load latest committed tag, if any
        with saver:                        # installs the SIGTERM handler
            for batch in data:
                engine.train_batch(batch)
                saver.after_step()
    """

    PREEMPT_MARKER = "preempted"

    def __init__(self, engine, save_dir: str, interval_steps: int = 0,
                 tag_prefix: str = "global_step", exit_on_preempt: bool = True,
                 client_state_fn: Optional[Callable[[], dict]] = None):
        self.engine = engine
        self.save_dir = save_dir
        self.interval_steps = int(interval_steps)
        self.tag_prefix = tag_prefix
        self.exit_on_preempt = exit_on_preempt
        self.client_state_fn = client_state_fn
        self._preempt = threading.Event()
        self._prev_handler = None
        self._installed = False
        self.last_saved_tag: Optional[str] = None

    # ------------------------------------------------------------- signal wiring
    def install(self) -> "CheckpointAutoSaver":
        """Install the SIGTERM handler (main thread only — a no-op flag set, so
        it is safe inside any training loop)."""
        self._prev_handler = signal.signal(signal.SIGTERM, self._on_sigterm)
        self._installed = True
        return self

    def uninstall(self):
        if self._installed:
            signal.signal(signal.SIGTERM, self._prev_handler or signal.SIG_DFL)
            self._installed = False

    def __enter__(self) -> "CheckpointAutoSaver":
        return self.install()

    def __exit__(self, *exc):
        self.uninstall()
        return False

    def _on_sigterm(self, signum, frame):
        logger.warning("[autosave] SIGTERM received — checkpoint at next step "
                       "boundary, then exit for scheduler restart")
        self._preempt.set()

    @property
    def preempted(self) -> bool:
        return self._preempt.is_set()

    # ------------------------------------------------------------------- saving
    def save(self, mark_preempted: bool = False) -> str:
        tag = f"{self.tag_prefix}{self.engine.global_steps}"
        client_state = self.client_state_fn() if self.client_state_fn else None
        path = self.engine.save_checkpoint(self.save_dir, tag=tag,
                                           client_state=client_state)
        self.last_saved_tag = tag
        if mark_preempted and dist.get_rank() == 0:
            marker = os.path.join(self.save_dir, self.PREEMPT_MARKER)
            with open(marker + ".tmp", "w") as f:
                f.write(tag)
            os.rename(marker + ".tmp", marker)
        return path

    def after_step(self) -> Optional[str]:
        """Call once per optimizer step. Saves when the interval elapses or a
        preemption is pending; on preemption also exits (``exit_on_preempt``).
        Returns the saved path, or None when no save was due.

        Multi-host: ranks can observe SIGTERM on different step boundaries, so
        the flag is agreed via a max-allreduce each step — every rank then
        enters the collective save at the SAME step (mismatched steps would
        deadlock the save barriers)."""
        preempted = self._preempt.is_set()
        if dist.get_world_size() > 1:
            agreed = dist.all_reduce(np.asarray(int(preempted), np.int32),
                                     op="max")
            if bool(agreed) and not preempted:
                self._preempt.set()
            preempted = bool(agreed)
        if preempted:
            path = self.save(mark_preempted=True)
            if self.exit_on_preempt:
                raise SystemExit(128 + signal.SIGTERM)
            self._preempt.clear()
            return path
        steps = self.engine.global_steps
        if self.interval_steps > 0 and steps > 0 \
                and steps % self.interval_steps == 0 \
                and self.last_saved_tag != f"{self.tag_prefix}{steps}":
            return self.save()
        return None

    # ----------------------------------------------------------------- resuming
    def resume(self):
        """Load the newest committed checkpoint (via ``latest`` with torn-tag
        fallback) and clear any preemption marker. Returns
        ``(path, client_state)`` or ``(None, {})`` when nothing is saved yet."""
        path, client_state = self.engine.load_checkpoint(self.save_dir)
        marker = os.path.join(self.save_dir, self.PREEMPT_MARKER)
        if os.path.isfile(marker):
            if dist.get_rank() == 0:
                logger.info(f"[autosave] resuming after preemption "
                            f"(marker tag {open(marker).read().strip()!r})")
                os.unlink(marker)
        return path, client_state
