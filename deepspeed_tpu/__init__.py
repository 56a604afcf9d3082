"""deepspeed_tpu — a TPU-native training & inference framework.

Brand-new design with the capability surface of DeepSpeed (reference:
``deepspeed/__init__.py``): ``initialize()`` wraps a model + JSON config into a training engine
with ZeRO-style sharding over a named device mesh; ``init_inference()`` builds a TP-sharded
serving engine. Compute is JAX/XLA/Pallas over `jax.sharding.Mesh`; collectives are
sharding-induced and compiled onto ICI, not process-group calls.
"""

__version__ = "0.1.0"
version = __version__

from . import comm  # noqa: F401
from .comm import init_distributed  # noqa: F401
from .config import DeepSpeedConfig  # noqa: F401
from .parallel import MeshSpec  # noqa: F401
from .utils.logging import logger  # noqa: F401


def initialize(args=None,
               model=None,
               optimizer=None,
               model_parameters=None,
               training_data=None,
               lr_scheduler=None,
               mpu=None,
               dist_init_required=None,
               collate_fn=None,
               config=None,
               config_params=None):
    """Create a training engine. Reference: ``deepspeed/__init__.py:initialize:52``.

    Returns ``(engine, optimizer_handle, dataloader, lr_scheduler_handle)`` like the reference.
    ``model`` is a :class:`deepspeed_tpu.models.Model` (an apply-fn + param pytree pair) or a
    flax module wrapper; see ``runtime/engine.py``.
    """
    from .runtime.engine import DeepSpeedEngine
    from .runtime.pipe.module import PipelineModule
    from .utils.device import enable_compile_cache

    enable_compile_cache()
    config = config if config is not None else config_params
    if config is None and args is not None and hasattr(args, "deepspeed_config") \
            and args.deepspeed_config is not None:
        config = args.deepspeed_config
    if not (config is not None):
        raise AssertionError("DeepSpeed requires --deepspeed_config or config=")

    if isinstance(model, PipelineModule):
        from .runtime.pipe.engine import PipelineEngine
        engine = PipelineEngine(args=args, model=model, optimizer=optimizer,
                                model_parameters=model_parameters,
                                training_data=training_data, lr_scheduler=lr_scheduler,
                                mpu=mpu, collate_fn=collate_fn, config=config)
    else:
        engine = DeepSpeedEngine(args=args, model=model, optimizer=optimizer,
                                 model_parameters=model_parameters,
                                 training_data=training_data, lr_scheduler=lr_scheduler,
                                 mpu=mpu, collate_fn=collate_fn, config=config)
    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler


def init_inference(model, config=None, **kwargs):
    """Create an inference engine. Reference: ``deepspeed/__init__.py:init_inference:233``.

    Decoder (CausalLM) models serve through :class:`InferenceEngine` (KV-cache
    generation); encoder models (BERT/DistilBERT configs or HF modules) through
    :class:`EncoderInferenceEngine` (whole-sequence forward) — the reference's
    bert/distil_bert injection containers."""
    from .inference.engine import InferenceEngine
    from .inference.config import DeepSpeedInferenceConfig
    from .utils.device import enable_compile_cache

    enable_compile_cache()
    if config is None:
        config = {}
    if isinstance(config, dict):
        config.update({k: v for k, v in kwargs.items() if v is not None})
        config = DeepSpeedInferenceConfig(**config)

    from .models.encoder import EncoderConfig
    is_encoder = isinstance(model, EncoderConfig)
    if not is_encoder:
        try:
            from .module_inject.encoder_policies import is_hf_encoder
            is_encoder = is_hf_encoder(model)
        except ImportError:
            pass
    if is_encoder:
        from .inference.encoder_engine import EncoderInferenceEngine
        return EncoderInferenceEngine(model, config)
    return InferenceEngine(model, config)


def add_config_arguments(parser):
    """Reference ``deepspeed/__init__.py:add_config_arguments`` (``_add_core_arguments:159``)."""
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed (helper flag, parsed for compatibility)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to DeepSpeed json configuration")
    group.add_argument("--deepscale", default=False, action="store_true",
                       help=argparse_suppress())
    group.add_argument("--deepscale_config", default=None, type=str,
                       help=argparse_suppress())
    return parser


def argparse_suppress():
    import argparse
    return argparse.SUPPRESS

from . import inference  # noqa: F401,E402  (init_inference config surface)
from . import moe  # noqa: F401,E402
from .runtime.activation_checkpointing import checkpointing  # noqa: F401,E402
from .profiling.flops_profiler import get_model_profile  # noqa: F401,E402
