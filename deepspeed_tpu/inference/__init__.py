from .config import DeepSpeedInferenceConfig, DeepSpeedTPConfig
from .engine import InferenceEngine
from .diffusion_engine import DiffusionInferenceEngine, init_diffusion_inference
from .serving import (ChunkedDecodeExecutor, ContinuousBatchingScheduler,
                      QueueFullError, RequestHandle, RequestState, ServingConfig,
                      ServingTelemetry)
from .speculative import (DraftModelProposer, NgramProposer, SpeculativeConfig,
                          make_proposer)
