from .model_config import (ModelConfig, MODEL_PRESETS, HF_SHAPE_KEYS,  # noqa: F401
                           apply_hf_overrides, get_model_config)
from .engine_config import EngineConfig, CacheConfig, SchedulerConfig, ParallelConfig, ResilienceConfig, QoSTier, latent_model_refusal  # noqa: F401
