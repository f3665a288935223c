from .model_config import (ModelConfig, MODEL_PRESETS, HF_SHAPE_KEYS,  # noqa: F401
                           apply_hf_overrides, get_model_config)
from .engine_config import EngineConfig, CacheConfig, SchedulerConfig, ParallelConfig, ResilienceConfig, QoSTier, cache_kind_refusal  # noqa: F401
