from .llama import (  # noqa: F401
    StepMeta,
    init_params,
    forward,
    compute_logits,
)
from .registry import get_model_config  # noqa: F401
