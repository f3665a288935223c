from .rope import apply_rope, rope_cos_sin  # noqa: F401
from .attention import NO_KERNELS, Kernels  # noqa: F401
from .sampling import sample_tokens  # noqa: F401
