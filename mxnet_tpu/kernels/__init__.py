"""Hand-written Pallas TPU kernels.

The compute hot-spots the XLA autofuser can't schedule optimally get
explicit MXU/VMEM kernels here (SURVEY §5.7 long-context requirement; the
reference's analog is the hand-tuned CUDA in src/operator/contrib/
transformer.cu and mshadow).  Kernels are platform-gated by callers via
``jax.lax.platform_dependent`` — every kernel ships with a portable dense
fallback and an interpret-mode path used by the CPU test suite as the
numerics oracle.
"""

from .flash_attention import flash_attention  # noqa: F401
from .ring_attention import (ring_attention,  # noqa: F401
                             sequence_parallel_attention)
