"""ray_tpu.ops: TPU compute kernels (Pallas) with pure-jax fallbacks.

The device-compute counterpart of the framework: where the reference
orchestrates external CUDA kernels (torch ops under DDP workers), ray_tpu
owns its hot ops as Pallas TPU kernels (SURVEY.md §7 phase 5; pallas_guide
playbook), each with a reference jax implementation used for testing on CPU
and as the autodiff backward.
"""

from .attention import flash_attention, mha_reference  # noqa: F401
from .grouped_matmul import grouped_matmul  # noqa: F401
from .layers import rms_norm, rope, swiglu  # noqa: F401
from .loss import cross_entropy  # noqa: F401
