"""ray_tpu.ops: TPU compute kernels (Pallas) with pure-jax fallbacks.

The device-compute counterpart of the framework: where the reference
orchestrates external CUDA kernels (torch ops under DDP workers), ray_tpu
owns its hot ops as Pallas TPU kernels (SURVEY.md §7 phase 5; pallas_guide
playbook), each with a reference jax implementation used for testing on CPU
and as the autodiff backward.
"""

from .attention import flash_attention, mha_reference  # noqa: F401
from .gated_delta import (gated_delta_plan, gated_delta_reference,  # noqa: F401
                          gated_delta_rule)
from .grouped_matmul import grouped_matmul  # noqa: F401
from .kda import kda_plan, kda_reference, kda_rule  # noqa: F401
from .layers import (causal_conv1d_silu, gated_rms_norm,  # noqa: F401
                     head_rms_norm_gated, layer_norm, rms_norm, rope, swiglu)
from .loss import cross_entropy  # noqa: F401
from .selective_scan import (selective_scan, selective_scan_plan,  # noqa: F401
                             selective_scan_reference)
from .ssm_scan import ssm_scan, ssm_scan_plan, ssm_scan_reference  # noqa: F401
