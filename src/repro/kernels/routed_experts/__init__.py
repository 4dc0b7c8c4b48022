from repro.kernels.routed_experts.ops import routed_experts  # noqa: F401
from repro.kernels.routed_experts.ref import routed_experts_ref  # noqa: F401
