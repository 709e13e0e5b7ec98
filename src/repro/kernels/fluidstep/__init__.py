# Fused per-step contention/rate core of the fluid simulator's hot loop
# (domain incidence matmuls, Eq. 5 rate, slowest-member scale, gating-side
# k/min-old-rem).  JaxSimConfig.kernel picks the implementation; left
# empty it is the compiled Pallas kernel on a TPU and the lax reference
# everywhere else.
from repro.kernels.fluidstep.ops import fluid_step_core, resolve_impl

__all__ = ["fluid_step_core", "resolve_impl"]
