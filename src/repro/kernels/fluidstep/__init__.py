# Per-step contention/rate core of the fluid simulator's hot loop
# (per-domain in-flight counts, Eq. 5 rate, slowest-member scale,
# gating-side k/min-old-rem), in lax on every backend.
from repro.kernels.fluidstep.core import fluid_step_core, job_overlap

__all__ = ["fluid_step_core", "job_overlap"]
