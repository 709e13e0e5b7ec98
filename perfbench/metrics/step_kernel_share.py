"""Share of the chunk program's device time spent in the Pallas step
kernel (``_fluid_step_kernel``), found by name in the device trace.
Nothing to read where the step core runs as plain XLA operations."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.kernel_s or not t.chunk_s:
        return None
    return 100.0 * t.kernel_s / t.chunk_s
