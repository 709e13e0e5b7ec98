"""Programs compiled, or loaded from the persistent compilation cache,
while queries ran (``jax.monitoring``'s backend-compile events after the
warm-up).  Above 0, set-up missed a shape the window used."""


def read(ctx):
    return float(ctx.compiles)
