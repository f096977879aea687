"""compile_s: seconds JAX spent compiling, or reading compiled programs
back from the persistent cache, during set-up (``jax.monitoring`` events,
summed by ``benchlib/compile_clock.py``).  Moves setup_s."""


def read(ctx):
    return ctx.compile_s
