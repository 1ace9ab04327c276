"""setup.compile_s: seconds JAX spent tracing, lowering and compiling
(a compile-cache read counts as a compile) during set-up, from
jax.monitoring events. Moves ``setup_s``."""


def read(ctx):
    return ctx.get("setup_compile_s")
