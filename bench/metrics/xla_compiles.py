"""xla_compiles: XLA compilations (not cache loads) while the window ran, counted from jax.monitoring."""


def read(ctx):
    return ctx["xla_compiles"]
