"""Executables compiled or loaded from the persistent cache inside the
measured window (``jax.monitoring`` events the harness counts); 0 when
every shape was warmed up."""


def read(ctx):
    return ctx.compiles
