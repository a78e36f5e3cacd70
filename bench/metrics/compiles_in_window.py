"""Executables the server lowered inside the window (one per jit
signature it had not met), from its ``jax.monitoring`` listener."""


def read(ctx):
    return ctx.window.get("lowered")
