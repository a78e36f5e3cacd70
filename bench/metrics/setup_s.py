"""Seconds from the start of the run's process to its first due request:
the hub's build and fits, every warmed shape, the edge's start."""


def read(ctx):
    return ctx.setup_s
