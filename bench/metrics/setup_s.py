"""Seconds from the process's start to the window's opening: JAX and the
chip, endpoint initialisation, compiles, uploads and one warm invocation
of every function."""


def read(ctx):
    return ctx.setup_s
