"""Seconds JAX spent tracing, lowering and compiling (or loading from the
persistent cache) during set-up, from its compile-duration events."""


def read(ctx):
    return ctx.get("compile_s")
