"""Seconds of JAX's compile-duration events (lowering, and compiling or
loading from the persistent cache) during set-up."""


def read(ctx):
    return ctx["compile_s"]
