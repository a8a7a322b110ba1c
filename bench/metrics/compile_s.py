"""Seconds JAX spent compiling during set-up: tracing, lowering, and the
backend compile or its read from the persistent cache, summed from JAX's
own monitoring events."""


def read(ctx):
    return ctx["setup_compile_s"]
