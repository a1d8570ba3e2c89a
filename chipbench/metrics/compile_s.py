"""Seconds of JAX tracing, lowering and compiling in set-up: the union of
JAX's compile-event spans (``CompileClock``), persistent-cache hits
included, since they still trace and lower."""


def read(ctx):
    return ctx.counters.get("compile_s")
