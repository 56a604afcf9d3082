"""Seconds of set-up spent tracing, lowering and compiling (or loading from
the compile cache): the union of jax's compile-event intervals before the
window opened."""

NAME = "setup_compile_s"
UNIT = "s"
LAYER = "device set-up"
MOVES = "setup_s"
KINDS = ("train", "serve_closed")


def read(ctx):
    return ctx.probe.compile_seconds(ctx.t0, ctx.result.window[0])
