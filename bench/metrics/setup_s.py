"""Set-up: process start to the window's start (imports, reaching the
chips, building and compiling the step or loading it from the compile
cache, making the lap of traffic, serving the warm-up packets)."""


def read(ctx):
    return ctx.served.setup_s
