"""Process start to the window's start: JAX start-up, network build,
classifier training, switching compile, warm-up."""


def read(ctx):
    return ctx.setup_s
