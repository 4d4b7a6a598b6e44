"""Process start to the first timed request: generation, load, warm-up."""


def read(ctx):
    return ctx.setup_s
