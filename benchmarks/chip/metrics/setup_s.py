"""Process start to the first timed request: imports, weights, engine,
compilation (or loading it from the cache) and the mix's warm-up."""

NAME, UNIT = "setup_s", "s"


def read(ctx):
    return ctx.setup_s
