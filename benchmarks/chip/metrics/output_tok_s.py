"""Output tokens delivered in the window over the window's seconds."""

NAME, UNIT = "output_tok_s", "tokens/s"


def read(ctx):
    n = sum(1 for t in ctx.stamps if ctx.origin <= t < ctx.window_end)
    return n / ctx.seconds if n else None
