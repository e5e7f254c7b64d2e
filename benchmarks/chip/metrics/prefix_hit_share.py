"""Admission and prefix index: share of the prompt tokens admitted in the
window that came from cached prefix pages rather than from prefill
(``cache.stats["prefix_tokens_reused"]`` against the prefill rows the
engine recorded)."""

NAME, UNIT = "prefix_hit_share", "%"


def read(ctx):
    if "prefix_tokens_reused" not in ctx.c1:
        return None
    reused = ctx.c1["prefix_tokens_reused"] - ctx.c0["prefix_tokens_reused"]
    filled = ctx.c1["prefill_rows"] - ctx.c0["prefill_rows"]
    if reused + filled <= 0:
        return None
    return 100.0 * reused / (reused + filled)
