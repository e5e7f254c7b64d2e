"""Admission: 95th percentile over the window's requests of the wait from
a request's first slot to the dispatch of its first prefill chunk (the
program's stamps ``RequestHandle.prefill_start - admitted``): the turns
it waits behind other prompts' chunks. Requests waiting across a profiler
start or stop (``ctx.stalls``) are left out."""

import program

NAME, UNIT = "prefill_wait_p95_ms", "ms"


def read(ctx):
    return program.wait_p95_ms(ctx, "admitted", "prefill_start")
