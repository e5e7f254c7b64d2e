"""Admission: 95th percentile over the window's requests of the wait from
the engine's accepting a request to its first slot (the program's stamps
``RequestHandle.admitted - submitted``). Requests waiting across a
profiler start or stop (``ctx.stalls``) are left out, as in
``gen_lag_p95_ms``."""

import program

NAME, UNIT = "queue_wait_p95_ms", "ms"


def read(ctx):
    return program.wait_p95_ms(ctx, "submitted", "admitted")
