"""Executor and host, the paged engine's open-loop cells: mean over the
engine's own step records (``serving.step``) that start in the window
but outside the traced slice of the step's length less its
``serving.sync`` time: the host's admission, planning, packing,
dispatch and delivery per step, read with the profiler off (the
in-program counterpart of ``host_ms_per_step.paged``)."""

import program

NAME, UNIT = "host_busy_ms_per_step.paged", "ms"


def read(ctx):
    if ctx.loop != "open" or ctx.kind != "paged":
        return None
    return program.host_busy_ms_per_step(ctx)
