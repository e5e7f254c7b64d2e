"""Executor and host, the paged engine's open-loop cells: mean over the
traced engine steps (``bench.step`` spans) of the step's length less the
device time inside it: the host's scheduling, packing, dispatch and
synchronisation per step."""

NAME, UNIT = "host_ms_per_step.paged", "ms"


def read(ctx):
    if ctx.loop != "open" or ctx.kind != "paged" or ctx.summary is None:
        return None
    dur = ctx.summary.span_ns.get("bench.step", [])
    busy = ctx.summary.span_busy_ns.get("bench.step", [])
    if not dur:
        return None
    return sum(d - b for d, b in zip(dur, busy)) / len(dur) / 1e6
