"""Model step, closed-loop cells: model FLOPs of the rows the traced steps
processed (``flops.step_flops`` of each executor call; padded rows count
nothing) over the time inside the ``bench.step`` spans times the chip's
peak. The whole step's share of the chip, which bounds every kernel's."""

import flops

NAME, UNIT = "step_mfu.batch", "%"


def read(ctx):
    if ctx.loop != "closed" or ctx.summary is None or not ctx.exec_log:
        return None
    dur = ctx.summary.span_ns.get("bench.step", [])
    if not dur:
        return None
    work = sum(flops.step_flops(ctx.dm, r) for r in ctx.exec_log)
    return 100.0 * work / (sum(dur) / 1e9 * ctx.peak["flops_bf16"])
