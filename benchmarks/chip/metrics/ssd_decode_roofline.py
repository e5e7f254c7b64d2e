"""Kernels: the SSD decode step kernel's share of its roofline. For each
traced decode call, one kernel call per layer, the least time is the
larger of its FLOPs over the chip's peak and its least bytes (the float32
state of each decoding slot read and written, and its per-token inputs
and outputs) over HBM bandwidth; the share is their sum over the kernel's
device time in the trace. The kernel events are matched by the pattern
below, on the instruction text the trace prints: the Pallas call whose
first operand is the 4-D float32 state (slots, heads, head_dim, state),
which the SSD scan kernel (first operand bfloat16 x) never has."""

import flops
import devtrace

NAME, UNIT = "ssd_decode_roofline", "%"
PATTERNS = [r"custom-call\(f32\[\d+,\d+,\d+,\d+\]"]


def read(ctx):
    if ctx.trace is None or "state" not in ctx.dm:
        return None
    ns, n = devtrace.op_time(ctx.trace, PATTERNS)
    if not n:
        return None
    pf, pb = ctx.peak["flops_bf16"], ctx.peak["hbm_bytes_per_s"]
    t = 0.0
    for rec in ctx.exec_log:
        if "decode_active" in rec:
            f, b = flops.ssd_decode_call(ctx.dm, rec)
            t += ctx.dm["layers"] * max(f / pf, b / pb)
    return 100.0 * t / (ns / 1e9)
