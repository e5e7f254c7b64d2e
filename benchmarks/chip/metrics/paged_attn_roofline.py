"""Kernels: the paged attention kernels' share of their roofline. For each
traced executor call, one call per layer, the least time is the larger of
its FLOPs over the chip's peak and its least bytes (each sequence's
unpadded keys and values once, queries and outputs) over HBM bandwidth;
the share is their sum over the kernels' device time in the trace. The
kernel events are matched by the names below."""

import flops
import devtrace

NAME, UNIT = "paged_attn_roofline", "%"
PATTERNS = [r'custom_call_target="tpu_custom_call"']


def least_time(ctx):
    pf, pb = ctx.peak["flops_bf16"], ctx.peak["hbm_bytes_per_s"]
    t = 0.0
    for rec in ctx.exec_log:
        if not rec.get("decode_ctx") and not rec.get("chunk"):
            continue
        f, b = flops.paged_attention_call(ctx.dm, rec)
        t += ctx.dm["layers"] * max(f / pf, b / pb)
    return t


def read(ctx):
    if ctx.trace is None or "kv_heads" not in ctx.dm:
        return None
    ns, n = devtrace.op_time(ctx.trace, PATTERNS)
    if not n:
        return None
    return 100.0 * least_time(ctx) / (ns / 1e9)
