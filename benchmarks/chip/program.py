"""What the program records of itself, read through the requests' handles:
the lifecycle stamps on each ``RequestHandle`` (``submitted``,
``admitted``, ``prefill_start``, on ``time.perf_counter`` like the
benchmark's own clock) and the engine's per-step records
(``engine.utilization.step_records``: start, end and the self time of
each ``serving.*`` phase, on ``time.perf_counter_ns``).

A program that records none of these (an older checkout) gives nothing
here, and each metric that reads it then returns None.
"""

from __future__ import annotations

import numpy as np


def _spans_stall(ctx, a: float, b: float) -> bool:
    """True where [a, b] holds a profiler start or stop (``ctx.stalls``)."""
    return any(a < hi and b > lo for lo, hi in ctx.stalls)


def wait_p95_ms(ctx, frm: str, to: str) -> float | None:
    """95th percentile (ms) over the window's requests of the time from
    stamp ``frm`` to stamp ``to``; a request still without ``to`` counts
    the time until the run stopped waiting (as ``ttft_p95_ms`` does).
    Requests waiting across a profiler start or stop are left out: that
    stall is the measurement's."""
    waits = []
    for r in ctx.recs:
        a = getattr(r.handle, frm, None)
        if a is None:
            continue
        b = getattr(r.handle, to, None)
        b = ctx.end_wait if b is None else b
        if not _spans_stall(ctx, a, b):
            waits.append((b - a) * 1e3)
    return float(np.percentile(waits, 95)) if waits else None


def step_records(ctx) -> list | None:
    """The engine's step records, reached through any request's handle."""
    for r in ctx.recs:
        util = getattr(getattr(r.handle, "engine", None), "utilization", None)
        recs = getattr(util, "step_records", None)
        if recs is not None:
            return list(recs)
    return None


def untraced_steps(ctx) -> list:
    """Step records that start inside the window but outside the traced
    slice (from the end of the profiler's start to the start of its stop),
    so that none carries the profiler's own cost."""
    recs = step_records(ctx) or []
    lo = ctx.stalls[0][1] if ctx.stalls else float("inf")
    hi = ctx.stalls[1][0] if len(ctx.stalls) > 1 else float("inf")
    out = []
    for s in recs:
        t = s.start_ns / 1e9
        if ctx.origin <= t < ctx.window_end and not lo <= t < hi:
            out.append(s)
    return out


def host_busy_ms_per_step(ctx) -> float | None:
    """Mean over the untraced steps of the step's length less its
    ``serving.sync`` time (ms): the host's own work per engine step."""
    steps = untraced_steps(ctx)
    if not steps:
        return None
    busy = [s.end_ns - s.start_ns - s.phases.get("sync", 0) for s in steps]
    return float(np.mean(busy)) / 1e6
