"""Reduction of a profiler trace (``.xplane.pb``) to device and host numbers.

Read with ``jax.profiler.ProfileData`` only. What is taken from it:

* device op events: every event on the ``XLA Ops`` line of each device
  plane (``/device:TPU:n``), with its name, start and end in ns;
* host spans: the benchmark's own ``jax.profiler.TraceAnnotation`` spans
  (names starting ``bench.``) from the host planes.

From those :func:`summarize` gives, over the window from the first
benchmark span to the last, the device's busy time (the union of op
intervals), the time of each op name, the idle gaps between ops named by
the innermost benchmark span the host was in at the gap's middle (or
``host:outside`` where it was in none), and for each span the device time
that ran inside it. Kernel metrics sum the events whose name matches their
own patterns (:func:`op_time`).
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

import numpy as np

DEVICE_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
# ops that contain other ops on the same line (a scan's loop, a call):
# they count towards busy time, not among the top ops
CONTAINERS = ("while", "call", "conditional")
# "%name = <result type> kind(operands...)..." as the TPU trace prints ops
_OP = re.compile(r"^(%?[\w.\-]+) = (.*?) ([a-z][\w\-]*)\(")


def short_name(name: str) -> tuple[str, str]:
    """(short name, op kind) of a trace op: its HLO name, kind and result
    type cut to 60 characters (the full text is the whole instruction)."""
    m = _OP.match(name)
    if not m:
        return name[:100], ""
    return f"{m.group(1)} {m.group(3)} {m.group(2)[:60]}", m.group(3)


@dataclass
class Trace:
    """Events of one traced slice; times in ns on the trace's clock."""

    ops: list[tuple[str, int, int]]            # (name, start, end) device
    spans: list[tuple[str, int, int]]          # (name, start, end) host
    devices: int = 1
    op_stats: dict = field(default_factory=dict)  # name -> stats of 1st event


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def _stat_dict(stats) -> dict:
    out = {}
    for item in stats:
        try:
            k, v = item
        except (TypeError, ValueError):
            continue
        out[str(k)] = v
    return out


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, spans, stats = [], [], {}
    devices = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            if DEVICE_LINE not in lines:
                continue
            devices += 1
            for ev in lines[DEVICE_LINE].events:
                s = int(ev.start_ns)
                ops.append((ev.name, s, s + int(ev.duration_ns)))
                if ev.name not in stats:
                    stats[ev.name] = _stat_dict(ev.stats)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = int(ev.start_ns)
                        spans.append((ev.name, s, s + int(ev.duration_ns)))
    return Trace(ops=ops, spans=spans, devices=max(devices, 1), op_stats=stats)


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Merged, sorted intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged: list[tuple[int, int]], starts: np.ndarray, lo: int,
            hi: int) -> int:
    """ns of [lo, hi) covered by ``merged`` (sorted, disjoint, with
    ``starts`` its start times as an array)."""
    if not merged:
        return 0
    i = max(int(np.searchsorted(starts, lo, "right")) - 1, 0)
    tot = 0
    while i < len(merged) and merged[i][0] < hi:
        s, e = merged[i]
        tot += max(0, min(e, hi) - max(s, lo))
        i += 1
    return tot


def _innermost(spans, t: int) -> str:
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "host:outside"


@dataclass
class Summary:
    window_ns: int
    busy_ns: int                       # per device, averaged over devices
    op_ns: dict[str, int]              # summed over devices
    idle_gaps: list[tuple[str, int]]   # longest gaps, named by host span
    span_ns: dict[str, list[int]]      # span name -> durations
    span_busy_ns: dict[str, list[int]]  # span name -> device ns inside each


def summarize(tr: Trace, top: int = 10) -> Summary:
    ops = tr.ops
    if not ops:
        raise ValueError("the trace holds no device op")
    spans = tr.spans
    # the window runs from the first benchmark span to the last: the
    # profiler's own start and stop lie outside it
    ends = spans or ops
    lo = min(s for _, s, _ in ends)
    hi = max(e for _, _, e in ends)
    merged = union([(max(s, lo), min(e, hi)) for _, s, e in ops
                    if e > lo and s < hi])
    busy = sum(e - s for s, e in merged)
    op_ns: dict[str, int] = {}
    for name, s, e in ops:
        op_ns[name] = op_ns.get(name, 0) + (e - s)
    gaps = []
    prev = lo
    for s, e in merged:
        if s > prev:
            gaps.append((s - prev, prev, s))
        prev = max(prev, e)
    if hi > prev:
        gaps.append((hi - prev, prev, hi))
    gaps.sort(reverse=True)
    named = [(_innermost(spans, (a + b) // 2), g) for g, a, b in gaps[:top]]
    starts = np.fromiter((s for s, _ in merged), np.int64, len(merged))
    span_ns: dict[str, list[int]] = {}
    span_busy: dict[str, list[int]] = {}
    for name, s, e in spans:
        span_ns.setdefault(name, []).append(e - s)
        span_busy.setdefault(name, []).append(covered(merged, starts, s, e))
    return Summary(window_ns=hi - lo, busy_ns=busy // tr.devices, op_ns=op_ns,
                   idle_gaps=named, span_ns=span_ns, span_busy_ns=span_busy)


def op_time(tr: Trace, patterns: list[str]) -> tuple[int, int]:
    """(summed ns, event count) of device ops whose name, or one of whose
    string stats, matches any of ``patterns``."""
    rx = [re.compile(p) for p in patterns]
    keep = {}
    for name, st in tr.op_stats.items():
        texts = [name] + [v for v in st.values() if isinstance(v, str)]
        keep[name] = any(r.search(t) for r in rx for t in texts)
    ns = n = 0
    for name, s, e in tr.ops:
        if keep.get(name):
            ns += e - s
            n += 1
    return ns, n


def top_ops(sm: Summary, top: int = 10) -> list[tuple[str, int]]:
    """The ``top`` op names by device time, containers left out, each with
    its summed ns, under its short name."""
    merged: dict[str, int] = {}
    for name, ns in sm.op_ns.items():
        short, kind = short_name(name)
        if kind not in CONTAINERS:
            merged[short] = merged.get(short, 0) + ns
    return sorted(merged.items(), key=lambda kv: -kv[1])[:top]


def breakdown(sm: Summary, top: int = 10) -> dict:
    return {
        "device_ops": [[n, ns / 1e9] for n, ns in top_ops(sm, top)],
        "idle_gaps": [[n, ns / 1e9] for n, ns in sm.idle_gaps[:top]],
    }


def describe(tr: Trace, limit: int = 40) -> str:
    """A human-readable look at the trace: the longest ops with their
    stats, and the span names (for writing kernel patterns)."""
    sm = summarize(tr)
    lines = [f"devices={tr.devices} ops={len(tr.ops)} spans={len(tr.spans)} "
             f"window_ms={sm.window_ns / 1e6:.3f} busy_ms={sm.busy_ns / 1e6:.3f}"]
    for name, ns in sorted(sm.op_ns.items(), key=lambda kv: -kv[1])[:limit]:
        lines.append(f"op {ns / 1e6:10.3f} ms  {name[:400]}")
    for name, d in sm.span_ns.items():
        lines.append(f"span {name}: n={len(d)} total_ms={sum(d) / 1e6:.3f}")
    return "\n".join(lines)
