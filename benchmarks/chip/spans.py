#!/usr/bin/env python3
"""The program's own spans in a kept trace (``run.py --trace-dir``):

    python3 benchmarks/chip/spans.py <trace dir> [<trace dir> ...]

For each trace, one JSON line with:

* ``phases``: ms per ``serving.step`` of each ``serving.*`` phase's self
  time (its length less that of the ``serving.*`` spans nested in it);
* ``outside_step``: how many phase spans lie outside every
  ``serving.step`` span of their thread (0 when the nesting holds);
* ``self_cover``: the phases' summed self time over the step's length,
  the lowest and the median over the traced steps, and
  ``spans_per_step``, the phase spans a step holds on average;
* ``offset_ms``: how far the device's clock runs behind the host's, as
  quartiles of a lower and an upper bound per host quiet interval (a
  ``serving.sync`` end to the next ``serving.dispatch`` start, when the
  device has nothing queued) and the device idle gap it holds;
* ``idle_gaps``: the longest idle gaps of the device between the first and
  the last ``bench.*`` span, each named by the innermost ``serving.*``
  span at its middle on the raw clocks, and again with the offset (the
  middle of the two bounds' medians) applied;
* ``kernels``: the events of each named Pallas kernel, and the events the
  kernel metrics' patterns match;
* ``op_name_metadata``: whether any device op event carries the framework
  op names (``op_name``) that ``jax.named_scope`` would set, in its text or
  its stats.
"""

from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import devtrace  # noqa: E402

PREFIX = "serving."
KERNELS = ("paged_decode_attention", "paged_prefill_attention",
           "paged_mixed_attention", "ssd_chunk_scan", "ssd_decode_step",
           "flash_attention")
KERNEL_METRICS = ("paged_attn_roofline", "ssd_decode_roofline")


def load_spans(path: str) -> list[tuple[str, int, int, int]]:
    """(name, start, end, thread line) of every ``serving.*`` and
    ``bench.*`` span on the host planes."""
    from jax.profiler import ProfileData

    out = []
    line_id = 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            line_id += 1
            for ev in ln.events:
                if ev.name.startswith((PREFIX, devtrace.SPAN_PREFIX)):
                    s = int(ev.start_ns)
                    out.append((ev.name, s, s + int(ev.duration_ns), line_id))
    return out


def _self_times(spans) -> list[tuple[str, int]]:
    """(name, self ns) of nested spans of one thread: each span's length
    less that of the spans directly inside it."""
    out, stack = [], []
    for name, s, e, _ in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            out.append(tuple(stack.pop()[::3]))
        if stack:
            stack[-1][3] -= e - s
        stack.append([name, s, e, e - s])
    out += [(n, ns) for n, _, _, ns in stack]
    return out


def step_phases(spans) -> tuple[list[dict], int]:
    """Per ``serving.step``: its length and each phase's self time (ns);
    and the count of phase spans outside every step of their thread."""
    serving = [s for s in spans if s[0].startswith(PREFIX)]
    steps = [s for s in serving if s[0] == PREFIX + "step"]
    phases = [s for s in serving if s[0] != PREFIX + "step"]
    placed = 0
    out = []
    for _, s0, e0, line in steps:
        mine = [p for p in phases if p[3] == line and s0 <= p[1] and p[2] <= e0]
        placed += len(mine)
        rec = {"step_ns": e0 - s0, "spans": len(mine), "phases": {}}
        for name, ns in _self_times(mine):
            name = name[len(PREFIX):]
            rec["phases"][name] = rec["phases"].get(name, 0) + ns
        out.append(rec)
    return out, len(phases) - placed


def _quiet(spans) -> list[tuple[int, int]]:
    """Host intervals from a ``serving.sync`` end to the next
    ``serving.dispatch`` start on the same thread."""
    out = []
    by_line: dict[int, list] = {}
    for name, s, e, line in spans:
        if name in (PREFIX + "sync", PREFIX + "dispatch"):
            by_line.setdefault(line, []).append((s, e, name))
    for evs in by_line.values():
        evs.sort()
        for (s, e, n), (s2, _, n2) in zip(evs, evs[1:]):
            if n == PREFIX + "sync" and n2 == PREFIX + "dispatch" and s2 > e:
                out.append((e, s2))
    return out


def clock_offset_bounds(spans, gaps, near_ns: int = 5_000_000,
                        least_ns: int = 250_000) -> list[tuple[int, int]]:
    """Bounds on how far the device's clock runs behind the host's (host
    time = device time + offset), one pair per host quiet interval of
    0.5 ms or more matched with the device idle gap of ``least_ns`` or
    more nearest it: the device cannot end its work after the host's
    ``serving.sync`` returned (offset <= sync end - last op end), nor start
    the next before the host's ``serving.dispatch`` began (offset >=
    dispatch start - next op start)."""
    gaps = [g for g in gaps if g[1] - g[0] >= least_ns]
    if not gaps:
        return []
    mids = np.array([(a + b) / 2 for a, b in gaps])
    out = []
    for e, a in _quiet(spans):
        if a - e < 500_000:
            continue
        i = int(np.argmin(np.abs(mids - (e + a) / 2)))
        if abs(mids[i] - (e + a) / 2) <= near_ns:
            c, b = gaps[i]
            out.append((a - b, e - c))
    return out


def device_gaps(tr, spans) -> list[tuple[int, int]]:
    """Idle intervals of the device between the first and the last
    ``bench.*`` span (the window ``devtrace.summarize`` reads)."""
    bench = [(s, e) for n, s, e, _ in spans
             if n.startswith(devtrace.SPAN_PREFIX)] or [
                 (s, e) for _, s, e in tr.ops]
    lo, hi = min(s for s, _ in bench), max(e for _, e in bench)
    merged = devtrace.union([(max(s, lo), min(e, hi)) for _, s, e in tr.ops
                             if e > lo and s < hi])
    gaps, prev = [], lo
    for s, e in merged:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        gaps.append((prev, hi))
    return gaps


def kernel_events(tr) -> dict:
    out = {k: sum(1 for n, _, _ in tr.ops if re.match(rf"%{k}(\.\d+)? ", n))
           for k in KERNELS}
    for m in KERNEL_METRICS:
        spec = importlib.util.spec_from_file_location(
            m, HERE / "metrics" / f"{m}.py")
        metric = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(metric)
        out[m] = devtrace.op_time(tr, metric.PATTERNS)[1]
    return out


def has_op_names(tr) -> bool:
    return any("op_name" in n or any("op_name" in str(k) or "op_name" in str(v)
                                     for k, v in st.items())
               for n, st in tr.op_stats.items())


def analyse(path: str, top: int = 10) -> dict:
    tr = devtrace.load(path)
    spans = load_spans(path)
    steps, outside = step_phases(spans)
    res: dict = {"trace": path, "steps": len(steps), "outside_step": outside}
    if steps:
        names = sorted({k for s in steps for k in s["phases"]})
        res["phases"] = {"step": float(np.mean([s["step_ns"] for s in steps]))
                         / 1e6}
        for k in names:
            res["phases"][k] = float(np.mean(
                [s["phases"].get(k, 0) for s in steps])) / 1e6
        cover = [sum(s["phases"].values()) / s["step_ns"] for s in steps
                 if s["step_ns"] > 0]
        res["self_cover"] = {"min": float(min(cover)),
                             "median": float(np.median(cover))}
        res["spans_per_step"] = float(np.mean([s["spans"] for s in steps]))
    if tr.ops:
        gaps = device_gaps(tr, spans)
        bounds = clock_offset_bounds(spans, gaps)
        d = 0.0
        if bounds:
            low, up = np.array(bounds, float).T / 1e6
            res["offset_ms"] = {
                "low": [float(x) for x in np.percentile(low, [25, 50, 75])],
                "up": [float(x) for x in np.percentile(up, [25, 50, 75])],
                "n": len(bounds)}
            d = 1e6 * float(np.median(low) + np.median(up)) / 2
        longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
        mine = [(n, s, e) for n, s, e, _ in spans if n.startswith(PREFIX)]
        res["idle_gaps"] = [
            [(b - a) / 1e6, devtrace._innermost(mine, (a + b) / 2),
             devtrace._innermost(mine, (a + b) / 2 + d)] for a, b in longest]
        res["kernels"] = kernel_events(tr)
        res["op_name_metadata"] = has_op_names(tr)
    return res


def main(argv: list[str]) -> int:
    for d in argv:
        print(json.dumps(analyse(devtrace.find_xplane(d))), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
