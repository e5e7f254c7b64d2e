"""The metrics that read what the program records of itself
(``program.py``: lifecycle stamps on the handles, the engine's step
records) and the reading of its ``serving.*`` spans in a kept trace
(``spans.py``), on CPU rehearsals at a reduced size and on hand-made
spans with known answers."""

import math
from pathlib import Path

import pytest

import devtrace
import run
import spans
from reduced import run_reduced

BENCH = run.load_json(run.ROOT / "BENCHMARK.json")
PROGRAM = {m["name"]: m["workloads"] for m in BENCH["per_layer"]
           if m["source"] == "program_counter"
           and m["name"].startswith(("queue_wait", "prefill_wait",
                                     "host_busy"))}
RECORDED = Path(__file__).resolve().parent / "data" / "v5e_three_steps.xplane.pb"


def test_five_program_metrics_declared():
    assert sorted(PROGRAM) == [
        "host_busy_ms_per_step.batch", "host_busy_ms_per_step.paged",
        "host_busy_ms_per_step.ssm", "prefill_wait_p95_ms",
        "queue_wait_p95_ms"]


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_program_metrics_read_in_traced_rehearsal(workload, tmp_path):
    res = run_reduced(workload, trace=True, trace_dir=str(tmp_path))
    assert res["correct"] is True
    mine = [n for n, cells in PROGRAM.items() if workload in cells]
    assert mine
    for name in mine:
        v = res["metrics"][name]["value"]
        assert math.isfinite(v) and v >= 0, name
    # the kept trace holds the engine's steps with every phase inside one
    got = spans.analyse(devtrace.find_xplane(str(tmp_path)))
    assert got["steps"] > 0 and got["outside_step"] == 0
    assert {"admit", "plan", "dispatch", "sync", "deliver"} <= set(
        got["phases"])
    # a reduced step takes about a millisecond on the CPU, so the few
    # microseconds each span costs between phases weigh more than on the
    # chip's 50-180 ms steps
    assert got["self_cover"]["median"] >= 0.8


def test_ttft_splits_into_program_stamps(monkeypatch):
    """gen lag + queue wait + prefill wait + (prefill_start -> first token)
    is the benchmark's TTFT, less the rest of the step after the engine
    stamped the token (and the submit call's own time)."""
    seen, make = [], run.Ctx

    def keep(**kw):
        seen.append(make(**kw))
        return seen[-1]

    monkeypatch.setattr(run, "Ctx", keep)
    run_reduced("mamba2-1.3b.chat-burst", trace=False)
    (ctx,) = seen
    recs = [r for r in ctx.recs if r.first is not None]
    assert recs
    for r in recs:
        h = r.handle
        emitted = h.arrival + h.ttft
        parts = ((r.submit - r.due) + (h.admitted - h.submitted)
                 + (h.prefill_start - h.admitted)
                 + (emitted - h.prefill_start))
        rest = r.first - emitted
        assert h.arrival == r.due and rest >= 0
        assert 0 <= (r.first - r.due) - parts - rest < 2e-3, r.item.uid


def test_parent_program_reads_nothing():
    """A program without stamps or step records (an older checkout) gives
    None, and no error, in every metric that reads them."""

    class Handle:
        pass

    class Rec:
        handle = Handle()

    ctx = run.Ctx(loop="open", kind="ssm", seconds=1.0, dm={}, peak={},
                  origin=0.0, window_end=1.0, setup_s=0.0, end_wait=1.0,
                  recs=[Rec()], stamps=[], gaps=[], c0={}, c1={},
                  slot_samples=[], stalls=[(0.2, 0.3), (0.6, 0.7)])
    for name in PROGRAM:
        mod = run.load_module(run.HERE / "metrics" / f"{name}.py", name)
        assert mod.read(ctx) is None, name


def test_self_times_and_nesting():
    ev = [("serving.step", 0, 100, 1), ("serving.admit", 0, 10, 1),
          ("serving.tier_prefetch", 2, 6, 1), ("serving.dispatch", 20, 30, 1),
          ("serving.sync", 30, 90, 1), ("serving.deliver", 90, 98, 1),
          ("serving.plan", 150, 160, 1), ("serving.sync", 40, 50, 2)]
    steps, outside = spans.step_phases(ev)
    # the plan after the step and the sync on another thread
    assert outside == 2
    (rec,) = steps
    assert rec["step_ns"] == 100 and rec["spans"] == 5
    assert rec["phases"] == {"admit": 6, "tier_prefetch": 4, "dispatch": 10,
                             "sync": 60, "deliver": 8}


def test_clock_offset_bounds_from_quiet_intervals():
    # host: sync ends at 10 ms, the next dispatch starts at 13 ms (and so
    # on every 50 ms); the device's clock runs 1 ms behind: it ends at
    # 8.5 and starts again at 11.8 (host 9.5 and 12.8); a tiny gap beside
    # it matches nothing
    ms = 1_000_000
    ev, gaps = [], []
    for k in range(4):
        t = 50 * k * ms
        ev += [("serving.sync", t + 5 * ms, t + 10 * ms, 1),
               ("serving.dispatch", t + 13 * ms, t + 14 * ms, 1)]
        gaps += [(t + 8 * ms, t + 8 * ms + 1000),
                 (t + 8.5 * ms, t + 11.8 * ms)]
    got = spans.clock_offset_bounds(ev, gaps)
    assert got == [(1.2 * ms, 1.5 * ms)] * 4


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_v5e_ops_carry_no_op_names():
    """On the chip's trace an XLA op event is its HLO instruction without
    metadata, and its stats are times only: a ``jax.named_scope`` would
    reach nothing that a metric reads."""
    got = spans.analyse(str(RECORDED))
    assert got["op_name_metadata"] is False
    assert got["steps"] == 0 and got["kernels"]["ssd_decode_step"] == 0
