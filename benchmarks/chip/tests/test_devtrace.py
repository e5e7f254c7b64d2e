"""devtrace.py: the reduction from trace events to busy time, idle gaps,
per-span device time and kernel time, on hand-made events with known
answers, and the loader on a small trace recorded on a TPU v5e (three
``bench.step`` spans, each a jitted bf16 matmul of about 25 us followed by
a 2 ms host sleep, then a ``bench.generate`` span with a 1 ms sleep). In
that trace the device's ops sit about 1 ms before the host spans that
launched them: the two clocks agree to about a millisecond only."""

from pathlib import Path

import pytest

import devtrace
from devtrace import Trace

RECORDED = Path(__file__).resolve().parent / "data" / "v5e_three_steps.xplane.pb"

OP = '%closed_call.1 = f32[8,64,64,128]{3,2,1,0} custom-call(f32[8,64,64,128] %x), custom_call_target="tpu_custom_call"'


def hand_trace():
    # ns: two steps of 100 each; ops inside them with a nested loop op
    ops = [
        ("%while.1 = (s32[]) while((s32[]) %t)", 10, 60),
        (OP, 15, 25),
        (OP, 30, 45),
        ("%fusion.2 = bf16[4]{0} fusion(bf16[4] %a)", 60, 70),
        ("%fusion.2 = bf16[4]{0} fusion(bf16[4] %a)", 120, 190),
    ]
    spans = [("bench.step", 0, 100), ("bench.execute", 5, 95),
             ("bench.generate", 100, 110), ("bench.step", 110, 200)]
    return Trace(ops=ops, spans=spans, devices=1,
                 op_stats={n: {} for n, _, _ in ops})


def test_summary_busy_gaps_and_spans():
    sm = devtrace.summarize(hand_trace())
    assert sm.window_ns == 200
    # union: [10, 70) and [120, 190)
    assert sm.busy_ns == 60 + 70
    # gaps [0,10), [70,120), [190,200), named by the innermost span at
    # their middles 5 (bench.execute), 95 (bench.step), 195 (bench.step)
    assert sm.idle_gaps[0] == ("bench.step", 50)
    assert ("bench.execute", 10) in sm.idle_gaps
    assert sorted(g for _, g in sm.idle_gaps) == [10, 10, 50]
    assert sm.span_ns["bench.step"] == [100, 90]
    assert sm.span_busy_ns["bench.step"] == [60, 70]
    assert sm.span_busy_ns["bench.generate"] == [0]


def test_op_time_and_breakdown():
    tr = hand_trace()
    ns, n = devtrace.op_time(tr, [r'custom_call_target="tpu_custom_call"'])
    assert (ns, n) == (25, 2)
    ns, n = devtrace.op_time(tr, [r"custom-call\(f32\[\d+,\d+,\d+,\d+\]"])
    assert (ns, n) == (25, 2)
    bd = devtrace.breakdown(devtrace.summarize(tr))
    names = [n for n, _ in bd["device_ops"]]
    # the loop contains other ops: it counts for busy time, not as an op
    assert not any(" while " in n for n in names)
    assert bd["device_ops"][0] == ["%fusion.2 fusion bf16[4]{0}", 80e-9]


def test_no_device_ops_is_an_error():
    with pytest.raises(ValueError):
        devtrace.summarize(Trace(ops=[], spans=[("bench.step", 0, 5)]))


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_v5e_trace():
    tr = devtrace.load(str(RECORDED))
    assert tr.devices == 1 and tr.ops
    names = [n for n, _, _ in tr.spans]
    assert names.count("bench.step") == 3
    assert names.count("bench.generate") == 3
    assert all(" = " in n for n, _, _ in tr.ops)  # whole HLO instructions
    sm = devtrace.summarize(tr)
    # three matmuls of ~25 us in a window of ~13 ms of host sleeps: the
    # device is busy a few hundredths of the window, in short stretches
    assert 0 < sm.busy_ns < 0.02 * sm.window_ns
    assert 12e6 < sm.window_ns < 15e6
    assert sm.idle_gaps[0][1] >= 2_000_000
    inside = (sum(sm.span_busy_ns["bench.step"])
              + sum(sm.span_busy_ns["bench.generate"]))
    assert 0 < inside <= sm.busy_ns
