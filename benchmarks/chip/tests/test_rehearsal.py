"""Each cell's generator, driver loop, metric files and check, end to end
on the CPU at a reduced size. On the CPU no device metric may come out:
the traced run has no device plane to read."""

import json

import pytest

import run
from reduced import run_reduced

CELLS = [w["name"] for w in
         run.load_json(run.ROOT / "BENCHMARK.json")["workloads"]]
DEVICE_SOURCES = {"device_trace"}


def _metric(name):
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    return {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}[name]


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearsal(workload, trace):
    res = run.order_result(run_reduced(workload, trace=trace))
    json.dumps(res)  # the result line is JSON
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "check"
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    assert "busy_s" not in res["device"] and "breakdown" not in res
    for name in res["metrics"]:
        assert _metric(name)["source"] not in DEVICE_SOURCES, name
    if not trace:
        assert "setup_s" in res["metrics"]


def test_no_chip_no_result():
    """On the CPU the command exits non-zero and prints no result line."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(run.HERE / "run.py"), "--workload", CELLS[0],
         "--seed", str(2**40), "--seconds", "10", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout
