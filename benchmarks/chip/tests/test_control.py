"""The control of the output check: the reference put in the program's
place, computed in a lower precision (weights rounded per output channel
to int8 or fp8, bfloat16 activations), read at the positions the program
served and judged by the same comparison and limits as the program.

At a size a test run holds, the control goes through ``run_cell``, is
judged there beside the program, and in fp8 reads above the program on
both compared numbers (at two layers of width 64, int8 rounding is too
fine to show). At the cells' own sizes
on the chip, ``control.py`` took the readings the limits were set from;
they are kept in ``tests/data/check_readings.json``, and every program
reading passes the configuration's check while every control reading
fails it."""

import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

import run
from reduced import reduced_cell

CELLS = [w["name"] for w in
         run.load_json(run.ROOT / "BENCHMARK.json")["workloads"]]
READINGS = json.loads((Path(__file__).parent / "data" /
                       "check_readings.json").read_text())


@pytest.mark.parametrize("workload", CELLS)
def test_control_reads_above_the_program(workload):
    cell, cfg = reduced_cell(workload)
    # the configurations' dtype, so that the program's reading is not
    # trivially zero
    cfg = dataclasses.replace(cfg, dtype="bfloat16")
    res = run.run_cell(cell, seed=2**35 + 11, seconds=2.0, trace=False,
                       cfg=cfg, impl="xla_chunked", controls=("int8", "fp8"))
    assert res["correct"] is True
    for q in ("int8", "fp8"):
        ctrl = res["controls"][q]
        assert ctrl["correct"] is run.passes(
            {k: ctrl[k] for k in res["check"]})
        for name, c in res["check"].items():
            assert ctrl[name]["limit"] == c["limit"]
            if q == "fp8":
                assert ctrl[name]["value"] > c["value"], (q, name)


def _judged(conf: dict, reading: dict) -> bool:
    return run.passes({k: {"value": reading[k], "limit": v}
                       for k, v in conf["check"].items()})


@pytest.mark.parametrize("config", sorted(READINGS))
def test_chip_readings_pass_the_program_and_fail_the_control(config):
    conf = run.load_json(run.HERE / "configs" / f"{config}.json")
    rows = READINGS[config]
    assert len({r["seed"] for r in rows["program"]}) >= 12
    for r in rows["program"]:
        assert _judged(conf, r), r
    for q in ("int8", "fp8"):
        assert len(rows[q]) >= 3
        for r in rows[q]:
            assert not _judged(conf, r), (q, r)


def test_check_numbers_widest_and_mean():
    gaps = [np.array([0.0, 0.5]), np.array([0.1])]
    c = run.check_numbers(gaps, {"max_gap": 0.4, "mean_gap": 0.3})
    assert c["max_gap"] == {"value": 0.5, "limit": 0.4}
    assert c["mean_gap"]["value"] == pytest.approx(0.2)
    assert not run.passes(c)
    assert not run.passes(run.check_numbers([], {"max_gap": 1.0}))
