#!/usr/bin/env python3
"""Readings for the output check's limit, on the chip at the cell's size:

    python3 benchmarks/chip/control.py --workload <name> --seeds 101 102 ... \\
        --seconds 10 [--controls int8 fp8]

For each seed, in one process, the cell runs as ``run.py`` runs it (with a
short window at the cell's own load) and prints the compared numbers of
its served tokens against the float32 reference (the program's reading),
and for each control precision the same numbers of the token that the
reference, computed in that precision, puts first at the same positions,
judged by the same limits (the control's reading). Each limit in the
configuration's ``check`` lies between the largest program reading and the
smallest control reading. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0,
                    help="window; a closed loop needs the cell's own to "
                         "finish requests")
    ap.add_argument("--controls", nargs="*", default=["int8", "fp8"])
    args = ap.parse_args(argv)
    cell = run.find_cell(run.load_json(run.ROOT / "BENCHMARK.json"),
                         args.workload)
    if not run.prepare(cell):
        return 2
    rows = []
    for seed in args.seeds:
        res = run.run_cell(cell, seed=seed, seconds=args.seconds, trace=False,
                           controls=tuple(args.controls))
        row = {"seed": seed, "correct": res["correct"],
               "program": {k: c["value"] for k, c in res["check"].items()},
               "attempted": res["attempted"], "failed": res["failed"]}
        for q, c in res.get("controls", {}).items():
            row[q] = {"correct": c["correct"],
                      **{k: c[k]["value"] for k in res["check"]}}
        print("reading " + json.dumps(row), flush=True)
        if None not in row["program"].values():  # a request to compare
            rows.append(row)
    summary = {"workload": args.workload, "seeds": len(rows)}
    for k in cell.conf["check"]:
        if rows:
            summary[f"program_max.{k}"] = max(r["program"][k] for r in rows)
            for q in args.controls:
                summary[f"{q}_min.{k}"] = min(r[q][k] for r in rows)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
