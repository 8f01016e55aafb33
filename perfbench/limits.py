"""Readings that the limits of ``correct`` are set from, for one cell:
the numbers compared on sound runs of the program over several seeds, on
the control (the reference in the precision below the configuration's,
in the program's place) and on each planted fault.

    python3 perfbench/limits.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 4,5,6 [--fault-seeds 7,8,9] \
        [--seconds 2]

Every run is in this one process, on the machine's chip.  Prints one JSON
line per run and, last, for each number the largest sound reading, the
smallest control reading and the smallest reading of each fault.
"""

from __future__ import annotations

import argparse
import json
import sys

import faults
import harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    seeds = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    cell = harness.resolve_cell(harness.load_benchmark(), args.workload)
    harness.require_chips(cell.chips)
    harness.enable_compile_cache()
    loop = harness.loop_for(cell.traffic)
    rows = []

    def record(kind, seed, checks, extra=None):
        row = {"kind": kind, "seed": seed,
               "numbers": {c.name: c.value for c in checks}, **(extra or {})}
        rows.append(row)
        print(json.dumps(row), flush=True)

    for seed in seeds(args.seeds):
        run = harness.Run(cell, seed, args.seconds, False)
        win, checks, correct = harness.drive(run, loop)
        record("program", seed, checks, {"correct": correct,
                                         "attempted": win.attempted})
    for seed in seeds(args.control_seeds):
        run = harness.Run(cell, seed, args.seconds, False)
        record("control", seed, loop.control(run))
    for fault in faults.BY_LOOP[cell.traffic["loop"]]:
        for seed in seeds(args.fault_seeds):
            run = harness.Run(cell, seed, args.seconds, False)
            with fault():
                _, checks, correct = harness.drive(run, loop)
            record(fault.__name__, seed, checks, {"correct": correct})

    summary = {}
    for row in rows:
        for name, v in row["numbers"].items():
            s = summary.setdefault(name, {})
            if row["kind"] == "program":
                s["sound_max"] = max(s.get("sound_max", 0.0), v)
            else:
                s[row["kind"] + "_min"] = min(s.get(row["kind"] + "_min",
                                                    float("inf")), v)
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
