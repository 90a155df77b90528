"""Reads, on the chip and at a cell's own size, the numbers that a
limit of `correct` is set from: over a list of seeds, what the program
gives and what the control gives (the plain reference in the program's
place, one precision below the one the configuration states).

    python3 benchmark/control.py --workload <name> --seeds 1,2,3 \
        [--seconds <s>]

One process for all the seeds, so that set-up is paid once.  The
benchmark's own runs never call this; PERF.md holds the readings.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=30.0)
    args = p.parse_args(argv)
    cell, runner, devices, _ = harness.open_cell(args.workload)
    rows = runner.control(cell, seeds=[int(s) for s in args.seeds.split(",")],
                          seconds=args.seconds, devices=devices)
    summary = {}
    for key in rows[0]:
        if key.startswith("program."):
            summary[key + ".largest"] = max(r[key] for r in rows)
        elif key.startswith("control."):
            summary[key + ".smallest"] = min(r[key] for r in rows)
    print(json.dumps({"workload": cell.name, "seeds": len(rows), **summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
