"""Finds, once, the highest arrival rate an open-loop cell sustains:
the cell's own traffic at each of a list of rates, one window each, in
one process.  A rate is sustained when the requests that complete in
the window's second half keep up with those that fall due in it (19
of 20, since a count of some dozens swings by one or two) and the
median wait for a first token does not grow from the first half to
the second.  The
knee and the rate chosen below it are then written into the traffic
file by hand; the benchmark's runs never search.

    python3 benchmark/sweep.py --workload <name> --rates 1.6,2.0,2.4 \
        --seconds 40 --seed 5
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=40.0)
    p.add_argument("--seed", type=int, default=5)
    args = p.parse_args(argv)
    cell, serve, devices, _ = harness.open_cell(args.workload)
    for rate in (float(r) for r in args.rates.split(",")):
        traffic = serve.merged(cell.traffic,
                               {"arrivals": {"rate_per_s": rate}})
        clock = time.perf_counter
        got = serve.serve_once(cell, cell.config, traffic, seed=args.seed,
                               seconds=args.seconds, devices=devices,
                               clock=clock, spans=harness.Spans(clock))
        t0, t1 = got["window"]
        half = (t0 + t1) / 2
        records = got["records"].values()
        due = sum(1 for r in records if half <= r["due"] < t1)
        done = sum(1 for r in records
                   if r["finished"] is not None and half <= r["finished"] < t1)
        m = serve.serve_metrics(got["records"], got["window"])

        def ttft(lo, hi):
            return harness.median(
                [(r["token_times"][0] - r["due"]) * 1e3
                 if r["token_times"] else float("inf")
                 for r in records if lo <= r["due"] < hi])

        unfinished = sum(1 for r in records if r["due"] < t1 and (
            r["finished"] is None or r["finished"] > t1))
        print(json.dumps({
            "rate_per_s": rate, "second_half_due": due,
            "second_half_finished": done, "keeps_up": done >= 0.95 * due,
            "unfinished_at_end": unfinished,
            "ttft_p50_ms.first_half": ttft(t0, half),
            "ttft_p50_ms.second_half": ttft(half, t1),
            "ttft_p90_ms": m["ttft_p90_ms"], "tpot_mean_ms": m["tpot_mean_ms"],
            "tpot_p50_ms": m["tpot_p50_ms"], "tpot_p90_ms": m["tpot_p90_ms"],
            "out_tok_per_s": m["out_tok_per_s"],
            "steps": got["facts"]["steps"],
            "compiles_in_window": got["facts"]["compiles_in_window"],
            "memory_peak_bytes": got["device"]["memory_peak_bytes"],
        }), flush=True)
        del got
    return 0


if __name__ == "__main__":
    sys.exit(main())
