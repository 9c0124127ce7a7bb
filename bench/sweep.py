"""Find a cell's knee: the highest offered rate that its Poisson traffic
sustains without a growing backlog, on every seed swept.

    python3 bench/sweep.py --workload <cell> --seeds <n>,<n>,... \\
        --seconds <s> --rates 1.5,1.75,2

Each rate and seed is one run of the cell (``bench/run.py``'s
``run_cell``) with the traffic file's ``rate_per_s`` replaced, all in one
process.  Each run prints one JSON line: the end-to-end metrics, the
requests still queued when the window closed, and the median time to
first token of each third of the window.  A backlog grows where the last
third waits far longer than the first.  The seed draws the order of the
arrivals and lengths, and the order decides whether the slots fill, so a
rate counts as sustained only if no seed queues.  The benchmark's own
runs never sweep: the rate found here is written into the traffic file.

The last line names the knee: the highest rate that, with every rate
below it, left nothing queued at the close on any seed and kept every
seed's ``ttft_p90_ms`` within twice the lowest rate's median.  The cell
runs at four fifths of it, rounded down to 0.05 requests/s.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    cell = run.load_cell(args.workload)
    run.enable_compile_cache()
    peaks, why = run.require_chip(cell.chips)
    if peaks is None:
        print(f"[device] {why}", file=sys.stderr)
        return run.NO_CHIP
    cfg = run.program_config(cell.config)
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = sorted(float(r) for r in args.rates.split(","))
    seen = {}
    for rate in rates:
        for seed in seeds:
            lines = []
            trial = run.Cell(cell.name, cell.chips, cell.config,
                             dict(cell.traffic, rate_per_s=rate),
                             cell.end_to_end, cell.per_layer)
            res = run.run_cell(trial, cfg, seed, args.seconds, False,
                               peaks=peaks, say=lines.append)
            window = next(x for x in lines if x.startswith("[window]"))
            field = dict(kv.split("=", 1) for kv in window.split()[1:])
            seen.setdefault(rate, []).append(
                (int(field["queued_at_close"]),
                 res["metrics"]["ttft_p90_ms"]["value"]))
            print(json.dumps({
                "rate_per_s": rate, "seed": seed, "correct": res["correct"],
                "queued_at_close": int(field["queued_at_close"]),
                "ttft_median_ms_by_third": field["ttft_median_ms_by_third"],
                "steps": int(field["steps"]),
                "metrics": {k: v["value"]
                            for k, v in res["metrics"].items()}}),
                flush=True)
    knee = knee_of(seen)
    print(json.dumps({"knee_per_s": knee, "rate_per_s":
                      None if knee is None else
                      math.floor(0.8 * knee * 20 + 1e-9) / 20}), flush=True)
    return 0


def knee_of(seen):
    """The highest rate sustained, with every rate below it, on every seed;
    ``seen`` maps a rate to (queued at close, ttft_p90_ms) per seed."""
    base = statistics.median(t for _, t in seen[min(seen)])
    knee = None
    for rate in sorted(seen):
        if any(q or t > 2 * base for q, t in seen[rate]):
            break
        knee = rate
    return knee


if __name__ == "__main__":
    sys.exit(main())
