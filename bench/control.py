"""The output check's two readings, on the chip at a cell's own size.

    python3 bench/control.py --workload <cell> --seconds <s> \\
        --seeds <n>,<n>,... [--control-seeds <n>,<n>,...]

For every seed it runs the cell as ``bench/run.py`` does (set-up, lead-in,
a window of ``--seconds`` at the cell's own load) and reads the number
that the check compares: the widest gap of a served token below the
float32 reference's best logit.  For the control seeds it also reads the
same number for the token that the reference computed in fp8 puts first
at each position of the same prompts and tokens: the control, the step
below the bf16 that the configurations state.  The control reading is
judged as the program's is, against the configuration's limit, and has
to come out not correct.

The limit in the configuration file is set from these readings: above
the largest program reading over a dozen seeds or more and below the
smallest control reading.  The benchmark's own runs never run this.
Each reading is printed as one JSON line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import check, run  # noqa: E402


def control_gap(ref, w, dims, finished, rids, n_at) -> float:
    """Widest gap of the fp8 reference's first choice, over the tokens
    that the check reads."""
    def pick(seq, at):
        lg = ref.logits_at(w, seq, at, dims=check.dims_key(dims),
                           quant="fp8")
        return lg.argmax(axis=-1)

    worst = 0.0
    for r in rids:
        prompt, served = finished[r]
        g = check.gaps(ref, w, dims, prompt, served, n_at, pick=pick)
        worst = max(worst, float(g.max()))
    return worst


def readings(cell, cfg, seed: int, seconds: float, control: bool,
             peaks=None, say=print):
    """(program reading, control reading or None) of one run."""
    out = {}

    def inspect(ref, w, dims, finished, rids, n_at):
        if control:
            out["control"] = control_gap(ref, w, dims, finished, rids, n_at)

    res = run.run_cell(cell, cfg, seed, seconds, False, peaks=peaks,
                       inspect=inspect, say=say)
    return res, out.get("control")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args()
    cell = run.load_cell(args.workload)
    run.enable_compile_cache()
    peaks, why = run.require_chip(cell.chips)
    if peaks is None:
        print(f"[device] {why}", file=sys.stderr)
        return run.NO_CHIP
    cfg = run.program_config(cell.config)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in seeds + sorted(controls - set(seeds)):
        res, ctl = readings(cell, cfg, seed, args.seconds, seed in controls,
                            peaks=peaks,
                            say=lambda line: print(line, file=sys.stderr))
        gap = res["checks"]["served_logit_gap"]
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            "program": gap["value"], "correct": res["correct"],
            "control": ctl, "control_correct": None if ctl is None else
            check.judge({"served_logit_gap": (ctl, gap["limit"])}),
            "limit": gap["limit"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
