#!/usr/bin/env python3
"""Read the numbers the ``served_gap_max`` limit is set from, on the chip.

    python3 bench/control.py --workload qwen3-0.6b.chat --seconds 30 \
        --seeds 11,12,13 --control-seeds 11,12,13

Runs the cell once per seed in one process (windows at the cell's own
load, the same check as a benchmark run) and prints, per seed, the
program's checks.  On the seeds in ``--control-seeds`` the float8
control is also put in the program's place: the same reference with its
linear layers in float8 e4m3, judged by the token it puts first at each
position of the same prompts and served tokens, through the same
comparison, so a sound limit makes it come out not correct.  At a sampled
request's positions any token among the reference's ``top_k`` best
passes, so there only greedy requests can catch the control.  The limit
lies between the largest program reading and the smallest control
reading.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)

    from bench import harness, spec
    from bench.run import require_chips

    cell = spec.load_cell(args.workload)
    devices = require_chips(cell.chips)
    harness.use_caches()
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.monotonic()
        out = harness.run_cell(cell, seed, args.seconds, False, t,
                               devices=devices, control=seed in ctrl)
        program = out.get("program_checks", out["checks"])
        row = {"seed": seed, "attempted": out["attempted"],
               "failed": out["failed"],
               "setup_s": out["metrics"]["setup_s"]["value"],
               "program_correct": harness.passed(program),
               **{k: v["value"] for k, v in program.items()}}
        if seed in ctrl:
            row["control_correct"] = out["correct"]
            row["control_gap_max"] = out["checks"]["served_gap_max"]["value"]
            row["readings"] = out["readings"]
        print(json.dumps(row), flush=True)
        rows.append(row)
    control = [r["control_gap_max"] for r in rows if "control_gap_max" in r]
    print(json.dumps({"program_max": max(r["served_gap_max"] for r in rows),
                      "control_min": min(control) if control else None}),
          flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
