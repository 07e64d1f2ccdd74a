#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload qwen3-0.6b.chat --seed 7 --seconds 30 \
        --trace 0

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the middle of the window.  The
last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), and last ``checks``, each number compared beside its limit; the
same checks are the last lines of standard error.  Any platform but TPU,
or fewer chips than the cell asks for, exits non-zero with no result.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

T_START = time.monotonic()
ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def require_chips(n: int):
    """The first ``n`` devices, or SystemExit unless JAX found ``n`` TPUs:
    a run that fell back to the CPU measures nothing."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found "
                         f"{devices[0].platform!r} devices")
    if len(devices) < n:
        raise SystemExit(f"bench: the cell needs {n} chips, JAX found "
                         f"{len(devices)}")
    return devices[:n]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness, spec

    cell = spec.load_cell(args.workload)
    devices = require_chips(cell.chips)
    harness.use_caches()
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START, devices=devices)
    for name, c in out["checks"].items():
        at = "at least" if name in harness.AT_LEAST else "at most"
        harness.log(f"check {name}: {c['value']} ({at} {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
