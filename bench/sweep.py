#!/usr/bin/env python3
"""Find an open-loop cell's knee: one process, one set-up, several rates.

    python3 bench/sweep.py --workload qwen3-0.6b.chat --seed 5 \
        --seconds 50 --rates 0.2,0.4,0.6,0.8,1 [--write]

Each rate gets an open-loop window of ``--seconds`` through the same
gateway, one after the other, with the cell's mix at that rate.  The
lowest rate is the unloaded reference: it has to be low enough that
requests seldom wait for each other.  A rate is sustained when every
request due in its window is answered in full and the 95th percentile of
time to first token is at most ``TTFT_RISE`` times that at the lowest
rate: past the knee requests queue behind each other's prefill and that
tail grows without end.  The knee is the highest rate below the first
one not sustained; ``--write`` stores 0.8 x knee as the mix's
``rate_per_s``.  Prints one JSON line per rate, then one with the knee.
"""
from __future__ import annotations

import argparse
import asyncio
import copy
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

TTFT_RISE = 2.0


async def _sweep(router, cell, seed: int, seconds: float, rates):
    from bench import client, measures
    from bench.traffic.generate import make_schedule
    from repro.serve.gateway import Gateway

    model_id = router.models()[0].model_id
    vocab = router.models()[0].engine.cfg.vocab
    rows = []
    async with Gateway(router, port=0) as gw:
        mix = copy.deepcopy(cell.traffic)
        mix["arrival"]["rate_per_s"] = max(rates)
        warm = make_schedule(mix, seed, seconds, vocab).warmup
        await client.run_asks(gw.port, model_id, warm)
        for rate in rates:
            mix["arrival"]["rate_per_s"] = rate
            sched = make_schedule(mix, seed, seconds, vocab)
            t0 = time.monotonic()
            recs = await client.open_loop(gw.port, model_id, sched, t0,
                                          seconds, 60.0)
            run = measures.Run(conf=cell.config, peaks={}, seconds=seconds,
                               setup_s=0.0, t0=t0, records=recs,
                               reference=cell.reference)
            row = {"rate_per_s": rate, "requests": len(recs),
                   "failed": sum(not r.ok for r in recs),
                   "tokens_per_s": measures.window_tokens(run) / seconds,
                   "ttft_p50_ms": measures.median(measures.ttfts_ms(run)),
                   "ttft_p95_ms": measures.percentile(measures.ttfts_ms(run),
                                                      95),
                   "itl_p95_ms": measures.percentile(measures.itls_ms(run),
                                                     95)}
            base = rows[0]["ttft_p95_ms"] if rows else row["ttft_p95_ms"]
            row["sustained"] = (row["failed"] == 0 and
                                row["ttft_p95_ms"] <= TTFT_RISE * base)
            print(json.dumps(row), flush=True)
            rows.append(row)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second, ascending")
    ap.add_argument("--write", action="store_true",
                    help="store 0.8 x knee in the mix's traffic file")
    args = ap.parse_args(argv)

    from bench import harness, spec
    from bench.run import require_chips

    cell = spec.load_cell(args.workload)
    require_chips(cell.chips)
    harness.use_caches()
    rates = sorted(float(r) for r in args.rates.split(","))
    _, router = harness.build(
        cell, harness.program_config(cell.config, cell.reference), args.seed)
    rows = asyncio.run(_sweep(router, cell, args.seed, args.seconds, rates))
    knee = None
    for r in rows:              # ascending: the knee is below the first miss
        if not r["sustained"]:
            break
        knee = r["rate_per_s"]
    print(json.dumps({"knee_rate_per_s": knee,
                      "cell_rate_per_s": None if knee is None
                      else round(0.8 * knee, 3)}), flush=True)
    if args.write and knee is not None:
        path = ROOT / "bench" / "traffic" / f"{cell.traffic_name}.json"
        mix = json.loads(path.read_text())
        mix["arrival"]["rate_per_s"] = round(0.8 * knee, 3)
        path.write_text(json.dumps(mix, indent=2) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
