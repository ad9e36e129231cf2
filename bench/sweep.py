#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate the engine
sustains.  Run once on the chip when a cell is added; the cell file then
carries its rate as a number (about four fifths of the knee).

    python3 bench/sweep.py --workload <cell> --rates 1.0,1.5,2.0 --seconds 20

One process: set-up and warm-up once, then one window per rate, each
with a seed of its own.  For each rate it prints the offered and completed
request rates, TTFT p50/p90, queue wait p90 and how long the measured
requests took to drain after the window closed.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1000)
    ap.add_argument("--drain-limit", type=float, default=20.0)
    args = ap.parse_args(argv)
    try:
        ctx = run.prepare(args.workload, root=run.ROOT,
                          bench_dir=run.spec.BENCH_DIR, bm_root=run.ROOT,
                          require_chip=True)
    except run.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    params = run.make_params(ctx, args.seed)
    eng = run.build_engine(ctx, params)
    run.warm(ctx, eng)
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        ctx.cell["rate_rps"] = rate
        rec = run.serve(ctx, eng, args.seed + 1 + i, args.seconds, None,
                        drain_limit=args.drain_limit)
        m = rec.measured
        ttft = [r.deliveries[0][0] - r.due for r in m if r.deliveries]
        qw = [r.dispatch - r.due for r in m if r.dispatch is not None]
        done_in = [r for r in m if r.done is not None and r.done < args.seconds]
        last = max((r.done for r in m if r.done is not None), default=None)
        row = {"rate": rate, "measured": len(m),
               "offered_rps": len(m) / args.seconds,
               "completed_in_window_rps": len(done_in) / args.seconds,
               "unfinished": sum(1 for r in m if r.done is None),
               "drain_s": None if last is None else max(0.0, last - args.seconds),
               "ttft_p50_ms": float(np.percentile(ttft, 50)) * 1e3 if ttft else None,
               "ttft_p90_ms": float(np.percentile(ttft, 90)) * 1e3 if ttft else None,
               "queue_wait_p90_ms": float(np.percentile(qw, 90)) * 1e3 if qw else None,
               "window_compiles": rec.compiles.get("window", 0)}
        rows.append(row)
        print("sweep " + json.dumps(row), flush=True)
        # drain whatever is left before the next rate
        while len(eng.sched) or any(r is not None for r in eng.live):
            eng.step()
    print(json.dumps({"workload": args.workload, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
