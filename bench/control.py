#!/usr/bin/env python3
"""Readings that set a cell's output-check limits (run on the chip).

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 15

For each seed, in one process: weights from the seed, the engine, a short
window at the cell's own load, then the same sample of finished requests
that a run checks, read twice against the float32 reference — once with
the tokens the program served (the lower readings) and once with the
tokens the float8 control puts first at each position of the same prompts
and tokens (the upper readings).  The benchmark's own runs never run the
control.  Prints one ``control`` line per seed and a JSON summary.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import run  # noqa: E402


def readings(ctx, seed: int, seconds: float) -> dict:
    params = run.make_params(ctx, seed)
    eng = run.build_engine(ctx, params)
    rec = run.serve(ctx, eng, seed, seconds, None)
    sample = run.sample_for_check(ctx, rec, seed)
    from bench.traffic import Source
    src = Source(ctx.traffic, seed, ctx.cfg.vocab, ctx.cell.get("rate_rps"))
    prompts = {r.uid: src.item(r.uid).prompt for r in sample}
    del eng
    gc.collect()
    t = time.perf_counter()
    prog, prog_info = run.check(ctx, params, sample, prompts)
    t_prog = time.perf_counter() - t
    ctl, ctl_info = run.check(ctx, params, sample, prompts, control=True)
    return {"seed": seed, "program": prog, "control": ctl,
            "program_info": prog_info, "control_info": ctl_info,
            "sample_tokens": sum(len(r.tokens) for r in sample),
            "sample_requests": len(sample), "check_s": t_prog,
            "failed": sum(1 for r in rec.measured if r.done is None)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    try:
        ctx = run.prepare(args.workload, root=run.ROOT,
                          bench_dir=run.spec.BENCH_DIR, bm_root=run.ROOT,
                          require_chip=True)
    except run.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    rows = []
    for s in (int(x) for x in args.seeds.split(",")):
        row = readings(ctx, s, args.seconds)
        rows.append(row)
        print("control " + json.dumps(row), flush=True)
    print(json.dumps({"workload": args.workload, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
