"""Benchmark aggregator: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``--quick`` shrinks shapes.

``--tune`` is the autotuner entrypoint instead: measure-tune every
registered kernel space over representative shapes, persist the winners,
and emit the tuning cache as a JSON artifact (``--tune-out``) so CI can
carry it across runs and a deployment can ship it with the binary.
"""
from __future__ import annotations

import argparse
import os
import sys
import time


# Representative (kernel → shapes) for the tune entrypoint; --quick keeps
# the same kernels but shrinks every shape.
TUNE_SHAPES = {
    "lanczos_reorth": [(4, 256, 512), (8, 64, 1024)],
    "matvec_expand": [(1024, 2048)],
    "lowrank_matmul": [(16, 1024, 1024)],
    "dkv_attention": [(8, 1024, 32)],
    "decode_block": [(8, 128, 512)],       # (slots, horizon, kv width)
}
TUNE_SHAPES_QUICK = {
    "lanczos_reorth": [(2, 48, 96)],
    "matvec_expand": [(128, 256)],
    "lowrank_matmul": [(8, 128, 128)],
    "dkv_attention": [(4, 96, 16)],
    "decode_block": [(4, 16, 64)],
}


def run_tune(quick: bool, out_path: str) -> None:
    """Measure-tune every registered kernel and write the cache artifact."""
    from repro import tune
    from repro.engine.platform import kernel_backend

    from .common import write_json

    shapes = TUNE_SHAPES_QUICK if quick else TUNE_SHAPES
    cache = tune.default_cache()
    print("name,us_per_call,derived")
    for kernel in tune.available_spaces():
        fix = {"backend": kernel_backend()} \
            if kernel == "lanczos_reorth" else None
        for shape in shapes.get(kernel, ()):
            res = tune.tune(kernel, shape, "float32", fix=fix,
                            measure_candidates=True,
                            prune=tune.DEFAULT_PRUNE,
                            reps=3 if quick else 5, cache=cache)
            best = ",".join(f"{k}={v}" for k, v in sorted(res.best.items()))
            print(f"tune/{kernel}/{'x'.join(map(str, res.shape))},"
                  f"{(res.measured_s or 0.0) * 1e6:.3f},"
                  f"{res.source}:{best}", flush=True)
    # measure the backend choice itself and persist it as the machine's
    # backend="auto" answer (the engine_backend cache override)
    bres = tune.tune_backend(shape=(2, 48, 96) if quick else (4, 256, 512),
                             reps=2 if quick else 5, cache=cache)
    print(f"tune/engine_backend,{(bres.measured_s or 0.0) * 1e6:.3f},"
          f"measured:backend={bres.best['backend']}", flush=True)
    cache.save()
    write_json(out_path, {"path": cache.path, "entries": cache.as_dict()},
               indent=1, sort_keys=True)
    print(f"_meta/tune_cache,{len(cache)},{out_path}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated module suffixes to run")
    ap.add_argument("--tune", action="store_true",
                    help="run the autotuner entrypoint instead of the "
                         "figure benchmarks")
    ap.add_argument("--tune-out",
                    default=os.path.join(os.path.dirname(__file__), "out",
                                         "tune_cache.json"),
                    help="where --tune writes the cache artifact")
    args = ap.parse_args()

    if args.tune:
        run_tune(args.quick, args.tune_out)
        return

    from . import (dkv_quality, engine_throughput, fig2_convergence,
                   fig3_breakdown, fig10_outliers, fig11_layer_runtime,
                   fig12_expansion, serving_admission, table2_table3_configs)
    mods = {
        "fig2": fig2_convergence, "fig3": fig3_breakdown,
        "fig10": fig10_outliers, "fig11": fig11_layer_runtime,
        "fig12": fig12_expansion, "table2_table3": table2_table3_configs,
        "dkv_quality": dkv_quality, "engine": engine_throughput,
        "serving": serving_admission,
    }
    if args.only:
        keep = args.only.split(",")
        mods = {k: v for k, v in mods.items() if k in keep}

    print("name,us_per_call,derived")
    ok = True
    for name, mod in mods.items():
        t0 = time.perf_counter()
        try:
            for row in mod.run(quick=args.quick):
                print(f"{row[0]},{row[1]:.3f},{row[2]}", flush=True)
            print(f"_meta/{name}_wall_s,"
                  f"{(time.perf_counter() - t0) * 1e6:.0f},ok", flush=True)
        except Exception as e:                       # keep the suite going
            ok = False
            import traceback
            traceback.print_exc()
            print(f"_meta/{name},0,FAILED:{e}", flush=True)
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
