"""Serving CLI: continuous-batching engine on a registered config.

  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b \
      --decompose-kv-rank 64 --dkv-tail 128 --requests 8

serves the architecture at its published widths, with seeded random
weights.  ``--reduced`` swaps in the tiny same-family variant
(``ArchConfig.reduced``: d_model 128, vocab 512) for CPU runs and tests:

  JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.serve \
      --arch gemma-2b --reduced --requests 8

The engine is family-generic (``repro.serving.families``): ``--family
ssm|moe|hybrid|dense`` serves that family's default arch on the same
slot/fused/async machinery, e.g.

  PYTHONPATH=src python -m repro.launch.serve --family ssm --reduced

Decomposed-KV serving (the paper's activation decomposition applied to the
KV stream) rides one DecomposeEngine, constructed here from the CLI flags
and handed to the serving engine (``--decompose-kv-rank 8 --dkv-tail 16``).
``--backend auto`` (the default) resolves to the compiled Pallas kernels on
a TPU and to the jnp reference elsewhere.

``--expansion auto`` resolves through the ``repro.tune`` autotuner; warmup
then PRE-TUNES the prefill decomposition shape this serving config will
actually launch (the bucketed prompt length through the lanczos_reorth
kernel family), so the first request pays no tuning cost and the resolved
operating point is printed before traffic starts.

:func:`main` takes an ``argv`` list and returns the engine and its finished
requests, so scripts drive exactly the code the CLI runs.
"""
from __future__ import annotations

import argparse
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..configs.base import get_arch
from ..engine import DecomposeEngine, EngineConfig, available_backends
from ..models import api
from ..obs import (GLOBAL, Observability, compile_stats, write_json_snapshot,
                   write_prometheus)
from ..serving import Engine, Request
from .compile_cache import enable_compile_cache
from .mesh import parse_mesh


# default arch per serving family for `--family NAME` without `--arch`
_FAMILY_DEFAULT_ARCH = {
    "dense": "llama2-7b",
    "ssm": "mamba2-780m",
    "moe": "olmoe-1b-7b",
    "hybrid": "zamba2-1.2b",
}


def main(argv: Optional[Sequence[str]] = None
         ) -> Tuple[Engine, List[Request]]:
    """Run the CLI on ``argv`` (``sys.argv[1:]`` when None); returns the
    serving engine and its finished requests."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="architecture name (required unless --family "
                         "picks its default arch)")
    ap.add_argument("--family", default=None,
                    choices=sorted(_FAMILY_DEFAULT_ARCH),
                    help="serve this family's default arch (ssm = "
                         "mamba2-780m, moe = olmoe-1b-7b, hybrid = "
                         "zamba2-1.2b, dense = llama2-7b); --arch "
                         "overrides the arch, and the engine checks it "
                         "really is that family")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the tiny same-family variant (d_model 128, "
                         "vocab 512) instead of the published widths")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--decompose-kv-rank", type=int, default=0,
                    help="serve the low-rank KV cache at this rank (0=off)")
    ap.add_argument("--dkv-tail", type=int, default=16,
                    help="dense recent-token tail length")
    ap.add_argument("--dkv-exact", action="store_true",
                    help="direct-SVD KV factorization (near-full rank)")
    ap.add_argument("--paged", action="store_true",
                    help="paged decomposed-KV cache (block tables over "
                         "fixed-size page pools instead of a static slab)")
    ap.add_argument("--pages", type=int, default=0,
                    help="page-pool size in pages (0 = auto-sized from "
                         "slots x max-len with fold headroom)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="rows per page (prefix U rows / dense tail rows)")
    ap.add_argument("--prefix-cache", type=int, default=0,
                    help="shared-prefix cache capacity in entries (0 = "
                         "off; hits admit with tail-only work, skipping "
                         "the prefix forward pass AND its Lanczos)")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="stop token id: requests finish (and free their "
                         "slot) the moment they emit it")
    ap.add_argument("--backend", default="auto",
                    choices=available_backends() + ["auto"],
                    help="decomposition backend for the engine (auto = "
                         "compiled Pallas on a TPU, jnp reference "
                         "elsewhere, unless a tuned override exists)")
    ap.add_argument("--expansion", default="8",
                    help="D-com compute-expansion factor f, or 'auto' "
                         "(tuner-resolved per shape-bucket)")
    ap.add_argument("--no-pretune", action="store_true",
                    help="skip the warmup pre-tuning pass")
    ap.add_argument("--admission", default="per_slot",
                    choices=("per_slot", "gang"),
                    help="admission policy (gang = legacy, for A/B)")
    ap.add_argument("--sched-bucket", type=int, default=16,
                    help="prefill length bucket (bounds re-jits)")
    ap.add_argument("--admit-every", type=int, default=1,
                    help="decode rounds between admission checks")
    ap.add_argument("--max-admit", type=int, default=0,
                    help="max requests per admission batch (0=free slots)")
    ap.add_argument("--mesh", default="none",
                    help="serving mesh: 'none' (default), 'host' (all "
                         "local devices on the data axis), or 'DxM' (e.g. "
                         "8x1; force host devices with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8)")
    ap.add_argument("--decode-block", default="1",
                    help="fused decode steps per device launch: N, or "
                         "'auto' (tuner-resolved).  1 = classic per-token "
                         "dispatch; N>1 runs up to N steps in one jitted "
                         "on-device loop, token-identical output")
    ap.add_argument("--prefill-async", action="store_true",
                    help="disaggregated prefill/decode: admissions "
                         "(forward prefill + Lanczos) dispatch "
                         "asynchronously and splice into slots when "
                         "ready — decode never blocks on an in-flight "
                         "decomposition")
    ap.add_argument("--ready-order", default="ready",
                    choices=("ready", "deterministic"),
                    help="async splice order: 'ready' (as results "
                         "complete) or 'deterministic' (inline at the "
                         "dispatch round — byte-identical tokens to the "
                         "synchronous engine, for conformance A/Bs)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a Prometheus text exposition of every "
                         "metric (engine stats + decomposition/tuner/"
                         "compile telemetry) here at exit; '-.json' "
                         "suffix writes the JSON snapshot instead")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record request-lifecycle spans and write "
                         "Chrome trace-event JSON (Perfetto-loadable) "
                         "here at exit")
    ap.add_argument("--stats-every", type=int, default=0, metavar="N",
                    help="print a p50/p95/p99 stats snapshot every N "
                         "engine steps (0 = only the final summary)")
    args = ap.parse_args(argv)

    if args.arch is None:
        if args.family is None:
            ap.error("one of --arch / --family is required")
        args.arch = _FAMILY_DEFAULT_ARCH[args.family]
    enable_compile_cache()
    mesh = parse_mesh(args.mesh)
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.family is not None and cfg.family != args.family:
        ap.error(f"--arch {args.arch} is family {cfg.family!r}, "
                 f"not {args.family!r}")
    params = api.init_params(cfg, args.seed, mesh)
    expansion = args.expansion if args.expansion == "auto" \
        else int(args.expansion)
    decode_block = args.decode_block if args.decode_block == "auto" \
        else int(args.decode_block)
    dengine = DecomposeEngine(EngineConfig(
        backend=args.backend, expansion=expansion,
        kv_rank=args.decompose_kv_rank, kv_tail=args.dkv_tail,
        kv_exact=args.dkv_exact, kv_page=args.page_size,
        kv_pool_pages=args.pages, kv_prefix_cache=args.prefix_cache,
        sched_bucket=args.sched_bucket,
        sched_admit_every=args.admit_every, sched_max_admit=args.max_admit,
        decode_block=decode_block, mesh=mesh))

    if expansion == "auto" and not args.no_pretune:
        # Serving warmup: resolve the tuned operating points for the
        # shapes this config will actually launch — per-slot admission
        # prefills pow2(len(admitted)) ≤ slots requests, and the flat
        # prefill decomposition engine.decompose_kv runs through the
        # lanczos_reorth family is [num_layers·nb, plen_bucket, kvw] —
        # so every pow2 admission batch gets its bucket warmed before
        # traffic starts.  (Pointless for a fixed --expansion: resolution
        # never consults the tuner then.)
        from .. import tune
        plen = -(-args.prompt_len // max(1, args.sched_bucket)) \
            * max(1, args.sched_bucket)
        kvw = cfg.num_kv_heads * cfg.resolved_head_dim
        from ..models.decomposed_kv import factorized_layers
        n_fac = len(factorized_layers(cfg))  # layers whose K/V is factorized
        slots = max(1, args.slots)
        nbs, nb = {slots}, 1             # nb = min(pow2(admitted), slots)
        while nb < slots:
            nbs.add(nb)
            nb *= 2
        pre = tune.pretune(
            {"lanczos_reorth": [(n_fac * n, plen, kvw)
                                for n in sorted(nbs)]},
            fix={"backend": dengine.resolved_backend})
        for key, res in pre.items():
            print(f"pretune[{res.kernel}]: f={res.best['expansion']} "
                  f"({res.source}, {key})")

    obs = Observability(trace=args.trace_out is not None)
    eng = Engine(cfg, params, slots=args.slots, max_len=args.max_len,
                 decompose_kv_rank=args.decompose_kv_rank,
                 dkv_tail=args.dkv_tail, decompose_engine=dengine,
                 admission=args.admission, paged=args.paged,
                 eos_id=args.eos_id, prefill_async=args.prefill_async,
                 ready_order=args.ready_order, obs=obs)

    rng = np.random.RandomState(args.seed)
    for i in range(args.requests):
        eng.submit(Request(uid=i,
                           prompt=rng.randint(0, cfg.vocab, args.prompt_len,
                                              dtype=np.int32),
                           max_new_tokens=args.max_new))
    if args.stats_every > 0:
        # drive step() directly so periodic snapshots land on step edges
        done, steps = [], 0
        while steps < 10_000:
            done.extend(eng.step())
            steps += 1
            if steps % args.stats_every == 0:
                print(_pctl_line(eng.stats, prefix=f"step {steps}: "))
            if not eng._occupied() and not len(eng.sched):
                break
    else:
        done = eng.run()
    for r in sorted(done, key=lambda r: r.uid):
        print(f"req {r.uid}: {r.out_tokens}")
    s = eng.stats
    mesh_desc = "none" if mesh is None else \
        "x".join(str(mesh.shape[a]) for a in mesh.axis_names)
    async_desc = f"async({eng.ready_order})" if eng.prefill_async else "sync"
    print(f"engine: {dengine}  family={cfg.family}"
          f"[{type(eng.family).__name__}]  admission={args.admission}  "
          f"mesh={mesh_desc} ({len(jax.devices())} devices)  "
          f"decode_block={eng.decode_block}  prefill={async_desc}")
    print(f"stats: prefills={s.prefills} batches={s.prefill_batches} "
          f"decode_steps={s.decode_steps} blocks={s.blocks} "
          f"folds={s.tail_folds} stalls={s.stalls} "
          f"inflight_peak={s.prefill_inflight_peak} "
          f"tokens={s.tokens_out} stopped_eos={s.stopped_eos} "
          f"stopped_budget={s.stopped_budget} wall={s.wall_s:.2f}s "
          f"tok/s={s.tokens_out / max(s.wall_s, 1e-9):.1f} "
          f"ttft={s.mean_ttft_s * 1e3:.1f}ms "
          f"(queue={s.mean_ttft_queue_s * 1e3:.1f}ms "
          f"compute={s.mean_ttft_compute_s * 1e3:.1f}ms) "
          f"itl={s.mean_itl_s * 1e3:.1f}ms")
    print(_pctl_line(s))
    if eng.pager is not None:
        pg = eng.pager
        line = (f"paged: page={pg.page} pool={pg.num_pages}p "
                f"free={pg.alloc.free_pages}p "
                f"pool_bytes={pg.pool_bytes}")
        if pg.prefix is not None:
            line += (f" prefix_hits={s.prefix_hits} "
                     f"prefix_misses={s.prefix_misses} "
                     f"entries={len(pg.prefix)}")
        print(line)

    cw = compile_stats()
    if cw:
        print("compiles: " + " ".join(
            f"{ph}={d['compiles']}({d['seconds']:.2f}s)"
            for ph, d in sorted(cw.items())))
    if args.metrics_out:
        # engine registry (serving_*) + the process GLOBAL registry
        # (decompose/tuner/compile telemetry) in one exposition
        if args.metrics_out.endswith(".json"):
            write_json_snapshot(args.metrics_out, obs.registry, GLOBAL)
        else:
            write_prometheus(args.metrics_out, obs.registry, GLOBAL)
        print(f"metrics: wrote {args.metrics_out}")
    if args.trace_out:
        obs.tracer.export(args.trace_out)
        print(f"trace: wrote {args.trace_out} "
              f"({len(obs.tracer.events)} events, "
              f"{obs.tracer.dropped} dropped)")
    return eng, done


def _pctl_line(s, prefix: str = "") -> str:
    """p50/p95/p99 TTFT + ITL line from the streaming histograms."""
    def pct(series):
        return "/".join(f"{series.quantile(q) * 1e3:.1f}"
                        for q in (0.5, 0.95, 0.99))
    return (f"{prefix}pctl: ttft_ms p50/p95/p99={pct(s.ttft_s)} "
            f"itl_ms p50/p95/p99={pct(s.itl_s)} "
            f"tokens={s.tokens_out}")


if __name__ == "__main__":
    main()
