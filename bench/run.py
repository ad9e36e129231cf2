#!/usr/bin/env python3
"""Chip benchmark of the serving engine: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine with the chips the cell asks
for.  ``BENCHMARK.json`` names the cell; its model configuration, traffic
mix, serving settings and metric readers are found by name under
``bench/`` (see ``bench/spec.py``).

A run:

1. makes the weights from ``--seed`` on the device, builds
   ``repro.serving.Engine`` on a ``DecomposeEngine`` as the serve CLI
   does, and warms up every program shape the cell's traffic can reach
   (each prompt bucket at each admission batch size, splice and decode);
   all of that is ``setup_s``;
2. opens the window: requests fall due as the traffic file says (open loop
   at the cell's rate, or closed loop with a fixed number of clients), and
   the harness stamps every token delivery on its own clock from outside
   ``Engine.step()``.  Requests due in the window are the measured ones;
   load goes on unchanged after the close until each has finished;
3. with ``--trace 1``, traces the last ``TRACE_SECONDS`` of the window with
   the profiler and reports the per-layer metrics instead of the
   end-to-end ones;
4. reads ``peak_bytes_in_use``, frees the engine, and checks a sample of
   the finished requests, drawn from the seed with the longest among them,
   against the plain float32 reference: the ``served_logits`` of the
   block that the configuration names (``bench/blocks/<block>.py``).

Without a TPU, or with fewer chips than the cell asks for, or without the
system under test beside ``bench/``, it prints no result and exits 2.  The
last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (``breakdown`` with
``--trace 1``) and, last, ``check``: each compared number with its limit.
An earlier line, starting ``bench-record``, is the harness's own record of
the run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402

#: how long measured requests may take to finish after the window closes
DRAIN_LIMIT_S = 90.0
#: a traced run traces the last this-many seconds of its window: writing
#: the trace out takes the profiler about six seconds per traced second
TRACE_SECONDS = 10.0


class NoChip(Exception):
    """No accelerator, too few chips, or no system under test."""


@dataclass
class ReqRec:
    uid: int
    due: float                      # seconds after the window opened
    prompt_len: int
    measured: bool
    dispatch: Optional[float] = None
    first_engine: Optional[float] = None
    deliveries: List[Tuple[float, int]] = field(default_factory=list)
    done: Optional[float] = None
    n_seen: int = 0
    tokens: List[int] = field(default_factory=list)


@dataclass
class Record:
    """What one window produced; metric readers take this."""
    workload: str
    seconds: float
    model: dict
    engine_cfg: dict
    device_kind: str
    requests: List[ReqRec]
    steps: List[Tuple[float, float, str, int]]    # start, end, label, rounds
    decode_rounds: int = 0
    folds: int = 0
    compiles: Dict[str, int] = field(default_factory=dict)
    setup_s: float = 0.0
    trace: Optional[dict] = None
    generator_lag_max_s: float = 0.0
    generator_lag_mean_s: float = 0.0
    admission_batches: int = 0
    trace_stop_s: float = 0.0
    #: window seconds the device metrics cover: the traced span in a
    #: traced run, else the whole window
    span: Tuple[float, float] = (0.0, 0.0)
    #: the model's block (``bench/blocks/<block>.py``): its
    #: ``forward_flops`` and ``reorth_needed`` count the work the
    #: roofline readers divide
    block: object = None

    @property
    def measured(self) -> List[ReqRec]:
        return [r for r in self.requests if r.measured]

    def admitted_in_span(self) -> List[ReqRec]:
        """Requests whose admission a step begun inside the span
        dispatched: the span's annotation, and so its trace, holds those
        steps whole, with the programs they ran."""
        lo, hi = self.span
        ends = [s[1] for s in self.steps if lo <= s[0] < hi]
        if not ends:
            return []
        hi = max(ends)
        return [r for r in self.requests
                if r.dispatch is not None and lo <= r.dispatch < hi]


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

@dataclass
class Ctx:
    workload: dict
    config: dict
    traffic: dict
    cell: dict
    cfg: object                     # the program's ArchConfig
    model: dict
    engine_cfg: dict
    device: object
    n_devices: int
    counter: object
    block: object                   # bench/blocks/<block>.py


def _compile_cache(root: Path) -> None:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def prepare(name: str, *, root: Path, bench_dir: Path, bm_root: Path,
            require_chip: bool) -> Ctx:
    if not (root / "src" / "repro" / "serving" / "__init__.py").is_file():
        raise NoChip(f"the system under test is not in {root}/src")
    bm = spec.load_benchmark(bm_root)
    wl = spec.workload(bm, name)
    conf = spec.config_file(wl["config"], bench_dir)
    traffic = spec.traffic_file(wl["traffic"], bench_dir)
    cell = spec.cell_file(name, bench_dir)
    block = spec.config_block(conf, bench_dir)
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))
    _compile_cache(root)
    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_chip:
        if dev.platform != "tpu":
            raise NoChip(f"no TPU: JAX found {dev.platform!r}")
        if len(devices) < int(wl["chips"]):
            raise NoChip(f"the cell asks for {wl['chips']} chips, JAX sees "
                         f"{len(devices)}")
    from bench.compiles import CompileCounter
    from repro.configs.base import get_arch
    cfg = get_arch(conf["arch"]).replace(**conf.get("replace", {}))
    model = dict(conf["model"])
    for k, v in model.items():
        if getattr(cfg, k) != v:
            raise spec.SpecError(f"config {wl['config']}: {k}={v} in the "
                                 f"file, {getattr(cfg, k)} as run")
    ec = dict(conf["engine"])
    ec.update(cell["serving"])
    return Ctx(wl, conf, traffic, cell, cfg, model, ec, dev, len(devices),
               CompileCounter(), block)


def buckets(ctx: Ctx) -> List[int]:
    b = int(ctx.engine_cfg["sched_bucket"])
    lo, hi = ctx.traffic["prompt"]["min"], ctx.traffic["prompt"]["max"]
    return sorted({-(-n // b) * b for n in range(lo, hi + 1)})


def admit_sizes(ctx: Ctx) -> List[int]:
    ec = ctx.engine_cfg
    cap = int(ec.get("sched_max_admit") or 0) or int(ec["slots"])
    return list(range(1, min(cap, int(ec["slots"])) + 1))


def validate(ctx: Ctx) -> None:
    """Every shape of the window is one that warm-up reaches: no tail fold
    (the dense tail holds the longest answer) and no prompt near the cache
    end (the bucket always fits)."""
    ec, t = ctx.engine_cfg, ctx.traffic
    if int(ec["kv_tail"]) < int(t["output"]["max"]):
        raise spec.SpecError("kv_tail must hold the longest answer: a fold "
                             "would reshape the cache inside the window")
    if int(ec["max_len"]) < max(buckets(ctx)) + int(t["output"]["max"]) + 1:
        raise spec.SpecError("max_len must hold the largest bucket plus the "
                             "longest answer")


def build_engine(ctx: Ctx, params):
    from repro.engine import DecomposeEngine, EngineConfig
    from repro.serving import Engine
    ec = ctx.engine_cfg
    dengine = DecomposeEngine(EngineConfig(
        backend=ec.get("backend", "auto"), expansion=int(ec["expansion"]),
        kv_rank=int(ec["kv_rank"]), kv_tail=int(ec["kv_tail"]),
        kv_iters_extra=int(ec["kv_iters_extra"]),
        sched_bucket=int(ec["sched_bucket"]),
        sched_max_admit=int(ec.get("sched_max_admit") or 0),
        decode_block=int(ec["decode_block"])))
    return Engine(ctx.cfg, params, slots=int(ec["slots"]),
                  max_len=int(ec["max_len"]), decompose_engine=dengine)


def make_params(ctx: Ctx, seed: int):
    import jax
    from bench import weights
    params = weights.make(ctx.block.layout(ctx.model), seed,
                          ctx.config.get("dtype", "bfloat16"))
    jax.block_until_ready(params)
    return params


def warm(ctx: Ctx, eng) -> None:
    """Admit every (bucket, batch size) the window can produce, largest
    bucket first so the cache's prefix axis is at its final length before
    any splice, each request decoding one block."""
    import numpy as np
    from repro.serving import Request
    rng = np.random.default_rng(0)
    uid = -1
    for b in sorted(buckets(ctx), reverse=True):
        for k in admit_sizes(ctx):
            reqs = []
            for _ in range(k):
                reqs.append(Request(uid=uid, prompt=rng.integers(
                    1, ctx.cfg.vocab, b, dtype=np.int32), max_new_tokens=2))
                uid -= 1
            for r in reqs:
                eng.submit(r)
            while not all(r.done for r in reqs):
                eng.step()


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

def serve(ctx: Ctx, eng, seed: int, seconds: float, trace_dir: Optional[str],
          drain_limit: float = DRAIN_LIMIT_S) -> Record:
    import jax
    import numpy as np
    from bench.traffic import Source
    from repro.serving import Request

    src = Source(ctx.traffic, seed, ctx.cfg.vocab, ctx.cell.get("rate_rps"))
    recs: List[ReqRec] = []
    inflight: Dict[int, Tuple[object, ReqRec]] = {}
    steps: List[Tuple[float, float, str, int]] = []
    lags: List[float] = []
    state = {"next": 0}
    st0 = eng.stats
    batches0, folds0 = st0.prefill_batches, st0.tail_folds
    compiles0 = dict(ctx.counter.counts)

    def submit(due: float, now: float) -> None:
        it = src.item(state["next"])
        state["next"] += 1
        rec = ReqRec(uid=it.index, due=due, prompt_len=len(it.prompt),
                     measured=due < seconds)
        req = Request(uid=it.index, prompt=it.prompt,
                      max_new_tokens=it.max_new)
        req.t_submit = t0 + due
        eng.submit(req)
        recs.append(rec)
        inflight[it.index] = (req, rec)
        lags.append(now - due)

    ready: List[float] = []          # closed loop: client ready times
    open_loop = src.open_loop
    span = (max(0.0, seconds - TRACE_SECONDS) if trace_dir else 0.0, seconds)
    win = None                       # the span's annotation, while open
    t_stop = 0.0
    ctx.counter.phase = "window"
    t0 = time.perf_counter()
    if open_loop:
        next_due = src.item(0).gap_s
    else:
        ready = [0.0] * src.clients
    k = 0
    phase = "before"                 # before, in and after the span
    while True:
        now = time.perf_counter() - t0
        if phase == "before" and now >= span[0]:
            if trace_dir:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0      # host spans are ours alone
                opts.enable_hlo_proto = False
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
            win = jax.profiler.TraceAnnotation("bench_window")
            win.__enter__()
            phase = "in"
        if phase == "in" and now >= seconds:
            win.__exit__(None, None, None)
            phase = "after"
            ctx.counter.phase = "drain"
            if trace_dir:
                t_stop = time.perf_counter()
                jax.profiler.stop_trace()
                t_stop = time.perf_counter() - t_stop
        if open_loop:
            while next_due <= now:
                submit(next_due, now)
                next_due += src.item(state["next"]).gap_s
        else:
            while ready:
                submit(ready.pop(0), now)
        if now >= seconds and not any(r.done is None for r in recs
                                      if r.measured):
            break
        if now >= seconds + t_stop + drain_limit:
            break
        busy = len(eng.sched) > 0 or any(r is not None for r in eng.live)
        if busy:
            b0, r0 = eng.stats.prefill_batches, eng.stats.decode_steps
            with jax.profiler.TraceAnnotation(f"bench_step_{k}"):
                finished = eng.step()
            t = time.perf_counter() - t0
            label = "step: admission and decode" \
                if eng.stats.prefill_batches > b0 else "step: decode"
            steps.append((now, t, label, eng.stats.decode_steps - r0))
            for uid, (req, rec) in list(inflight.items()):
                n = len(req.out_tokens)
                if n > rec.n_seen:
                    rec.deliveries.append((t, n - rec.n_seen))
                    rec.n_seen = n
                    if rec.dispatch is None and req.t_dispatch:
                        rec.dispatch = req.t_dispatch - t0
                        rec.first_engine = req.t_first - t0
                if req.done:
                    rec.done = t
                    rec.tokens = list(req.out_tokens)
                    del inflight[uid]
                    if not open_loop:
                        ready.append(t)
            del finished
        else:
            wait = (next_due - now) if open_loop else 0.001
            with jax.profiler.TraceAnnotation(f"bench_wait_{k}"):
                time.sleep(max(0.0, min(wait, 0.05)))
            steps.append((now, time.perf_counter() - t0,
                          "wait: no request due", 0))
        k += 1
    if phase == "in":                # the drain limit cut the window short
        win.__exit__(None, None, None)
        if trace_dir:
            jax.profiler.stop_trace()
    ctx.counter.phase = "after"
    st = eng.stats
    lag_w = [x for x, r in zip(lags, recs) if r.measured]
    rec = Record(
        workload=ctx.workload["name"], seconds=seconds, model=ctx.model,
        engine_cfg=ctx.engine_cfg,
        device_kind=ctx.device.device_kind, requests=recs, steps=steps,
        decode_rounds=0, folds=st.tail_folds - folds0,
        compiles={k: v - compiles0.get(k, 0)
                  for k, v in ctx.counter.counts.items()},
        generator_lag_max_s=max(lag_w, default=0.0),
        generator_lag_mean_s=float(np.mean(lag_w)) if lag_w else 0.0,
        block=ctx.block)
    # the span's annotation, and so its trace, holds every step begun
    # inside it
    rec.decode_rounds = sum(s[3] for s in steps
                            if span[0] <= s[0] < span[1])
    rec.span = span
    rec.admission_batches = st.prefill_batches - batches0
    rec.trace_stop_s = t_stop
    return rec


# ---------------------------------------------------------------------------
# output check
# ---------------------------------------------------------------------------

def sample_for_check(ctx: Ctx, rec: Record, seed: int) -> List[ReqRec]:
    """The finished measured request with the longest answer, then others
    drawn from the seed until the sample holds ``check.tokens`` served
    tokens."""
    import numpy as np
    done = [r for r in rec.measured if r.done is not None and r.tokens]
    if not done:
        return []
    done.sort(key=lambda r: r.uid)
    longest = max(done, key=lambda r: (len(r.tokens), r.prompt_len))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([int(seed), 7]).permutation(len(rest))
    out, n = [longest], len(longest.tokens)
    for i in order:
        if n >= int(ctx.cell["check"]["tokens"]):
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def padded_prompt(ctx: Ctx, prompt):
    """The prompt as the scheduler admits it: left-padded with token 0 to
    its bucket (or as it is, where the bucket reaches the cache end)."""
    import numpy as np
    b = int(ctx.engine_cfg["sched_bucket"])
    plen = -(-len(prompt) // b) * b
    if plen >= int(ctx.engine_cfg["max_len"]):
        plen = len(prompt)
    out = np.zeros(plen, np.int32)
    out[plen - len(prompt):] = prompt
    return out


def check(ctx: Ctx, params, sample: List[ReqRec], prompts: Dict[int, object],
          control: bool = False) -> Tuple[Dict[str, float], dict]:
    """Gaps by which each served token's reference logit lies below the
    reference's best at its position, over every sampled token (the first
    from the prefill forward alone, the rest decoded through the low-rank
    cache).  With ``control``, the tokens are those the float8 control puts
    first at each position of the same prompts and tokens.

    Returns the compared numbers (``mean_gap``) and, for the record only,
    the widest gap, the share of tokens that are not the reference's best,
    and the widest first-token gap."""
    import numpy as np
    from bench import reference
    ec = ctx.engine_cfg
    rank = int(ec["kv_rank"])
    iters = rank + int(ec["kv_iters_extra"])
    all_gaps, firsts, margins, distinct = [], [], [], 0
    for r in sample:
        padded = padded_prompt(ctx, prompts[r.uid])
        kw = dict(rank=rank, iters=iters,
                  decode_pad=int(ctx.traffic["output"]["max"]))
        ref = ctx.block.served_logits(params, ctx.model, padded, r.tokens,
                                      **kw)
        toks = r.tokens
        if control:
            ctl = ctx.block.served_logits(params, ctx.model, padded,
                                          r.tokens, control=True, **kw)
            toks = list(ctl.argmax(-1))
        g = reference.gaps(ref, toks)
        all_gaps.append(g)
        firsts.append(float(g[0]))
        top2 = np.sort(ref, -1)[:, -2:]
        margins.append(top2[:, 1] - top2[:, 0])
        distinct += len(set(r.tokens))
    g = np.concatenate(all_gaps) if all_gaps else np.zeros(0)
    numbers = {"mean_gap": float(g.mean()) if len(g) else math.inf}
    info = {"widest_gap": float(g.max()) if len(g) else None,
            "not_best_share": float((g > 0).mean()) if len(g) else None,
            "widest_first_token_gap": max(firsts, default=None),
            "median_top2_margin": float(np.median(np.concatenate(margins)))
            if margins else None,
            "distinct_share": distinct / len(g) if len(g) else None,
            "tokens": int(len(g))}
    return numbers, info


# ---------------------------------------------------------------------------
# metrics and output
# ---------------------------------------------------------------------------

def read_trace(trace_dir: str, steps) -> dict:
    from bench import tracing
    ops, mods, host, ndev = tracing.read_xplane(trace_dir)
    wins = [e for e in host if e[0] == "bench_window"]
    if not wins:
        raise RuntimeError("the trace holds no bench_window annotation")
    _, lo, d = wins[0]
    labels = {}
    for i, s in enumerate(steps):
        labels[f"bench_step_{i}"] = s[2]
        labels[f"bench_wait_{i}"] = s[2]
    spans = [e for e in host if e[0] != "bench_window"]
    red = tracing.reduce(ops, mods, spans, lo, lo + d, labels)
    red["devices"] = ndev
    return red


def metrics_of(bm: dict, rec: Record, kind: str, bench_dir: Path) -> dict:
    out = {}
    for m in spec.cell_metrics(bm, rec.workload, kind):
        mod = spec.metric_module(m["name"], bench_dir)
        v = mod.read(rec)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def _say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def main(argv=None, *, require_chip: bool = True, root: Path = ROOT,
         bench_dir: Path = spec.BENCH_DIR, bm_root: Optional[Path] = None
         ) -> int:
    """One run.  ``require_chip=False`` and the directories are for tests,
    which run a small cell of their own on the CPU."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    root, bench_dir = Path(root), Path(bench_dir)
    bm_root = Path(bm_root) if bm_root is not None else root
    try:
        ctx = prepare(args.workload, root=root, bench_dir=bench_dir,
                      bm_root=bm_root, require_chip=require_chip)
        validate(ctx)
    except (NoChip, spec.SpecError) as e:
        _say(f"no result: {e}")
        return 2
    import jax
    bm = spec.load_benchmark(bm_root)
    seed = args.seed
    params = make_params(ctx, seed)
    eng = build_engine(ctx, params)
    warm(ctx, eng)
    setup_s = time.perf_counter() - T_START
    _say(f"set-up {setup_s:.3f}s; compiles {ctx.counter.counts}")

    trace_dir = None
    if args.trace:
        trace_dir = str(root / ".bench_trace" / f"{args.workload}-{seed}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    rec = serve(ctx, eng, seed, args.seconds, trace_dir)
    rec.setup_s = setup_s
    stats = jax.devices()[0].memory_stats() or {}
    peak = int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.devices()[:int(ctx.workload["chips"])]))
    t_trace = time.perf_counter()
    if trace_dir:
        rec.trace = read_trace(trace_dir, rec.steps)
        shutil.rmtree(trace_dir, ignore_errors=True)
    t_trace = time.perf_counter() - t_trace

    prompts = {}
    sample = sample_for_check(ctx, rec, seed)
    from bench.traffic import Source
    src = Source(ctx.traffic, seed, ctx.cfg.vocab, ctx.cell.get("rate_rps"))
    for r in sample:
        prompts[r.uid] = src.item(r.uid).prompt
    del eng
    gc.collect()
    ctx.counter.phase = "check"
    t_chk = time.perf_counter()
    got, got_info = check(ctx, params, sample, prompts) if sample \
        else ({}, {})
    t_chk = time.perf_counter() - t_chk
    limits = ctx.cell["check"]["limits"]
    measured = rec.measured
    failed = sum(1 for r in measured if r.done is None)
    correct = bool(sample) and failed == 0 and rec.folds == 0 and all(
        got.get(k, math.inf) <= v for k, v in limits.items())

    kind = "per_layer" if args.trace else "end_to_end"
    metrics = metrics_of(bm, rec, kind, bench_dir)
    device = {"platform": ctx.device.platform,
              "kind": ctx.device.device_kind, "count": ctx.n_devices,
              "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": len(measured), "failed": failed,
           "metrics": metrics, "device": device}
    if rec.trace is not None:
        device["busy_s"] = rec.trace["busy_s"]
        device["window_s"] = rec.trace["window_s"]
        ops = sorted(rec.trace["leaf_ops"].items(),
                     key=lambda kv: -kv[1])[:10]
        gaps = sorted(rec.trace["idle_by_host"].items(),
                      key=lambda kv: -kv[1])[:10]
        out["breakdown"] = {"device_ops": [[n, s] for n, s in ops],
                            "idle_gaps": [[n, s] for n, s in gaps]}
    out["check"] = {k: {"value": got.get(k), "limit": v}
                    for k, v in limits.items()}

    record = {
        "workload": rec.workload, "block": ctx.config["block"],
        "seed": seed, "seconds": args.seconds, "setup_s": setup_s,
        "check_s": t_chk, "trace_read_s": t_trace,
        "trace_stop_s": rec.trace_stop_s,
        "wall_s": time.perf_counter() - T_START,
        "requests": {"measured": len(measured),
                     "after_close": len(rec.requests) - len(measured),
                     "finished_in_window": sum(
                         1 for r in measured if r.done is not None
                         and r.done < args.seconds),
                     "failed": failed},
        "generator_lag_s": {"max": rec.generator_lag_max_s,
                            "mean": rec.generator_lag_mean_s},
        "compiles": dict(ctx.counter.counts),
        "compile_seconds": dict(ctx.counter.seconds),
        "folds": rec.folds, "decode_rounds_in_window": rec.decode_rounds,
        "admission_batches": rec.admission_batches,
        "peak_bytes_in_use": peak, "bytes_in_use": stats.get("bytes_in_use"),
        "check_info": got_info,
        "check_sample": [{"uid": r.uid, "prompt": r.prompt_len,
                          "tokens": len(r.tokens)} for r in sample],
    }
    if rec.trace is not None:
        record["trace"] = {k: rec.trace[k] for k in
                           ("busy_s", "window_s", "idle_by_host",
                            "longest_gaps", "module_counts", "devices")}
        record["trace"]["modules"] = rec.trace["modules"]
        record["trace"]["top_ops"] = sorted(
            rec.trace["leaf_ops"].items(), key=lambda kv: -kv[1])[:40]
    print("bench-record " + json.dumps(record), flush=True)
    for k, v in limits.items():
        print(f"check {k} {got.get(k)} limit {v}", file=sys.stderr,
              flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
