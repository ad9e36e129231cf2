#!/usr/bin/env python3
"""Where the step loop leaves the chip idle, and where admission's device
time goes: one traced window of a cell, read at the grain of the engine's
own spans and of the named scopes in its admission programs.

    python3 bench/phases.py --workload <cell> --seed <n> --seconds <s>

from the root of a checkout, on a machine with the chips the cell asks
for.  The window is ``bench/run.py --trace 1``'s: the same set-up, warm-up,
traffic and profiler options, the last ``run.TRACE_SECONDS`` traced.  There
is no output check.  The last line of standard output is one JSON object:
the benchmark's per-layer metrics of the cell, the readings of
``step_idle``, ``lanczos_ms`` and ``admit_pad_share`` (readers under
``bench/metrics/``, not yet listed in ``BENCHMARK.json``), and what they
are read from.

Beside what ``bench/run.py`` reads, the trace gives:

* ``idle_by_engine``: each idle gap of the device labelled by the
  innermost ``engine.<span>`` host annotation (``repro.obs.trace``) that
  holds its midpoint, "outside engine" where none does;
* ``scope_seconds``: device seconds of the leaf ops of each program, by
  the ``dcom.*`` named scope (``models/decomposed_kv.py``) of the op's
  instruction, "other" outside them.  The trace names an op's HLO
  instruction but not its scope, so the scope comes from the
  ``op_name`` metadata of that instruction in the compiled text of
  every prefill and splice executable the window can launch (compiled
  again after the window from the shapes warm-up admits; the persistent
  cache then serves them).  A fusion carries the metadata of its root
  instruction.

and the engine's ``serving_prefill_tokens_total{kind}`` counters, summed
over the steps begun in the span (``prefill_tokens``).
"""
from __future__ import annotations

import argparse
import bisect
import json
import re
import shutil
import sys
import time
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import spec, tracing  # noqa: E402
from bench.tracing import Event, Events  # noqa: E402

#: the readers this tool adds to the benchmark's per-layer metrics
METRICS = ("step_idle", "lanczos_ms", "admit_pad_share")
OUTSIDE_ENGINE = "outside engine"
#: host annotations of the engine's spans (``repro.obs.trace``)
ENGINE_PREFIX = "engine."
OTHER = "other"
#: ``%name = <shape> <opcode>(``: an instruction's name and result shape,
#: alike in compiled HLO text and in a device trace's op event names
_INSTR = re.compile(r"^\s*(?:ROOT )?((%\S+) = .*?\s[a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=(%[^\s,]+)")
_SCOPE = re.compile(r"(?:^|/)(dcom\.[A-Za-z0-9_]+)(?:/|$)")
_PROGRAM = re.compile(r"^(.*?)\(\d+\)$")


# ---------------------------------------------------------------------------
# reductions (plain data in, plain data out; checked without a chip)
# ---------------------------------------------------------------------------

def idle_by_engine(gaps: Sequence[Tuple[int, int]],
                   spans: Sequence[Event]) -> Dict[str, float]:
    """Idle seconds by the innermost engine span (``engine.<name>``
    events) holding each gap's midpoint; spans nest or follow each
    other, so the innermost is the latest-starting span that holds it."""
    spans = sorted(spans, key=lambda e: (e[1], -e[2]))
    starts = [s for _, s, _ in spans]
    out: Dict[str, float] = defaultdict(float)
    for a, b in gaps:
        mid = (a + b) // 2
        label = OUTSIDE_ENGINE
        i = bisect.bisect_right(starts, mid) - 1
        while i >= 0:
            name, s, d = spans[i]
            if mid < s + d:
                label = name[len(ENGINE_PREFIX):] \
                    if name.startswith(ENGINE_PREFIX) else name
                break
            i -= 1
        out[label] += (b - a) * 1e-9
    return dict(out)


def scope_map(hlo_text: str) -> Dict[str, str]:
    """Instruction → ``dcom.*`` scope (``OTHER`` outside them) of one
    compiled HLO module, keyed twice: by ``%name = <shape> <opcode>`` and
    by ``%name``.  A fusion takes the scope of its fused computation's
    root instruction, or its own where that root carries no ``op_name``."""
    def scope_of(line: str) -> Optional[str]:
        op = _OP_NAME.search(line)
        if not op:
            return None
        sc = _SCOPE.search(op.group(1))
        return sc.group(1) if sc else OTHER

    root: Dict[str, Optional[str]] = {}     # computation → root's scope
    instrs = []
    comp = None
    for line in hlo_text.splitlines():
        if line.startswith(("%", "ENTRY ")):
            comp = line.split()[1 if line.startswith("ENTRY ") else 0]
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        if line.lstrip().startswith("ROOT "):
            root[comp] = scope_of(line)
        called = _CALLS.search(line) \
            if m.group(1).endswith(" fusion") else None
        instrs.append((m.group(1), m.group(2), scope_of(line),
                       called.group(1) if called else None))
    out: Dict[str, str] = {}
    for key, name, own, called in instrs:
        scope = (root.get(called) if called else None) or own or OTHER
        out[key] = scope
        out[name] = scope
    return out


def op_scope(event_name: str, maps: Sequence[Dict[str, str]]
             ) -> Optional[str]:
    """The scope of a device op event in the first map that knows its
    instruction with its shape, else by its name alone; None where none
    does."""
    m = _INSTR.match(event_name)
    if not m:
        return None
    for key in (m.group(1), m.group(2)):
        for mp in maps:
            if key in mp:
                return mp[key]
    return None


def program_of(module_name: str) -> str:
    """``jit_prefill`` of a trace's program event ``jit_prefill(<id>)``."""
    m = _PROGRAM.match(module_name)
    return m.group(1) if m else module_name


def leaf_seconds(ops, modules, lo: int, hi: int
                 ) -> List[Tuple[str, str, float]]:
    """(program, op event name, device seconds inside [lo, hi)) of every
    leaf op (loops and calls, which contain other events, left out).  An
    op belongs to the program event that holds its start."""
    ops, mods = Events.of(ops), Events.of(modules)
    ids, s, e = ops.clipped(lo, hi)
    progs = sorted({program_of(n) for n in mods.names}) + ["no program"]
    prog_of_name = np.array([progs.index(program_of(n))
                             for n in mods.names] + [len(progs) - 1])
    order = np.argsort(mods.start, kind="stable")
    m_start, m_ids = mods.start[order], mods.ids[order]
    m_end = m_start + mods.dur[order]
    j = np.searchsorted(m_start, s, side="right") - 1
    held = (j >= 0) & (s < m_end[np.maximum(j, 0)]) if len(m_start) \
        else np.zeros(len(s), bool)
    mod = np.where(held, m_ids[np.maximum(j, 0)] if len(m_ids) else 0,
                   len(mods.names))
    per = np.bincount(ids * len(progs) + prog_of_name[mod],
                      weights=(e - s).astype(np.float64),
                      minlength=len(ops.names) * len(progs))
    out = []
    for k in np.flatnonzero(per):
        oid, p = divmod(int(k), len(progs))
        name = ops.names[oid]
        if tracing.op_key(name).rsplit(" ", 1)[-1] not in \
                tracing.CONTAINERS:
            out.append((progs[p], name, float(per[k]) * 1e-9))
    return out


def scope_seconds(leaves: Sequence[Tuple[str, str, float]],
                  maps: Dict[str, Sequence[Dict[str, str]]]
                  ) -> Dict[str, Dict[str, float]]:
    """Leaf-op seconds (``leaf_seconds``) by program and scope.  An op's
    scope comes from its program's maps: ``OTHER`` where its instruction
    has no ``dcom.*`` scope or the program has no maps, ``"unmapped"``
    where no map of the program knows the instruction."""
    out: Dict[str, Dict[str, float]] = defaultdict(
        lambda: defaultdict(float))
    for prog, name, sec in leaves:
        pmaps = maps.get(prog)
        scope = OTHER if pmaps is None \
            else op_scope(name, pmaps) or "unmapped"
        out[prog][scope] += sec
    return {p: dict(v) for p, v in out.items()}


# ---------------------------------------------------------------------------
# reading the trace and the program
# ---------------------------------------------------------------------------

def read_xplane(log_dir: str):
    """``tracing.read_xplane``'s device ops, programs and ``bench_*``
    host annotations, and the ``engine.*`` ones beside them, in one pass
    over the newest ``.xplane.pb`` under ``log_dir``."""
    import jax
    pd = jax.profiler.ProfileData.from_file(tracing._newest_xplane(log_dir))
    found = {"XLA Ops": ({}, [], [], []), "XLA Modules": ({}, [], [], [])}
    bench: List[Event] = []
    engine: List[Event] = []
    devices = set()
    for plane in pd.planes:
        m = tracing._DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in found:
                devices.add(m.group(1))
                index, ids, st, du = found[line.name]
                for ev in line.events:
                    ids.append(index.setdefault(ev.name, len(index)))
                    st.append(ev.start_ns)
                    du.append(ev.duration_ns)
            elif not m and plane.name.startswith("/host"):
                for ev in line.events:
                    if ev.name.startswith("bench_"):
                        bench.append((ev.name, int(ev.start_ns),
                                      int(ev.duration_ns)))
                    elif ev.name.startswith(ENGINE_PREFIX):
                        engine.append((ev.name, int(ev.start_ns),
                                       int(ev.duration_ns)))
    ops, mods = (Events(list(found[k][0]), *found[k][1:])
                 for k in ("XLA Ops", "XLA Modules"))
    return ops, mods, bench, engine, len(devices)


class Probe:
    """Per call of ``Engine.step``: the change of the prefill token
    counters.  Wraps the engine's step on the instance."""

    def __init__(self, eng):
        self.eng = eng
        self.steps: List[dict] = []
        step = eng.step

        def wrapped_step():
            c0 = self.counts()
            out = step()
            self.steps.append({k: v - c0[k]
                               for k, v in self.counts().items()})
            return out

        eng.step = wrapped_step

    def counts(self) -> Dict[str, int]:
        """The prefill token counters (none where the engine has none)."""
        return {k: c.value for k, c in getattr(
            self.eng.stats, "prefill_tokens", {}).items()}

    def in_span(self, rec) -> List[dict]:
        """The counter changes of the steps of ``rec`` begun inside its
        span: its engine steps are the calls the probe saw, in order."""
        calls = [s for s in rec.steps if s[2].startswith("step")]
        lo, hi = rec.span
        return [p for s, p in zip(calls, self.steps) if lo <= s[0] < hi]


def scope_maps(ctx, eng) -> Dict[str, list]:
    """Scope maps, by trace program name, of every prefill and splice
    executable the window can launch: each (bucket, batch size) that
    ``run.warm`` admits, in rows rounded up to a power of two as the
    engine's slab path launches them."""
    import jax
    from bench import run

    def spec_of(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    sharding=getattr(x, "sharding", None))

    fam = eng.family
    live = jax.tree.map(spec_of, eng.cache)
    out: Dict[str, list] = {"jit_prefill": [], "jit__lambda": []}
    for b in run.buckets(ctx):
        fresh = {}
        for k in run.admit_sizes(ctx):
            nb = min(1 << (k - 1).bit_length(), max(eng.slots, 1))
            if nb not in fresh:
                exe = fam._prefill_dkv.lower(
                    eng.params, np.zeros((nb, b), np.int32)).compile()
                out["jit_prefill"].append(scope_map(exe.as_text()))
                fresh[nb] = jax.tree.map(spec_of, exe.out_info[1])
            idx = jax.ShapeDtypeStruct((k,), np.int32)
            out["jit__lambda"].append(scope_map(fam._splice_dkv.lower(
                live, fresh[nb], idx, idx).compile().as_text()))
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def main(argv=None, *, require_chip: bool = True, root: Path = ROOT,
         bench_dir: Path = spec.BENCH_DIR, bm_root: Optional[Path] = None
         ) -> int:
    """One traced window.  ``require_chip=False`` and the directories are
    for tests, which run a small cell of their own on the CPU."""
    from bench import run
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    root, bench_dir = Path(root), Path(bench_dir)
    bm_root = Path(bm_root) if bm_root is not None else root
    try:
        ctx = run.prepare(args.workload, root=root, bench_dir=bench_dir,
                          bm_root=bm_root, require_chip=require_chip)
        run.validate(ctx)
    except (run.NoChip, spec.SpecError) as e:
        print(f"phases: no result: {e}", file=sys.stderr)
        return 2
    import jax
    # the scope maps compile the window's executables again: with op
    # metadata outside the cache key, the persistent cache could hand
    # back an executable of another checkout, without this one's scopes
    key = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, key)
    jax.config.update(key, True)
    try:
        out = traced_window(ctx, args.seed, args.seconds, root, bench_dir,
                            spec.load_benchmark(bm_root))
    finally:
        jax.config.update(key, was)
    print(json.dumps(out), flush=True)
    return 0


def traced_window(ctx, seed: int, seconds: float, root: Path,
                  bench_dir: Path, bm: dict) -> dict:
    """Set up, serve and trace one window as ``bench/run.py`` does, and
    read it (see the module docstring)."""
    from bench import run
    params = run.make_params(ctx, seed)
    eng = run.build_engine(ctx, params)
    run.warm(ctx, eng)
    setup_s = time.perf_counter() - run.T_START
    probe = Probe(eng)
    trace_dir = str(root / ".bench_trace" /
                    f"phases-{ctx.workload['name']}-{seed}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    rec = run.serve(ctx, eng, seed, seconds, trace_dir)
    rec.setup_s = setup_s
    t_read = time.perf_counter()
    ops, mods, bench, engine, ndev = read_xplane(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    wins = [e for e in bench if e[0] == "bench_window"]
    if not wins:
        raise RuntimeError("the trace holds no bench_window annotation")
    _, lo, d = wins[0]
    hi = lo + d
    labels = {}
    for i, s in enumerate(rec.steps):
        labels[f"bench_step_{i}"] = s[2]
        labels[f"bench_wait_{i}"] = s[2]
    rec.trace = tracing.reduce(ops, mods, [e for e in bench
                                           if e[0] != "bench_window"],
                               lo, hi, labels)
    rec.trace["devices"] = ndev
    rec.trace["idle_by_engine"] = idle_by_engine(
        tracing.idle_gaps(ops, lo, hi),
        [e for e in engine if e[1] < hi and e[1] + e[2] > lo])
    t_read = time.perf_counter() - t_read
    steps = probe.in_span(rec)
    t_maps = time.perf_counter()
    maps = scope_maps(ctx, eng)
    t_maps = time.perf_counter() - t_maps
    leaves = leaf_seconds(ops, mods, lo, hi)
    rec.trace["scope_seconds"] = scope_seconds(leaves, maps)
    rec.trace["prefill_tokens"] = {
        k: sum(s.get(k, 0) for s in steps)
        for k in ("prompt", "pad")}

    metrics = run.metrics_of(bm, rec, "per_layer", bench_dir)
    for name in METRICS:
        mod = spec.metric_module(name, bench_dir)
        v = mod.read(rec)
        if v is not None:
            metrics[name] = {"value": v, "unit": mod.UNIT}
    # what the scopes leave out of the admission programs, largest first
    outside = sorted(((f"{p} {tracing.op_key(n)}", sec)
                      for p, n, sec in leaves if p in maps
                      and op_scope(n, maps[p]) in (OTHER, None)),
                     key=lambda kv: -kv[1])[:15]
    # how many of those seconds matched an instruction with its shape,
    # not by its name alone (names repeat across executables)
    shaped = sum(sec for p, n, sec in leaves if p in maps and any(
        _INSTR.match(n) and _INSTR.match(n).group(1) in mp
        for mp in maps[p]))
    window = rec.trace["window_s"]
    return {
        "workload": rec.workload, "seed": seed,
        "device": {"platform": ctx.device.platform,
                   "kind": ctx.device.device_kind, "count": ctx.n_devices},
        "setup_s": setup_s, "trace_stop_s": rec.trace_stop_s,
        "trace_read_s": t_read, "scope_maps_s": t_maps,
        "admitted_in_span": len(rec.admitted_in_span()),
        "steps_in_span": len(steps), "compiles": dict(ctx.counter.counts),
        "metrics": metrics,
        # the harness's own step labels, for the shared-clock check
        "harness_step_idle": 100.0 * sum(
            v for k, v in rec.trace["idle_by_host"].items()
            if k.startswith("step")) / window if window > 0 else None,
        "outside_scopes": outside, "matched_with_shape_s": shaped,
        "trace": {k: rec.trace[k] for k in (
            "busy_s", "window_s", "idle_by_host", "idle_by_engine",
            "scope_seconds", "prefill_tokens", "modules", "module_counts",
            "devices")}}


if __name__ == "__main__":
    sys.exit(main())
