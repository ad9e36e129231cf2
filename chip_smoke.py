#!/usr/bin/env python3
"""Chip smoke test: granite-3-2b served at published widths on a TPU.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py             # one chip: kernel, serve and decode phases
    python chip_smoke.py --chips 4   # four chips: the DP-sharded serve path only

It drives the main path once through the entry points a user calls —
``repro.launch.serve.main`` → ``serving.Engine`` → decomposed-KV prefill →
batched Lanczos with the compiled re-orthogonalization kernel → decode
through the low-rank cache — with random weights made from ``--seed``, and
checks what comes out.  Any failed check exits non-zero.  Without a TPU, or
without the ``repro`` package next to this file, it exits non-zero at once
and prints no result.  The last line of a passing run is one JSON object:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Earlier lines carry information only: phase wall times (compilation
included), tok/s, TTFT, ITL and peak device memory.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import io
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "granite-3-2b"
SLOTS, REQUESTS, PROMPT, MAX_NEW = 8, 16, 1024, 64
RANK, TAIL, EXPANSION = 64, 128, 8
DECODE_STEPS = 8

#: pallas-vs-reference reconstruction gap, ‖R_pallas − R_ref‖_F / ‖R_ref‖_F.
#: Both run the same CGS2 Lanczos (rank + 8 steps) in float32 at full matmul
#: precision on the same K/V, so only summation order differs: float32
#: rounding (6e-8) grown through 72 re-orthogonalized steps and the rank-64
#: cut stays well under 1e-3, while a kernel fault (a block skipped, a pass
#: missing, a Q block misaligned, matmuls at bf16 precision) moves the
#: reconstruction by 1e-2 or more.
KERNEL_TOL = 1e-3
#: dkv-vs-dense decode logits, ‖L_dkv − L_dense‖_F / ‖L_dense‖_F per step.
#: At rank 512 (the whole KV width) the factorization is lossless in exact
#: arithmetic; the cache stores U·Σ and Vᵀ in bf16 (two roundings of 2^-9
#: where the dense cache has one) and 40 bf16 layers carry that into the
#: logits: the reduced 40-layer model shows 2e-2 on the CPU.  A wrong factor,
#: layer or tail moves the logits by O(1).
DECODE_TOL = 5e-2


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def info(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def serve_args(seed: int, *extra: str) -> list:
    return ["--arch", ARCH, "--seed", str(seed),
            "--slots", str(SLOTS), "--requests", str(REQUESTS),
            "--prompt-len", str(PROMPT), "--max-new", str(MAX_NEW),
            "--max-len", str(PROMPT + TAIL),
            "--decompose-kv-rank", str(RANK), "--dkv-tail", str(TAIL),
            "--decode-block", "8", "--expansion", str(EXPANSION), *extra]


def prompts(vocab: int, seed: int, n: int):
    """The first ``n`` prompts ``serve.main`` submits for ``seed``."""
    import numpy as np
    rng = np.random.RandomState(seed)
    return np.stack([rng.randint(0, vocab, PROMPT, dtype=np.int32)
                     for _ in range(n)])


def peak_bytes() -> int:
    import jax
    return int((jax.devices()[0].memory_stats() or {})
               .get("peak_bytes_in_use", 0))


def run_serve(args: list, label: str):
    """``serve.main(args)`` with its per-request lines kept out of the log;
    checks that every request finished with its whole budget and that every
    token is inside the vocabulary."""
    from repro.launch import serve
    out = io.StringIO()
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(out):
        eng, done = serve.main(args)
    wall = time.perf_counter() - t0
    for line in out.getvalue().splitlines():
        if not line.startswith("req "):
            info(f"[{label}] {line}")
    vocab = eng.cfg.vocab
    check(len(done) == REQUESTS,
          f"{label}: {len(done)} of {REQUESTS} requests finished")
    for r in done:
        check(len(r.out_tokens) == MAX_NEW,
              f"{label}: request {r.uid} emitted {len(r.out_tokens)} "
              f"of {MAX_NEW} tokens")
        check(all(0 <= t < vocab for t in r.out_tokens),
              f"{label}: request {r.uid} emitted a token outside "
              f"[0, {vocab})")
    s = eng.stats
    info(f"[{label}] wall={wall:.3f}s (compilation included) "
         f"tok/s={s.tokens_out / max(s.wall_s, 1e-9):.3f} "
         f"ttft_p50={s.ttft_s.quantile(0.5) * 1e3:.3f}ms "
         f"itl_p50={s.itl_s.quantile(0.5) * 1e3:.3f}ms "
         f"peak_bytes_in_use={peak_bytes()}")
    tokens = {r.uid: list(r.out_tokens) for r in done}
    return eng, tokens


def kernel_phase(cfg, params, seed: int) -> None:
    """Factorize the real prefill K/V of the first admitted batch with the
    compiled kernel and with the jnp reference; the reconstructions must
    agree, and the kernel must be compiled, not interpreted."""
    import jax
    import jax.numpy as jnp
    from repro.engine import DecomposeEngine, EngineConfig
    from repro.models import transformer as T

    toks = jnp.asarray(prompts(cfg.vocab, seed, SLOTS))
    _, cache = jax.jit(lambda p, t: T.prefill(p, cfg, t, PROMPT))(params,
                                                                   toks)
    kvw = cfg.num_kv_heads * cfg.resolved_head_dim
    recon = {}
    for backend in ("pallas", "reference"):
        eng = DecomposeEngine(EngineConfig(backend=backend,
                                           expansion=EXPANSION,
                                           kv_rank=RANK))
        fn = jax.jit(lambda kv, eng=eng: [
            jnp.einsum("btr,brh->bth", *eng.decompose_kv(
                kv[n].reshape(-1, PROMPT, kvw).astype(jnp.float32), RANK))
            for n in ("k", "v")])
        t0 = time.perf_counter()
        with jax.default_matmul_precision("highest"):
            compiled = fn.lower(cache).compile()
        if backend == "pallas":
            check("tpu_custom_call" in compiled.as_text(),
                  "the pallas decomposition holds no tpu_custom_call: the "
                  "kernel was not compiled for the chip")
        recon[backend] = jax.block_until_ready(compiled(cache))
        info(f"[kernel] {backend}: decompose_kv of 2 x "
             f"[{cfg.num_layers * SLOTS}, {PROMPT}, {kvw}] at rank {RANK} "
             f"in {time.perf_counter() - t0:.3f}s (compilation included)")
    for i, name in enumerate(("k", "v")):
        a = cache[name].reshape(-1, PROMPT, kvw).astype(jnp.float32)
        r_p, r_r = recon["pallas"][i], recon["reference"][i]
        gap = float(jnp.linalg.norm(r_p - r_r) / jnp.linalg.norm(r_r))
        res_p = float(jnp.linalg.norm(a - r_p) / jnp.linalg.norm(a))
        res_r = float(jnp.linalg.norm(a - r_r) / jnp.linalg.norm(a))
        info(f"[kernel] {name}: pallas-vs-reference gap={gap:.3e} "
             f"(tol {KERNEL_TOL:g}); rank-{RANK} residual "
             f"pallas={res_p:.6f} reference={res_r:.6f}")
        check(gap < KERNEL_TOL, f"{name}: pallas-vs-reference "
              f"reconstruction gap {gap:.3e} >= {KERNEL_TOL:g}")


def check_served_prefill(eng) -> None:
    """The prefill program the serving engine ran for a full admission
    batch holds the compiled kernel (the persistent cache already holds
    this executable, so compiling it again is a lookup)."""
    import jax
    import jax.numpy as jnp
    toks = jax.ShapeDtypeStruct((SLOTS, PROMPT), jnp.int32)
    text = eng.family._prefill_dkv.lower(eng.params, toks).compile().as_text()
    check("tpu_custom_call" in text, "the served prefill holds no "
          "tpu_custom_call: the kernel was not compiled for the chip")
    info(f"[serve] served prefill [{SLOTS}, {PROMPT}] holds "
         f"{text.count('tpu_custom_call')} tpu_custom_call sites")


def decode_phase(cfg, params, seed: int) -> None:
    """Two requests through the dkv cache at full KV width (exact SVD, so
    lossless) against the dense cache: teacher-forced decode logits over
    DECODE_STEPS steps must agree."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.engine import DecomposeEngine, EngineConfig
    from repro.models import decomposed_kv as DK
    from repro.models import transformer as T

    kvw = cfg.num_kv_heads * cfg.resolved_head_dim
    toks = jnp.asarray(prompts(cfg.vocab, seed, 2))
    eng = DecomposeEngine(EngineConfig(kv_rank=kvw, kv_exact=True))
    lg_d, c_d = jax.jit(lambda p, t: T.prefill(
        p, cfg, t, PROMPT + DECODE_STEPS))(params, toks)
    _, c_k = jax.jit(lambda p, t: DK.prefill_dkv(
        p, cfg, t, kvw, tail=DECODE_STEPS, exact=True, engine=eng))(params,
                                                                     toks)
    check(c_k["k_u"].shape[-1] == kvw,
          f"exact factor rank {c_k['k_u'].shape[-1]} != kv width {kvw}")
    step_d = jax.jit(lambda p, t, c, pos: T.decode_step(p, cfg, t, c, pos))
    step_k = jax.jit(lambda p, t, c, pos: DK.decode_step_dkv(
        p, cfg, t, c, pos, frozen_len=PROMPT))
    tok = jnp.argmax(lg_d[:, :cfg.vocab], axis=-1).astype(jnp.int32)
    worst = 0.0
    for i in range(DECODE_STEPS):
        pos = jnp.full((2,), PROMPT + i, jnp.int32)
        l_d, c_d = step_d(params, tok, c_d, pos)
        l_k, c_k = step_k(params, tok, c_k, pos)
        l_d = np.asarray(l_d[:, :cfg.vocab], np.float32)
        l_k = np.asarray(l_k[:, :cfg.vocab], np.float32)
        check(np.isfinite(l_k).all(), f"step {i}: non-finite dkv logits")
        gap = float(np.linalg.norm(l_k - l_d) / np.linalg.norm(l_d))
        worst = max(worst, gap)
        check(gap < DECODE_TOL, f"decode step {i}: dkv-vs-dense logit gap "
              f"{gap:.3e} >= {DECODE_TOL:g}")
        tok = jnp.asarray(l_d.argmax(axis=-1), jnp.int32)
    info(f"[decode] rank {kvw} exact dkv vs dense cache, {DECODE_STEPS} "
         f"steps x 2 requests: worst logit gap={worst:.3e} "
         f"(tol {DECODE_TOL:g})")


def one_chip(seed: int) -> None:
    from repro.configs.base import get_arch
    from repro.models import api

    t0 = time.perf_counter()
    cfg = get_arch(ARCH)
    params = api.init_params(cfg, seed)
    kernel_phase(cfg, params, seed)
    decode_phase(cfg, params, seed)
    del params
    gc.collect()
    info(f"[kernel+decode] {time.perf_counter() - t0:.3f}s")

    eng, slot_tokens = run_serve(serve_args(seed), "serve")
    check(eng.dengine.resolved_backend == "pallas",
          f"serving resolved backend {eng.dengine.resolved_backend!r}, "
          "not the compiled kernels")
    check_served_prefill(eng)
    del eng
    gc.collect()
    eng, paged_tokens = run_serve(serve_args(seed, "--paged"), "serve-paged")
    same = sum(slot_tokens[u] == paged_tokens[u] for u in slot_tokens)
    info(f"[serve-paged] {same} of {REQUESTS} requests token-identical to "
         "the slot cache")


def four_chips(seed: int, n: int) -> None:
    """The DP-sharded serve path on an n x 1 mesh against the same requests
    on one device: tokens must match, the low-rank cache must be sharded
    over the slots, and the params must sit replicated on the mesh with no
    implicit device-to-device transfer during the run."""
    import jax

    eng1, tok1 = run_serve(serve_args(seed), "serve-1dev")
    del eng1
    gc.collect()
    with jax.transfer_guard_device_to_device("disallow"):
        eng, tokn = run_serve(serve_args(seed, "--mesh", f"{n}x1"),
                              f"serve-{n}dev")
    check(tokn == tok1, f"{n}-device tokens differ from 1-device tokens "
          f"for {sum(tokn[u] != tok1[u] for u in tok1)} requests")
    k_u = eng.cache["k_u"]
    shards = k_u.addressable_shards
    check(len(shards) == n, f"k_u has {len(shards)} addressable shards")
    check(all(s.data.shape[1] == SLOTS // n for s in shards),
          f"k_u is not split over the slots: shard shapes "
          f"{[s.data.shape for s in shards]}")
    devs = set(eng.mesh.devices.flat)
    for leaf in jax.tree_util.tree_leaves(eng.params):
        check(leaf.sharding.is_fully_replicated
              and leaf.sharding.device_set == devs,
              f"a params leaf is not replicated on the mesh: "
              f"{leaf.sharding}")
    info(f"[serve-{n}dev] tokens match 1 device; k_u {k_u.shape} in "
         f"{len(shards)} shards of {shards[0].data.shape}; params "
         "replicated on the mesh")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: kernel, serve and decode phases on one chip; "
                         "4: the DP-sharded serve path on a 4x1 mesh and "
                         "its one-device comparison, nothing else")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro" / "launch" / "serve.py").is_file():
        print("chip_smoke: the repro package is not next to this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    enable_compile_cache()
    info(f"device {dev.device_kind} x {len(devices)}; jax {jax.__version__}")
    t0 = time.perf_counter()
    try:
        if args.chips == 1:
            one_chip(args.seed)
        else:
            four_chips(args.seed, args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    info(f"total {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
