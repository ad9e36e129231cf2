"""The output check passes the served path and fails it when the timed
path is broken underneath, or when the float8 control takes its place.

Each test drives a whole run of a small CPU cell through the harness
(weights, engine, warm-up, window, sample, reference), skipping only the
look for a chip."""
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench.tests._tiny import DATA, run_cell  # noqa: E402


def _fresh_programs():
    """Drop the engine's compiled programs, so that a fault planted in a
    function they call is traced in (and taken out again)."""
    import jax
    from repro.serving import families
    for name in dir(families):
        fn = getattr(families, name)
        if name.startswith("_jitted") and hasattr(fn, "cache_clear"):
            fn.cache_clear()
    jax.clear_caches()


def test_served_path_is_correct():
    rc, res, log = run_cell("tiny.open", seed=2 ** 31 + 9)
    assert rc == 0, log
    assert res["correct"] is True, log
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "check"
    for name, got in res["check"].items():
        assert got["value"] <= got["limit"], name
    assert set(res["metrics"]) == {"setup_s", "ttft_p90_ms", "tpot_ms"}


def test_closed_loop_cell_reports_throughput():
    rc, res, log = run_cell("tiny.closed", seed=4)
    assert rc == 0 and res["correct"] is True, log
    assert res["metrics"]["output_tok_s"]["value"] > 0


def test_an_altered_token_fails_the_check(monkeypatch):
    """A token altered where it is produced: the sampler's pick for the
    first slot is replaced by its neighbour in the vocabulary."""
    import repro.serving as S

    def bad(logits, k):
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        return tok.at[0].set((tok[0] + 1) % 512)

    monkeypatch.setattr(S, "greedy_sampler", bad)
    rc, res, log = run_cell("tiny.open", seed=21)
    assert rc == 0, log
    assert res["correct"] is False


def test_broken_factors_fail_the_check(monkeypatch):
    """The factorization returns its factors with the left one zeroed:
    decode then attends to nothing of the prompt."""
    from repro.engine import DecomposeEngine
    orig = DecomposeEngine.decompose_kv

    def broken(self, x, rank, iters=None, exact=False):
        u, vt = orig(self, x, rank, iters=iters, exact=exact)
        return jnp.zeros_like(u), vt

    monkeypatch.setattr(DecomposeEngine, "decompose_kv", broken)
    _fresh_programs()
    try:
        rc, res, log = run_cell("tiny.open", seed=22)
    finally:
        monkeypatch.undo()
        _fresh_programs()
    assert rc == 0, log
    assert res["correct"] is False
    assert res["check"]["mean_gap"]["value"] > \
        res["check"]["mean_gap"]["limit"]


def test_float8_control_fails_a_limit():
    """The reference in float8 put in the program's place, at the CPU
    cell's size and over a window long enough to fill the cell's check
    sample, fails at least one of the cell's limits."""
    from bench import run
    ctx = run.prepare("tiny.open", root=run.ROOT, bench_dir=DATA,
                      bm_root=DATA, require_chip=False)
    params = run.make_params(ctx, 31)
    eng = run.build_engine(ctx, params)
    rec = run.serve(ctx, eng, 31, 5.0, None)
    sample = run.sample_for_check(ctx, rec, 31)
    from bench.traffic import Source
    src = Source(ctx.traffic, 31, ctx.cfg.vocab, ctx.cell["rate_rps"])
    prompts = {r.uid: src.item(r.uid).prompt for r in sample}
    prog, _ = run.check(ctx, params, sample, prompts)
    ctl, _ = run.check(ctx, params, sample, prompts, control=True)
    lim = ctx.cell["check"]["limits"]
    assert all(prog[k] <= lim[k] for k in lim), prog
    assert any(ctl[k] > lim[k] for k in lim), ctl


@pytest.mark.parametrize("argv", [["--workload", "tiny.open"],
                                  ["--workload", "tiny.open", "--seed", "1",
                                   "--seconds", "1"]])
def test_bad_arguments_exit_non_zero(argv):
    from bench import run
    with pytest.raises(SystemExit) as e:
        run.main(argv, require_chip=False, bench_dir=DATA, bm_root=DATA)
    assert e.value.code != 0


def test_no_chip_no_result(capsys):
    """On the CPU the command itself refuses: exit 2 and no result line."""
    from bench import run
    rc = run.main(["--workload", "tiny.open", "--seed", "1", "--seconds",
                   "1", "--trace", "0"], bench_dir=DATA, bm_root=DATA)
    assert rc == 2
    assert capsys.readouterr().out == ""
