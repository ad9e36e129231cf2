"""The engine's spans on the profiler's clock, idle gaps labelled by them,
the named scopes of the admission programs, and the three readers that
``bench/phases.py`` adds."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import phases, spec  # noqa: E402
from bench.run import Record, ReqRec  # noqa: E402
from bench.tests._tiny import DATA  # noqa: E402


@pytest.fixture(scope="module")
def dkv_engine():
    import jax

    from repro.configs import all_archs
    from repro.models import model_fns
    from repro.serving import Engine
    cfg = all_archs()["deepseek-7b"].reduced()
    params = model_fns(cfg).init(jax.random.PRNGKey(0), cfg)
    return cfg, Engine(cfg, params, slots=2, max_len=64,
                       decompose_kv_rank=4, dkv_tail=16, decode_block=4)


def test_engine_spans_share_the_harness_clock(dkv_engine, tmp_path):
    """A small engine stepped under the profiler inside ``bench_step_<i>``
    annotations: each ``engine.step`` lies inside its harness step and
    every other engine span inside an ``engine.step``."""
    import jax

    from repro.serving import Request
    cfg, eng = dkv_engine
    rng = np.random.RandomState(0)
    for i in range(3):
        eng.submit(Request(uid=i, prompt=rng.randint(1, cfg.vocab, 8 + i,
                                                     dtype=np.int32),
                           max_new_tokens=6))
    jax.profiler.start_trace(str(tmp_path))
    try:
        k = 0
        while len(eng.sched) or any(r is not None for r in eng.live):
            with jax.profiler.TraceAnnotation(f"bench_step_{k}"):
                eng.step()
            k += 1
    finally:
        jax.profiler.stop_trace()
    _, _, bench, engine, _ = phases.read_xplane(str(tmp_path))
    steps = [e for e in engine if e[0] == "engine.step"]
    assert len(steps) == k == len(bench)
    for (_, s, d), (name, bs, bd) in zip(sorted(steps, key=lambda e: e[1]),
                                         sorted(bench, key=lambda e: e[1])):
        assert bs <= s and s + d <= bs + bd, name
    names = {e[0] for e in engine}
    assert {"engine.admit", "engine.admit.prepare", "engine.admit.launch",
            "engine.splice", "engine.admit.first_token",
            "engine.admit.activate", "engine.decode-block",
            "engine.decode.prepare", "engine.decode.launch",
            "engine.decode.readback", "engine.decode.deliver"} <= names
    for n, s, d in engine:
        if n != "engine.step":
            assert any(a <= s and s + d <= a + b for _, a, b in steps), n


def test_idle_by_engine_takes_the_innermost_span():
    spans = [("engine.step", 0, 100), ("engine.decode-block", 10, 80),
             ("engine.decode.readback", 40, 20), ("engine.step", 150, 50)]
    gaps = [(42, 50),       # readback inside decode-block inside step
            (12, 18),       # decode-block
            (92, 98),       # step alone
            (100, 140),     # between steps
            (190, 230)]     # midpoint 210: after the last step
    got = phases.idle_by_engine(gaps, spans)
    assert got == pytest.approx({"decode.readback": 8e-9,
                                 "decode-block": 6e-9, "step": 6e-9,
                                 phases.OUTSIDE_ENGINE: 80e-9})


def test_prefill_and_splice_programs_carry_the_scopes(dkv_engine):
    """The compiled admission programs of a small config map instructions
    to ``dcom.forward`` and ``dcom.lanczos`` (prefill) and to
    ``dcom.splice`` (splice)."""
    import jax
    cfg, eng = dkv_engine
    fam = eng.family
    text = fam._prefill_dkv.lower(eng.params, np.zeros((2, 16), np.int32)
                                  ).compile().as_text()
    scopes = set(phases.scope_map(text).values())
    assert {"dcom.forward", "dcom.lanczos"} <= scopes
    _, fresh = jax.eval_shape(fam._prefill_dkv, eng.params,
                              jax.ShapeDtypeStruct((2, 16), np.int32))
    idx = jax.ShapeDtypeStruct((2,), np.int32)
    text = fam._splice_dkv.lower(fresh, fresh, idx, idx).compile().as_text()
    assert "dcom.splice" in set(phases.scope_map(text).values())


HLO = """\
%fused_computation.4 (param_0: s32[2,16]) -> f32[2,16] {
  %param_0 = s32[2,16]{1,0} parameter(0)
  %dot.1 = f32[2,16]{1,0} convolution(%param_0, %param_0), metadata={op_name="jit(prefill)/dcom.lanczos/dot_general"}
  ROOT %tanh.2 = f32[2,16]{1,0} tanh(%dot.1), metadata={op_name="jit(prefill)/dcom.forward/tanh"}
}

ENTRY %main.1 (p: s32[2,16]) -> f32[2] {
  %fusion.3 = f32[2,16]{1,0} fusion(s32[2,16]{1,0} %p), kind=kOutput, calls=%fused_computation.4, metadata={op_name="jit(prefill)/dcom.lanczos/dot_general"}
  %custom-call.9 = f32[2]{0} custom-call(f32[2,16]{1,0} %fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(prefill)/dcom.lanczos/while/body/pallas_call"}
  ROOT %copy.2 = f32[2]{0} copy(f32[2]{0} %custom-call.9)
}
"""


def _ev(text):
    return text.strip().split(", metadata")[0]


def test_scope_seconds_by_program_and_scope():
    """Leaf ops by program and scope; the fusion counts under its root
    instruction's scope (``dcom.forward``), not its own metadata."""
    lines = HLO.splitlines()
    fusion, call, copy = (_ev(lines[i]) for i in (7, 8, 9))
    copy = copy.replace("ROOT ", "")
    ops = [(fusion, 0, 40), (call, 40, 50), (copy, 90, 5),
           ("%while.1 = f32[2]{0} while(f32[2]{0} %x), body=%b", 40, 50),
           ("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %y)", 200, 30),
           ("%fusion.8 = f32[4]{0} fusion(f32[4]{0} %z)", 100, 10)]
    mods = [("jit_prefill(11)", 0, 96), ("jit_run(12)", 200, 40)]
    leaves = phases.leaf_seconds(ops, mods, 0, 1000)
    assert ("no program", "%fusion.8 = f32[4]{0} fusion(f32[4]{0} %z)",
            pytest.approx(10e-9)) in leaves
    maps = {"jit_prefill": [phases.scope_map(HLO)]}
    got = phases.scope_seconds(leaves, maps)
    assert got == {
        "jit_prefill": pytest.approx({"dcom.forward": 40e-9,
                                      "dcom.lanczos": 50e-9,
                                      phases.OTHER: 5e-9}),
        "jit_run": pytest.approx({phases.OTHER: 30e-9}),
        "no program": pytest.approx({phases.OTHER: 10e-9})}
    # an instruction no map knows is counted, and named so
    maps = {"jit_prefill": [{}]}
    assert set(phases.scope_seconds(leaves, maps)["jit_prefill"]) == \
        {"unmapped"}


def _record(trace):
    reqs = [ReqRec(uid=i, due=1.0, prompt_len=100, measured=True,
                   dispatch=1.5 + i) for i in range(2)]
    steps = [(1.4, 2.0, "step: admission and decode", 1),
             (2.4, 3.0, "step: admission and decode", 1)]
    return Record(workload="x", seconds=4.0, model={}, engine_cfg={},
                  device_kind="TPU v5 lite", requests=reqs, steps=steps,
                  trace=trace, span=(1.0, 4.0))


def read(name, rec):
    return spec.metric_module(name).read(rec)


def test_readers_on_a_small_record():
    rec = _record({
        "window_s": 2.0,
        "idle_by_engine": {"decode.readback": 0.03, "admit.prepare": 0.01,
                           phases.OUTSIDE_ENGINE: 0.5},
        "scope_seconds": {"jit_prefill": {"dcom.lanczos": 0.3,
                                          "dcom.forward": 0.2},
                          "jit_run": {phases.OTHER: 1.0}},
        "prefill_tokens": {"prompt": 26, "pad": 38}})
    assert read("step_idle", rec) == pytest.approx(100 * 0.04 / 2.0)
    assert read("lanczos_ms", rec) == pytest.approx(0.3 / 2 * 1e3)
    assert read("admit_pad_share", rec) == pytest.approx(100 * 38 / 64)


def test_readers_are_silent_where_the_program_gives_nothing():
    """A trace of a program without engine spans, scopes or counters."""
    empty = _record({"window_s": 2.0,
                     "idle_by_engine": {phases.OUTSIDE_ENGINE: 0.5},
                     "scope_seconds": {"jit_prefill": {phases.OTHER: 1.0}},
                     "prefill_tokens": {"prompt": 0, "pad": 0}})
    for rec in (empty, _record(None), _record({"window_s": 2.0})):
        for name in phases.METRICS:
            assert read(name, rec) is None, name


def test_phases_run_on_a_cpu_cell(capsys):
    """The tool end to end on the small CPU cell: the CPU trace has no
    device ops, so the whole span is idle and every engine step holds
    some of it; the counters give the padding share."""
    import json
    rc = phases.main(["--workload", "tiny.open", "--seed", "5",
                      "--seconds", "3"], require_chip=False,
                     bench_dir=DATA, bm_root=DATA)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    m = out["metrics"]
    assert 0 < m["step_idle"]["value"] <= 100 + 1e-9
    assert 0 < m["admit_pad_share"]["value"] < 100
    assert out["trace"]["prefill_tokens"]["prompt"] > 0
    assert out["steps_in_span"] > 0
