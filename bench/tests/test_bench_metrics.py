"""End-to-end arithmetic on a synthetic delivery log."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import spec  # noqa: E402
from bench.run import Record, ReqRec  # noqa: E402


def _record(stall=0.0):
    """Four requests, due every 0.5 s; each gets its first token 0.2 s
    after it is due, then three deliveries of 2 tokens every 0.1 s.  With
    ``stall``, request 1's third delivery comes ``stall`` seconds late."""
    reqs = []
    for i in range(4):
        due = 0.5 * i
        ds = [(due + 0.2, 1), (due + 0.3, 2), (due + 0.4, 2), (due + 0.5, 2)]
        if stall and i == 1:
            ds = ds[:2] + [(t + stall, n) for t, n in ds[2:]]
        reqs.append(ReqRec(uid=i, due=due, prompt_len=100,
                           measured=True, dispatch=due + 0.05,
                           deliveries=ds, done=ds[-1][0]))
    return Record(workload="x", seconds=2.5, model={},
                  engine_cfg={}, device_kind="TPU v5 lite", requests=reqs,
                  steps=[], span=(0.0, 2.5))


def read(name, rec):
    return spec.metric_module(name).read(rec)


def test_values_on_a_plain_log():
    rec = _record()
    assert read("ttft_p90_ms", rec) == pytest.approx(200.0)
    assert read("tpot_ms", rec) == pytest.approx(0.3 / 6 * 1e3)
    assert read("itl_p95_ms", rec) == pytest.approx(100.0)
    assert read("queue_wait_p90_ms", rec) == pytest.approx(50.0)
    # 4 requests x 7 tokens, all delivered before the window closes
    assert read("output_tok_s", rec) == pytest.approx(28 / 2.5)


def test_a_one_second_stall_moves_tpot_and_itl():
    plain, stalled = _record(), _record(stall=1.0)
    assert read("tpot_ms", stalled) == pytest.approx(
        read("tpot_ms", plain) + 1.0 / 24 * 1e3)
    assert read("itl_p95_ms", stalled) > 500.0 > read("itl_p95_ms", plain)
    assert read("ttft_p90_ms", stalled) == read("ttft_p90_ms", plain)


def test_itl_leaves_out_gaps_past_the_close_and_over_the_trace_start():
    """The profiler stalls the host where a traced span starts and where
    the window closes; a gap over either is not a gap between tokens."""
    r = ReqRec(uid=0, due=0.0, prompt_len=10, measured=True,
               deliveries=[(0.1, 1), (0.2, 1), (0.9, 1), (1.3, 1), (4.0, 1)])
    rec = Record(workload="x", seconds=2.0, model={}, engine_cfg={},
                 device_kind="TPU v5 lite", requests=[r], steps=[],
                 span=(0.0, 2.0))
    assert read("itl_p95_ms", rec) == pytest.approx(
        float(np.percentile([0.1, 0.7, 0.4], 95)) * 1e3)
    rec.span = (0.5, 2.0)
    assert read("itl_p95_ms", rec) == pytest.approx(
        float(np.percentile([0.1, 0.4], 95)) * 1e3)


def test_per_layer_readers_are_silent_without_a_trace():
    rec = _record()
    for name in ("device_idle", "decode_round_ms", "reorth_roofline",
                 "admit_mfu"):
        assert read(name, rec) is None


MODEL = {"num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
         "head_dim": 16, "d_ff": 128, "vocab": 256}


def _traced(prefill_s=0.5, decode_s=2.0):
    """A traced span [1, 3): admissions dispatched at 0.5 (before it), 1.2
    and, by a step begun at 2.9 that ends at 3.1, at 3.05; device time from
    the trace's program line."""
    reqs = [ReqRec(uid=i, due=t - 0.1, prompt_len=n, measured=True,
                   dispatch=t) for i, (t, n) in
            enumerate([(0.5, 300), (1.2, 100), (3.05, 200)])]
    steps = [(0.4, 0.9, "step: admission and decode", 1),
             (1.1, 1.6, "step: admission and decode", 1),
             (2.9, 3.1, "step: admission and decode", 1)]
    trace = {"modules": {"jit_prefill(1)": prefill_s, "jit__lambda(2)": 0.0,
                         "jit_run(3)": decode_s}, "ops": {}}
    return Record(workload="x", seconds=3.0, model=MODEL, engine_cfg={},
                  device_kind="TPU v5 lite", requests=reqs, steps=steps,
                  trace=trace, span=(1.0, 3.0),
                  block=spec.block_module("dense"))


def test_admit_mfu_is_forward_flops_over_admission_device_time():
    rec = _traced()
    assert [r.uid for r in rec.admitted_in_span()] == [1, 2]
    dense = spec.block_module("dense")
    flops = dense.forward_flops(MODEL, 100) + dense.forward_flops(MODEL, 200)
    assert read("admit_mfu", rec) == pytest.approx(
        100.0 * flops / (0.5 * 197e12))
    # decode time is not admission time
    assert read("admit_mfu", _traced(decode_s=9.0)) == \
        read("admit_mfu", rec)
    assert read("admit_mfu", _traced(prefill_s=1.0)) == pytest.approx(
        read("admit_mfu", rec) / 2)
