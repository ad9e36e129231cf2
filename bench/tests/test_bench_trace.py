"""Trace reduction: busy union, per-op sums, idle gaps and their labels."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import spec, tracing  # noqa: E402

EXCERPT = Path(__file__).resolve().parent / "data" / "trace_excerpt.json"


def test_union_of_overlapping_events():
    ev = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("d", 32, 1)]
    assert tracing.union_ns(ev, 0, 100) == 20
    assert tracing.union_ns(ev, 8, 33) == 7 + 3       # clipped to window
    assert tracing.idle_gaps(ev, 0, 40) == [(15, 30), (35, 40)]


def test_sums_labels_and_reduce():
    ops = [("fusion.1", 0, 100), ("reorth_kernel", 100, 300),
           ("reorth_kernel", 500, 300), ("fusion.1", 900, 50)]
    mods = [("jit_prefill(1)", 0, 800), ("jit_run(2)", 900, 50)]
    host = [("bench_step_0", 0, 820), ("bench_wait_1", 820, 180)]
    labels = {"bench_step_0": "step: admission and decode",
              "bench_wait_1": "wait: no request due"}
    red = tracing.reduce(ops, mods, host, 0, 1000, labels)
    assert red["busy_s"] == pytest.approx(750e-9)
    assert red["ops"]["reorth_kernel"] == pytest.approx(600e-9)
    assert red["modules"]["jit_run(2)"] == pytest.approx(50e-9)
    # gaps [400, 500) in the step, [800, 900) mostly and [950, 1000)
    # wholly in the wait
    assert red["idle_by_host"] == pytest.approx(
        {"step: admission and decode": 100e-9,
         "wait: no request due": 150e-9})
    assert tracing.label_gaps([(0, 10)], []) == [tracing.OUTSIDE]


@pytest.mark.skipif(not EXCERPT.is_file(), reason="no recorded excerpt")
def test_recorded_tpu_trace_excerpt():
    """Events cut from a traced window of the long-prompt cell on one
    TPU v5e: the re-orth kernel and the decode-block program are found by
    the names the metric readers match."""
    ex = json.loads(EXCERPT.read_text())
    ops = [tuple(e) for e in ex["ops"]]
    mods = [tuple(e) for e in ex["modules"]]
    red = tracing.reduce(ops, mods, [], ex["lo"], ex["hi"])
    assert red["busy_s"] == pytest.approx(ex["busy_s"], rel=1e-9)
    kernel = spec.metric_module("reorth_roofline").KERNEL
    prog = spec.metric_module("decode_round_ms").PROGRAM
    assert sum(v for k, v in red["ops"].items() if kernel.search(k)) > 0
    assert sum(v for k, v in red["modules"].items() if prog.search(k)) > 0
