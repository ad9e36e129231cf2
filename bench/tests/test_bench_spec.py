"""The benchmark finds every part by name, and a cell, configuration,
traffic mix, metric and model block added as new files only are run."""
import json
import shutil
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import spec  # noqa: E402
from bench.tests._tiny import DATA, run_cell  # noqa: E402


def test_every_name_in_benchmark_json_has_its_files():
    bm = spec.load_benchmark()
    for w in bm["workloads"]:
        assert spec.config_file(w["config"])["model"]
        assert spec.traffic_file(w["traffic"])["loop"] in ("open", "closed")
        assert "serving" in spec.cell_file(w["name"])
    for kind in ("end_to_end", "per_layer"):
        for m in bm[kind]:
            assert callable(spec.metric_module(m["name"]).read)
    for c in bm["configs"]:
        assert (spec.ROOT / c["file"]).is_file()


def test_metrics_of_a_cell_follow_their_workloads_key():
    bm = spec.load_benchmark(DATA)
    names = [m["name"] for m in spec.cell_metrics(bm, "tiny.closed",
                                                  "end_to_end")]
    assert "output_tok_s" in names and "ttft_p90_ms" not in names
    assert "setup_s" in names


def test_unknown_names_are_errors(tmp_path):
    with pytest.raises(spec.SpecError):
        spec.workload(spec.load_benchmark(DATA), "no-such-cell")
    with pytest.raises(spec.SpecError):
        spec.metric_module("no_such_metric")
    conf = spec.config_file("tiny", DATA)
    assert spec.config_block(conf, DATA) is spec.block_module("dense")
    with pytest.raises(spec.SpecError):
        spec.config_block({k: v for k, v in conf.items() if k != "block"},
                          DATA)
    with pytest.raises(spec.SpecError):
        spec.config_block(dict(conf, block="no_such_block"), DATA)
    (tmp_path / "blocks").mkdir()
    (tmp_path / "blocks" / "layout_only.py").write_text(
        "def layout(model):\n    return {}\n")
    with pytest.raises(spec.SpecError, match="served_logits"):
        spec.block_module("layout_only", tmp_path)


def test_new_cell_config_traffic_and_metric_from_files_only(tmp_path):
    """A directory of its own with new files, and no edit to any file the
    benchmark has: the harness runs the new cell and reports the new
    per-layer metric."""
    d = tmp_path / "bench"
    for sub in ("configs", "traffic", "cells", "metrics"):
        (d / sub).mkdir(parents=True)
    shutil.copy(DATA / "configs" / "tiny.json", d / "configs" / "tiny2.json")
    (d / "traffic" / "burst.json").write_text(json.dumps({
        "loop": "open", "block": 6,
        "prompt": {"dist": "uniform", "min": 10, "max": 30},
        "output": {"dist": "uniform", "min": 3, "max": 6}}))
    (d / "cells" / "tiny2.burst.json").write_text(json.dumps({
        "rate_rps": 5.0,
        "serving": {"slots": 2, "sched_bucket": 32, "sched_max_admit": 2,
                    "kv_tail": 8, "max_len": 64},
        "check": {"tokens": 8, "limits": {"mean_gap": 0.01}}}))
    (d / "metrics" / "deliveries_per_request.py").write_text(
        "UNIT = 'count'\n\n\ndef read(rec):\n"
        "    m = rec.measured\n"
        "    return sum(len(r.deliveries) for r in m) / len(m)\n")
    bm = json.loads((DATA / "BENCHMARK.json").read_text())
    bm["configs"] = [dict(bm["configs"][0], name="tiny2",
                          file="bench/configs/tiny2.json")]
    bm["workloads"] = [{"name": "tiny2.burst", "config": "tiny2",
                        "traffic": "burst", "chips": 1, "why": "test"}]
    bm["per_layer"] = [{"name": "deliveries_per_request", "unit": "count",
                        "better": "lower", "source": "host_clock",
                        "layer": "engine loop", "moves": "tpot_ms"}]
    for m in bm["end_to_end"]:
        m.pop("workloads", None)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    rc, res, log = run_cell("tiny2.burst", trace=1, bench_dir=d,
                            bm_root=tmp_path)
    assert rc == 0, log
    assert res["correct"] is True, log
    assert res["metrics"]["deliveries_per_request"]["value"] >= 1


#: a block of its own: the dense decoder's weights and reference, whose
#: chip holds twice the dense forward work and factorizes K/V in half
#: its layers; it counts the calls to its reference
HALF_DENSE = """\
from bench import spec

dense = spec.block_module("dense")
layout = dense.layout
CALLS = []


def served_logits(*args, **kw):
    CALLS.append(kw.get("control", False))
    return dense.served_logits(*args, **kw)


def forward_flops(model, prompt_len):
    return 2 * dense.forward_flops(model, prompt_len)


def reorth_needed(model, prompt_len, rank, iters_extra, a_bytes=2):
    fl, by = dense.reorth_needed(model, prompt_len, rank, iters_extra,
                                 a_bytes)
    return fl / 2, by / 2
"""


def _half_dense(d: Path) -> None:
    (d / "blocks").mkdir(parents=True, exist_ok=True)
    (d / "blocks" / "half_dense.py").write_text(HALF_DENSE)


def test_new_block_config_traffic_and_cell_from_files_only(tmp_path):
    """A block, a configuration that names it, a traffic mix and a cell,
    as new files in a directory of their own: the harness makes the
    block's weights, checks against the block's reference, and the run is
    correct."""
    d = tmp_path / "bench"
    for sub in ("configs", "traffic", "cells"):
        (d / sub).mkdir(parents=True)
    _half_dense(d)
    conf = spec.config_file("tiny", DATA)
    (d / "configs" / "tiny-half.json").write_text(
        json.dumps(dict(conf, block="half_dense")))
    (d / "traffic" / "few.json").write_text(json.dumps({
        "loop": "open", "block": 4,
        "prompt": {"dist": "uniform", "min": 10, "max": 30},
        "output": {"dist": "uniform", "min": 3, "max": 6}}))
    (d / "cells" / "tiny-half.few.json").write_text(json.dumps({
        "rate_rps": 5.0,
        "serving": {"slots": 2, "sched_bucket": 32, "sched_max_admit": 2,
                    "kv_tail": 8, "max_len": 64},
        "check": {"tokens": 8, "limits": {"mean_gap": 0.01}}}))
    bm = json.loads((DATA / "BENCHMARK.json").read_text())
    bm["configs"] = [dict(bm["configs"][0], name="tiny-half",
                          file="bench/configs/tiny-half.json")]
    bm["workloads"] = [{"name": "tiny-half.few", "config": "tiny-half",
                        "traffic": "few", "chips": 1, "why": "test"}]
    for m in bm["end_to_end"]:
        m.pop("workloads", None)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    rc, res, log = run_cell("tiny-half.few", bench_dir=d, bm_root=tmp_path)
    assert rc == 0, log
    assert res["correct"] is True, log
    record = json.loads(next(ln for ln in log.splitlines()
                             if ln.startswith("bench-record "))[13:])
    assert record["block"] == "half_dense"
    assert spec.block_module("half_dense", d).CALLS == \
        [False] * len(record["check_sample"])


def test_roofline_readers_count_the_records_block(tmp_path):
    """``admit_mfu`` and ``reorth_roofline`` on the recorded TPU trace
    excerpt read the work of the record's block: twice the dense forward
    FLOPs double one, half the re-orth work halves the other."""
    from bench import tracing
    from bench.run import Record, ReqRec
    _half_dense(tmp_path)
    ex = json.loads((DATA / "trace_excerpt.json").read_text())
    trace = tracing.reduce([tuple(e) for e in ex["ops"]],
                           [tuple(e) for e in ex["modules"]], [],
                           ex["lo"], ex["hi"])
    reqs = [ReqRec(uid=i, due=0.5, prompt_len=n, measured=True, dispatch=1.0)
            for i, n in enumerate((700, 1000))]

    def read(name, block):
        rec = Record(workload="x", seconds=10.0,
                     model=spec.config_file("granite-3-2b")["model"],
                     engine_cfg={"kv_rank": 64, "kv_iters_extra": 8},
                     device_kind="TPU v5 lite", requests=reqs,
                     steps=[(1.0, 2.0, "step: admission and decode", 1)],
                     trace=trace, span=(0.0, 10.0),
                     block=spec.block_module(block, tmp_path))
        return spec.metric_module(name).read(rec)

    for name, ratio in (("admit_mfu", 2.0), ("reorth_roofline", 0.5)):
        dense = read(name, "dense")
        assert dense > 0
        assert read(name, "half_dense") == pytest.approx(ratio * dense)
