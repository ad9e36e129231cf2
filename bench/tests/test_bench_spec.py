"""The benchmark finds every part by name, and a cell, configuration,
traffic mix and metric added as new files only are run."""
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


def test_unknown_names_are_errors():
    with pytest.raises(spec.SpecError):
        spec.workload(spec.load_benchmark(DATA), "no-such-cell")
    with pytest.raises(spec.SpecError):
        spec.metric_module("no_such_metric")


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
