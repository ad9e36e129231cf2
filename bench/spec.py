"""Find the benchmark's parts by name.

``BENCHMARK.json`` at the root of the checkout names every cell, model
configuration, traffic mix and metric.  Each part lives in a file of its
own under the benchmark's directory, found by that name:

* ``configs/<config>.json``  — the model as it is run, with its source
  and the name of its block;
* ``blocks/<block>.py``      — what is particular to one kind of model:
  its weights layout, its plain reference and its counted work (the
  contract is in ``blocks/__init__.py``);
* ``traffic/<traffic>.json`` — the parameters the one generator reads;
* ``cells/<workload>.json``  — the cell's serving settings, its offered
  load and the limits of its output check;
* ``metrics/<metric>.py``    — one reader per metric, ``read(record)``.

Adding a cell, a configuration, a traffic mix, a metric or a model of
another block therefore takes new files and new entries only.  Every
function takes the benchmark's directory, so a test can point it at a
directory of its own.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Tuple

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class SpecError(Exception):
    """A name in BENCHMARK.json has no file, or a file is malformed."""


def _json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing {path}")
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    return _json(Path(root) / "BENCHMARK.json")


def workload(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                    f"(have {[w['name'] for w in bm['workloads']]})")


def find(kind: str, name: str, suffix: str,
         bench_dir: Path = BENCH_DIR) -> Path:
    """``<kind>/<name><suffix>`` in ``bench_dir``, else in the benchmark's
    own directory."""
    for d in (Path(bench_dir), BENCH_DIR):
        path = d / kind / f"{name}{suffix}"
        if path.is_file():
            return path
    raise SpecError(f"no {kind}/{name}{suffix} in {bench_dir} or "
                    f"{BENCH_DIR}")


def config_file(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return _json(find("configs", name, ".json", bench_dir))


def traffic_file(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return _json(find("traffic", name, ".json", bench_dir))


def cell_file(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return _json(find("cells", name, ".json", bench_dir))


_LOADED: Dict[Path, ModuleType] = {}


def _load(kind: str, name: str, bench_dir: Path,
          functions: Tuple[str, ...]) -> ModuleType:
    """``<kind>/<name>.py``, loaded once per process (so a block's
    compiled reference programs are kept between runs), which has to
    define ``functions``."""
    path = find(kind, name, ".py", bench_dir)
    if path not in _LOADED:
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        missing = [f for f in functions if not callable(getattr(mod, f, None))]
        if missing:
            raise SpecError(f"{path} defines no {', '.join(missing)}")
        _LOADED[path] = mod
    return _LOADED[path]


def metric_module(name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    """The reader of one metric, ``read(record)``, from
    ``metrics/<name>.py``."""
    return _load("metrics", name, bench_dir, ("read",))


#: what every block defines (``blocks/__init__.py``)
BLOCK_FUNCTIONS = ("layout", "served_logits", "forward_flops",
                   "reorth_needed")


def block_module(name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    """The block of that name, from ``blocks/<name>.py``."""
    return _load("blocks", name, bench_dir, BLOCK_FUNCTIONS)


def config_block(conf: dict, bench_dir: Path = BENCH_DIR) -> ModuleType:
    """The block that a configuration file names under ``block``."""
    if "block" not in conf:
        raise SpecError("the configuration names no block")
    return block_module(conf["block"], bench_dir)


def cell_metrics(bm: dict, workload_name: str, kind: str) -> List[dict]:
    """The metrics of ``kind`` (``end_to_end`` or ``per_layer``) that the
    cell reports: those without a ``workloads`` key, and those that list
    it."""
    return [m for m in bm[kind]
            if workload_name in m.get("workloads", [workload_name])]
