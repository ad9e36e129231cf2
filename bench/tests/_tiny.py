"""The CPU test cells under ``bench/tests/data`` and a helper that runs
one of them through the harness, without the look for a chip."""
import contextlib
import io
import json
from pathlib import Path

DATA = Path(__file__).resolve().parent / "data"


def run_cell(workload, seed=11, seconds=3.0, trace=0, bench_dir=DATA,
             bm_root=DATA):
    """(exit code, result line as a dict or None, stdout text)."""
    from bench import run
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      require_chip=False, bench_dir=bench_dir,
                      bm_root=bm_root)
    lines = out.getvalue().strip().splitlines()
    result = json.loads(lines[-1]) if rc == 0 and lines else None
    return rc, result, out.getvalue() + err.getvalue()
