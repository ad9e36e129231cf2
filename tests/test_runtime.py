"""Fault-tolerant driver: checkpoint/restart, failure injection, watchdog."""

import numpy as np

from repro.configs import all_archs
from repro.configs.base import ShapeSpec
from repro.runtime.driver import (SimulatedFailure, StragglerWatchdog,
                                  train_loop)

CFG = all_archs()["llama2-7b"].reduced().replace(name="rt-test")
SHAPE = ShapeSpec("t", 16, 4, "train")


def test_train_loop_runs_and_checkpoints(tmp_path):
    res = train_loop(CFG, SHAPE, total_steps=12, ckpt_dir=str(tmp_path),
                     ckpt_every=5, print_fn=lambda s: None)
    assert res.step == 12
    assert len(res.losses) == 12
    assert np.isfinite(res.losses).all()


def test_failure_injection_restart_resumes(tmp_path):
    """Crash at step 8 → driver restores step-4 checkpoint and completes."""
    crashed = {"done": False}

    def hook(step):
        if step == 8 and not crashed["done"]:
            crashed["done"] = True
            raise SimulatedFailure("injected node failure")

    res = train_loop(CFG, SHAPE, total_steps=12, ckpt_dir=str(tmp_path),
                     ckpt_every=5, failure_hook=hook,
                     print_fn=lambda s: None)
    assert res.restarts == 1
    assert res.step == 12


def test_resume_is_deterministic(tmp_path):
    """Loss stream after restart matches an uninterrupted run."""
    r_plain = train_loop(CFG, SHAPE, total_steps=10,
                         ckpt_dir=str(tmp_path / "plain"), ckpt_every=4,
                         print_fn=lambda s: None)
    crashed = {"done": False}

    def hook(step):
        if step == 6 and not crashed["done"]:
            crashed["done"] = True
            raise SimulatedFailure("boom")

    r_crash = train_loop(CFG, SHAPE, total_steps=10,
                         ckpt_dir=str(tmp_path / "crash"), ckpt_every=4,
                         failure_hook=hook, print_fn=lambda s: None)
    # steps 8..9 (after the last common checkpoint) must agree
    np.testing.assert_allclose(r_plain.losses[-2:], r_crash.losses[-2:],
                               rtol=1e-4)


def test_straggler_watchdog():
    wd = StragglerWatchdog(alpha=0.5, threshold=2.0)
    for _ in range(5):
        assert not wd.observe(0.1)
    assert wd.observe(1.0)         # 10x slower -> flagged
    assert wd.flagged == 1


ELASTIC_TRAIN_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import all_archs
from repro.configs.base import ShapeSpec
from repro.data import DataConfig, SyntheticLM
from repro.distributed import sharding as sh
from repro.optim import make_optimizer
from repro.runtime import steps as steps_mod
from repro.runtime.driver import restore_for_mesh
from repro import checkpoint as ckpt
from repro.launch.mesh import make_host_mesh

cfg = all_archs()["deepseek-7b"].reduced().replace(name="elastic-e2e")
shape = ShapeSpec("t", 16, 8, "train")
opt = make_optimizer(cfg)
src = SyntheticLM(cfg, shape, DataConfig(seed=0))

def run_steps(params, opt_state, mesh, start, n):
    pshd = sh.params_sharding(jax.eval_shape(lambda: params), mesh, cfg)
    step = jax.jit(steps_mod.make_train_step(cfg, opt))
    losses = []
    with mesh:
        for i in range(start, start + n):
            batch = {k: jnp.asarray(v) for k, v in src.batch_at(i).items()}
            params, opt_state, m = step(params, opt_state, batch)
            losses.append(float(m["loss"]))
    return params, opt_state, losses

# phase 1: train 6 steps on a 2x4 mesh, checkpoint
mesh_a = make_host_mesh(2, 4)
params, opt_state = steps_mod.init_train_state(cfg, jax.random.PRNGKey(0),
                                               opt)
params, opt_state, la = run_steps(params, opt_state, mesh_a, 0, 6)
ckpt.save({"params": params, "opt": opt_state}, "%s", 5)

# phase 2: ELASTIC restore onto a 4x2 mesh, continue 3 steps
mesh_b = make_host_mesh(4, 2)
state = restore_for_mesh(cfg, "%s", mesh_b, optimizer=opt)
p2, o2, lb = run_steps(state["params"], state["opt"], mesh_b, 6, 3)

# reference: uninterrupted on mesh_a
p3, o3, lc = run_steps(params, opt_state, mesh_a, 6, 3)
np.testing.assert_allclose(lb, lc, rtol=2e-2)
print("ELASTIC_E2E_OK", lb)
"""


def test_elastic_remesh_end_to_end(tmp_path):
    """Train on mesh A -> checkpoint -> restore re-sharded on mesh B ->
    the continued loss stream matches the uninterrupted run."""
    import os
    import subprocess
    import sys
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    script = ELASTIC_TRAIN_SCRIPT % (str(tmp_path), str(tmp_path))
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert "ELASTIC_E2E_OK" in out.stdout, out.stderr[-2500:]
