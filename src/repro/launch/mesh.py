"""Production mesh construction (deliverable e).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before any jax init;
smoke tests see 1 device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the compiler propagates shardings
    through every op, as the serving and model code assume (make_mesh's
    default Explicit axes put shardings in the types, where a reshape of a
    sharded axis must state its output sharding)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single-pod 16×16 ("data","model") or 2-pod 2×16×16 ("pod","data",
    "model").  512 placeholder devices are required for multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """``(data, model)`` mesh on the local devices — smoke tests, examples,
    and mesh-parallel serving on forced host devices.  The no-arg form is
    the historical 1×1 mesh.  ``data×model`` must not exceed the local
    device count (force more with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` BEFORE jax
    initializes — the CI distributed job and
    ``benchmarks/serving_sharded.py`` both do)."""
    return _auto_mesh((data, model), ("data", "model"))


def parse_mesh(spec):
    """CLI ``--mesh`` wiring → Mesh or None.

    * ``"none"``/``""``/None — no mesh (single-device serving),
    * ``"host"``             — every local device on the "data" axis
                               (DP serving; 1 device ⇒ a 1×1 mesh),
    * ``"DxM"`` (e.g. ``8x1``, ``4x2``) — explicit (data, model) shape.
    """
    if spec is None or spec in ("", "none", "off"):
        return None
    if spec == "host":
        return make_host_mesh(len(jax.devices()), 1)
    try:
        data, model = (int(n) for n in spec.lower().split("x"))
    except ValueError:
        raise ValueError(
            f"--mesh must be 'none', 'host', or 'DxM' (got {spec!r})")
    n_dev = len(jax.devices())
    if data * model > n_dev:
        raise ValueError(
            f"--mesh {spec} needs {data * model} devices but only {n_dev} "
            f"are visible; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={data * model} before "
            f"launching (the CI/benchmark harnesses force 8)")
    return make_host_mesh(data, model)
