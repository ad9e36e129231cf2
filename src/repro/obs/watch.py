"""jit compile-watch: count + wall time of XLA recompilations.

``jax.monitoring`` emits a ``/jax/core/compile/backend_compile_duration``
duration event for every real backend compile (trace-cache hits fire
nothing), so listening to it is a zero-device-op way to catch the classic
serving regression — a step fn silently retracing per call because some
argument stopped hashing stably.  Each compile is attributed to the
innermost active :func:`~repro.obs.trace.phase_scope` (``prefill`` /
``decode`` / ``fold`` / ``splice`` / …), which is how "recompiles per
step fn" is answered without wrapping every jit wrapper.

Counters land in the GLOBAL registry:

* ``jit_compiles_total{phase=…}``        — backend compiles
* ``jit_compile_seconds_total{phase=…}`` — wall time inside XLA

``install_compile_watch`` is idempotent; the listener stays registered
for the life of the process (jax has no per-listener removal).
"""
from __future__ import annotations

from .registry import GLOBAL, MetricsRegistry
from .trace import current_phase

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_installed = False


def install_compile_watch(registry: MetricsRegistry = GLOBAL) -> bool:
    """Register the monitoring listener (once per process).  Returns True
    if this call installed it, False if it was already live."""
    global _installed
    if _installed:
        return False
    try:
        import jax.monitoring as monitoring
    except Exception:                     # pragma: no cover - jax absent
        return False

    def on_duration(event: str, duration: float, **kw) -> None:
        if event == _COMPILE_EVENT:
            phase = current_phase()
            registry.counter(
                "jit_compiles_total",
                "XLA backend compiles (recompile watch)",
                phase=phase).inc()
            registry.counter(
                "jit_compile_seconds_total",
                "wall seconds spent in XLA backend compiles",
                phase=phase).add(float(duration))

    monitoring.register_event_duration_secs_listener(on_duration)
    _installed = True
    return True


def compile_stats(registry: MetricsRegistry = GLOBAL) -> dict:
    """{phase: {"compiles": n, "seconds": s}} view of the watch counters."""
    out: dict = {}
    for m in registry.metrics():
        if m.name == "jit_compiles_total":
            out.setdefault(m.labels.get("phase", "other"),
                           {"compiles": 0, "seconds": 0.0})["compiles"] \
                = m.value
        elif m.name == "jit_compile_seconds_total":
            out.setdefault(m.labels.get("phase", "other"),
                           {"compiles": 0, "seconds": 0.0})["seconds"] \
                = m.value
    return out
