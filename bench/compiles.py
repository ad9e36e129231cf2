"""Count XLA backend compiles per phase of a run.

JAX emits the ``/jax/core/compile/backend_compile_duration`` duration event
for every compile that reaches the backend; a hit in the persistent cache
or in the process's own caches emits none.  The harness sets the phase
(``setup``, ``window``, ``drain``, ``check``) and reads the counts after.
"""
from __future__ import annotations

from typing import Dict

_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    def __init__(self):
        self.phase = "setup"
        self.counts: Dict[str, int] = {}
        self.seconds: Dict[str, float] = {}
        import jax.monitoring as monitoring
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == _EVENT:
            self.counts[self.phase] = self.counts.get(self.phase, 0) + 1
            self.seconds[self.phase] = self.seconds.get(self.phase, 0.0) \
                + float(duration)

    def count(self, phase: str) -> int:
        return self.counts.get(phase, 0)
