"""Request-lifecycle tracing: host-side spans → Chrome trace-event JSON.

Spans are wall-clock intervals (``time.perf_counter``) recorded as Chrome
trace-event ``"X"`` (complete) events, loadable in Perfetto / chrome://
tracing.  Tracks (one ``tid`` each, named via ``thread_name`` metadata
events) separate the concurrent stories serving interleaves:

* ``engine``   — the step loop: ``step`` spans containing ``admit`` /
  ``decode-block`` / ``fold`` / ``drain-pool`` children, and those the
  phases between device programs (``admit.prepare``, ``admit.launch``,
  ``splice``, ``admit.first_token``, ``admit.activate``;
  ``decode.prepare``, ``decode.launch``, ``decode.readback``,
  ``decode.deliver``).  Nesting is time containment on one tid, which is
  exactly how Perfetto renders it;
* ``tickets``  — in-flight async ``PrefillTicket``s (dispatch → splice),
  on their own track so the P/D overlap is visible as spans running UNDER
  the engine's decode spans;
* ``req/<uid>`` — one track per request: a ``request`` span
  (submit → finish) containing ``queue`` (submit → dispatch),
  ``prefill`` (dispatch → first token) and ``decode`` (first → last
  token) child spans.

Every ``engine``-track span is also a ``jax.profiler.TraceAnnotation``
named ``engine.<span>``, enabled tracer or not, so a profiler trace holds
the step loop's phases on the device trace's clock: an idle gap on the
device falls inside the host phase that left it idle.  While no profiler
trace is active that costs one ``TraceAnnotation.is_enabled()`` check per
span.  The other tracks overlap across steps and stay in the JSON only.

Everything else is plain Python list-append on the host — a disabled
tracer (the default) reduces every call to one attribute check and shared
no-op objects, and an enabled tracer never touches device state, so
tokens are byte-identical either way (the §13 zero-device-op rule;
conformance-gated in tests/test_serving_conformance.py).

The module also owns the PHASE stack used to attribute jit recompiles:
``phase_scope("decode")`` marks host-side sections that launch device
programs, and the compile-watch (``watch.py``) labels every XLA compile
event with the innermost active phase.
"""
from __future__ import annotations

import json
import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

#: the track whose spans also go into the profiler's trace
ENGINE_TRACK = "engine"


def _annotation(name: str, track: str) -> Optional[TraceAnnotation]:
    """The open ``engine.<name>`` profiler annotation of an engine-track
    span; None on other tracks and while no profiler trace is active."""
    if track != ENGINE_TRACK or not TraceAnnotation.is_enabled():
        return None
    ann = TraceAnnotation(f"engine.{name}")
    ann.__enter__()
    return ann


class Span:
    """One open interval on a track; ``end()`` records the event."""

    __slots__ = ("tracer", "name", "track", "args", "t0", "_done", "_ann")

    def __init__(self, tracer: "Tracer", name: str, track: str,
                 args: Optional[Dict[str, Any]] = None,
                 ann: Optional[TraceAnnotation] = None):
        self.tracer = tracer
        self.name = name
        self.track = track
        self.args = dict(args or {})
        self.t0 = time.perf_counter()
        self._done = False
        self._ann = ann

    def annotate(self, **kw) -> "Span":
        self.args.update(kw)
        return self

    def end(self, **kw) -> None:
        if self._done:                    # idempotent: double-end is a no-op
            return
        self._done = True
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        if kw:
            self.args.update(kw)
        self.tracer._record(self)

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> None:
        self.end()


class _NullSpan:
    """Shared no-op span for a disabled tracer (zero allocation per call)."""

    __slots__ = ()

    def annotate(self, **kw) -> "_NullSpan":
        return self

    def end(self, **kw) -> None:
        pass

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


NULL_SPAN = _NullSpan()


class _AnnotationSpan(_NullSpan):
    """An engine-track span of a disabled tracer while a profiler trace is
    active: the profiler annotation alone, closed once by ``end()``."""

    __slots__ = ("_ann",)

    def __init__(self, ann: TraceAnnotation):
        self._ann = ann

    def end(self, **kw) -> None:
        if self._ann is not None:
            ann, self._ann = self._ann, None
            ann.__exit__(None, None, None)

    def __exit__(self, *exc) -> None:
        self.end()


class Tracer:
    """Span recorder.  ``enabled=False`` (the default engine state) makes
    ``begin``/``span``/``instant`` constant-time no-ops."""

    def __init__(self, enabled: bool = True, max_events: int = 200_000):
        self.enabled = enabled
        self.events: List[dict] = []
        self.max_events = max_events      # hard bound: tracing may never
        #                                   become the unbounded-memory bug
        #                                   it exists to prevent
        self.dropped = 0
        self._t0 = time.perf_counter()
        self._tids: Dict[str, int] = {}

    # -- recording --------------------------------------------------------
    def begin(self, name: str, track: str = ENGINE_TRACK,
              args: Optional[Dict[str, Any]] = None):
        """Open a span; the caller ends it (possibly in another scope —
        request-lifecycle spans end steps later than they begin).  An
        engine-track span must end before the span that encloses it."""
        ann = _annotation(name, track)
        if not self.enabled:
            return NULL_SPAN if ann is None else _AnnotationSpan(ann)
        return Span(self, name, track, args, ann)

    span = begin                          # context-manager idiom: with t.span(..)

    def instant(self, name: str, track: str = ENGINE_TRACK,
                args: Optional[Dict[str, Any]] = None) -> None:
        if not self.enabled:
            return
        if len(self.events) >= self.max_events:
            self.dropped += 1
            return
        self.events.append({
            "name": name, "ph": "i", "s": "t",
            "ts": (time.perf_counter() - self._t0) * 1e6,
            "pid": 0, "tid": self._tid(track),
            "args": dict(args or {})})

    def _record(self, sp: Span) -> None:
        if len(self.events) >= self.max_events:
            self.dropped += 1
            return
        ts = (sp.t0 - self._t0) * 1e6
        self.events.append({
            "name": sp.name, "ph": "X", "ts": ts,
            "dur": (time.perf_counter() - self._t0) * 1e6 - ts,
            "pid": 0, "tid": self._tid(sp.track), "args": sp.args})

    def _tid(self, track: str) -> int:
        tid = self._tids.get(track)
        if tid is None:
            tid = len(self._tids) + 1
            self._tids[track] = tid
        return tid

    # -- export -----------------------------------------------------------
    def to_json(self) -> dict:
        """Chrome trace-event JSON object (Perfetto-loadable)."""
        meta = [{"name": "process_name", "ph": "M", "pid": 0, "tid": 0,
                 "args": {"name": "repro.serving"}}]
        for track, tid in sorted(self._tids.items(), key=lambda kv: kv[1]):
            meta.append({"name": "thread_name", "ph": "M", "pid": 0,
                         "tid": tid, "args": {"name": track}})
        return {"traceEvents": meta + self.events,
                "displayTimeUnit": "ms"}

    def export(self, path: str) -> None:
        from ..ioutil import atomic_write_json
        atomic_write_json(path, self.to_json())


def validate_trace(obj) -> int:
    """Validate Chrome trace-event JSON (a dict, JSON text, or a path).

    Checks the structural contract Perfetto's JSON importer needs — a
    ``traceEvents`` list whose entries carry ``ph``/``ts``/``pid``/``tid``
    (``dur`` too for ``"X"`` events) — and returns the number of complete
    spans.  Raises ``ValueError`` on any malformed event (CI smoke gates
    on this).
    """
    if isinstance(obj, str):
        if "\n" not in obj and not obj.lstrip().startswith(("{", "[")):
            with open(obj) as f:
                obj = json.load(f)
        else:
            obj = json.loads(obj)
    if not isinstance(obj, dict) or not isinstance(
            obj.get("traceEvents"), list):
        raise ValueError("trace: expected {'traceEvents': [...]}")
    spans = 0
    for ev in obj["traceEvents"]:
        if not isinstance(ev, dict):
            raise ValueError(f"trace event is not an object: {ev!r}")
        for fld in ("ph", "pid", "tid"):
            if fld not in ev:
                raise ValueError(f"trace event missing {fld!r}: {ev!r}")
        if ev["ph"] == "M":
            continue
        if "ts" not in ev or not isinstance(ev["ts"], (int, float)):
            raise ValueError(f"trace event missing numeric ts: {ev!r}")
        if ev["ph"] == "X":
            if not isinstance(ev.get("dur"), (int, float)) \
                    or ev["dur"] < 0:
                raise ValueError(f"X event needs dur >= 0: {ev!r}")
            spans += 1
    return spans


# ---------------------------------------------------------------------------
# Phase stack (jit compile attribution)
# ---------------------------------------------------------------------------

_phase = threading.local()


def current_phase() -> str:
    stack = getattr(_phase, "stack", None)
    return stack[-1] if stack else "other"


class phase_scope:
    """Mark a host section that launches device programs, so compile
    events fired while it is active are attributed to it (two list ops —
    always on, independent of any tracer)."""

    __slots__ = ("name",)

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        stack = getattr(_phase, "stack", None)
        if stack is None:
            stack = _phase.stack = []
        stack.append(self.name)
        return self

    def __exit__(self, *exc):
        _phase.stack.pop()
