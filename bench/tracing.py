"""Reduce a profiler trace of the measured window to numbers.

The harness traces the window with ``jax.profiler`` (``--trace 1`` only)
and marks it, and each engine step and each wait for an arrival, with host
annotations named ``bench_window``, ``bench_step_<i>`` and
``bench_wait_<i>``.  Device events are those of the planes named
``/device:TPU:<n>``: the lines ``XLA Ops`` (each operation, fused kernels,
Pallas calls and the loops that contain them) and ``XLA Modules`` (each
jitted program).  A traced window holds millions of op events, so they are
kept as arrays: distinct names once, and per event a name index, a start
and a duration in nanoseconds.

The reductions take ``(name, start_ns, duration_ns)`` tuples or such
arrays alike, so they are checked on a small recorded trace without a
chip.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

Event = Tuple[str, int, int]          # name, start ns, duration ns

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
#: an XLA Ops event's name is the HLO instruction's text:
#: ``%name = <shape> <opcode>(<operands>), ...``
_HLO = re.compile(r"^(%\S+) = .*?\s([a-z][a-z0-9\-]*)\(")
#: opcodes whose events contain their body's events on the same line
CONTAINERS = ("while", "conditional", "call")
OUTSIDE = "host: outside any harness span"


def op_key(name: str) -> str:
    """``<instruction> <opcode>`` of an HLO event name (the name itself
    where it is not HLO text)."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:120]


class Events:
    """Events as arrays: ``names`` (distinct), and per event ``ids`` into
    them, ``start`` and ``dur`` in nanoseconds."""

    def __init__(self, names: List[str], ids, start, dur):
        self.names = names
        self.ids = np.asarray(ids, np.int64)
        self.start = np.asarray(start, np.int64)
        self.dur = np.asarray(dur, np.int64)

    @classmethod
    def of(cls, events: Iterable[Event]) -> "Events":
        if isinstance(events, Events):
            return events
        index: Dict[str, int] = {}
        ids, st, du = [], [], []
        for n, s, d in events:
            ids.append(index.setdefault(n, len(index)))
            st.append(s)
            du.append(d)
        return cls(list(index), ids, st, du)

    def clipped(self, lo: int, hi: int):
        """(ids, start, end) of the events' parts inside [lo, hi)."""
        s = np.maximum(self.start, lo)
        e = np.minimum(self.start + self.dur, hi)
        keep = e > s
        return self.ids[keep], s[keep], e[keep]


def _merged(ev: Events, lo: int, hi: int):
    """Starts and ends of the union of the events inside [lo, hi)."""
    _, s, e = Events.of(ev).clipped(lo, hi)
    if not len(s):
        return s, e
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    starts = s[new]
    ends = np.append(reach[np.flatnonzero(new)[1:] - 1], reach[-1])
    return starts, ends


def union_ns(events, lo: int, hi: int) -> int:
    """Nanoseconds of [lo, hi) covered by at least one event."""
    s, e = _merged(events, lo, hi)
    return int((e - s).sum())


def idle_gaps(events, lo: int, hi: int) -> List[Tuple[int, int]]:
    """Intervals of [lo, hi) that no event covers, longest first."""
    s, e = _merged(events, lo, hi)
    a = np.concatenate([[lo], e])
    b = np.concatenate([s, [hi]])
    keep = b > a
    gaps = list(zip(a[keep].tolist(), b[keep].tolist()))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def seconds_by_name(events, lo: int, hi: int, key=None) -> Dict[str, float]:
    """Device seconds per event name (or per ``key(name)``), counting each
    event's part inside [lo, hi)."""
    ev = Events.of(events)
    ids, s, e = ev.clipped(lo, hi)
    per = np.bincount(ids, weights=(e - s).astype(np.float64),
                      minlength=len(ev.names))
    out: Dict[str, float] = defaultdict(float)
    for i in np.flatnonzero(per):
        name = ev.names[i]
        out[key(name) if key else name] += float(per[i]) * 1e-9
    return dict(out)


def label_gaps(gaps: Sequence[Tuple[int, int]],
               host: Sequence[Tuple[str, int, int]]) -> List[str]:
    """What the host was doing in each idle gap: the harness span (steps
    and waits follow each other without overlap) that holds the gap's
    midpoint."""
    if not host:
        return [OUTSIDE] * len(gaps)
    host = sorted(host, key=lambda h: h[1])
    starts = np.array([h[1] for h in host], np.int64)
    ends = np.array([h[1] + h[2] for h in host], np.int64)
    mids = np.array([(a + b) // 2 for a, b in gaps], np.int64)
    i = np.searchsorted(starts, mids, side="right") - 1
    out = []
    for j, m in zip(i.tolist(), mids.tolist()):
        out.append(host[j][0] if j >= 0 and m < ends[j] else OUTSIDE)
    return out


def reduce(dev_ops, dev_modules, host: List[Event], lo: int, hi: int,
           host_labels: Optional[Dict[str, str]] = None) -> dict:
    """Busy time, per-op and per-program seconds, and idle time by what
    the host was doing, inside [lo, hi).  Ops are keyed ``<instruction>
    <opcode>``; ``leaf_ops`` leaves out the loops and calls whose events
    contain other events."""
    labels = host_labels or {}
    ops_ev = Events.of(dev_ops)
    busy = union_ns(ops_ev, lo, hi)
    ops = seconds_by_name(ops_ev, lo, hi, key=op_key)
    leaf = {k: v for k, v in ops.items()
            if k.rsplit(" ", 1)[-1] not in CONTAINERS}
    mod_ev = Events.of(dev_modules)
    mods = seconds_by_name(mod_ev, lo, hi)
    inside = (mod_ev.start >= lo) & (mod_ev.start < hi)
    cnt = np.bincount(mod_ev.ids[inside], minlength=len(mod_ev.names))
    mod_counts = {mod_ev.names[i]: int(cnt[i]) for i in np.flatnonzero(cnt)}
    spans = [(labels.get(n, n), s, d) for n, s, d in host]
    gaps = idle_gaps(ops_ev, lo, hi)
    names = label_gaps(gaps, spans)
    by_label: Dict[str, float] = defaultdict(float)
    for g, n in zip(gaps, names):
        by_label[n] += (g[1] - g[0]) * 1e-9
    return {
        "busy_s": busy * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "ops": ops,
        "leaf_ops": leaf,
        "modules": mods,
        "module_counts": mod_counts,
        "idle_by_host": dict(by_label),
        "longest_gaps": [[n, (g[1] - g[0]) * 1e-9]
                         for g, n in list(zip(gaps, names))[:10]],
    }


def _newest_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def read_xplane(log_dir: str) -> Tuple[Events, Events, List[Event], int]:
    """(device ops, device programs, host annotations, device count) from
    the newest ``.xplane.pb`` under ``log_dir``.  Events of several chips
    are pooled; busy time is then a union over the chips."""
    import jax
    pd = jax.profiler.ProfileData.from_file(_newest_xplane(log_dir))
    found = {"XLA Ops": ({}, [], [], []), "XLA Modules": ({}, [], [], [])}
    host: List[Event] = []
    devices = set()
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in found:
                devices.add(m.group(1))
                index, ids, st, du = found[line.name]
                for e in line.events:
                    ids.append(index.setdefault(e.name, len(index)))
                    st.append(e.start_ns)
                    du.append(e.duration_ns)
            elif not m and plane.name.startswith("/host"):
                host.extend((e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events
                            if e.name.startswith("bench_"))
    ops, mods = (Events(list(found[k][0]), *found[k][1:])
                 for k in ("XLA Ops", "XLA Modules"))
    return ops, mods, host, len(devices)
