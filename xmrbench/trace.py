"""Reduction from a profiler trace to device busy time, per-program and
per-kernel device time, and idle gaps labelled by what the host was doing.

Events are plain ``(name, start_ns, end_ns)`` tuples, so the reduction is
tested on synthetic lists. :func:`load` reads the ``.xplane.pb`` that
``jax.profiler.trace`` writes: device operations from the ``XLA Ops`` line
of each ``/device:TPU:<n>`` plane, program executions from its ``XLA
Modules`` line, and the benchmark's own host spans (``xmrbench.*``
``TraceAnnotation`` names) from the host plane.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start_ns, end_ns

HOST_PREFIX = "xmrbench."
WINDOW_SPAN = "xmrbench.window"


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Event]]          # device id -> device operations
    modules: Dict[int, List[Event]]      # device id -> program executions
    host: List[Event]                    # the benchmark's host spans
    window: Tuple[float, float]          # the measured window, ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    """Events cut to ``[lo, hi]``; those wholly outside are dropped."""
    out = []
    for name, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((name, s, e))
    return out


def union(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """Disjoint, sorted intervals covered by at least one event."""
    spans = sorted((s, e) for _, s, e in events if e > s)
    out: List[Tuple[float, float]] = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    """Time in ``[lo, hi]`` during which some event runs."""
    return sum(e - s for s, e in union(clip(events, lo, hi)))


def idle_share(events: Sequence[Event], lo: float, hi: float) -> float:
    """``1 - busy / window`` over ``[lo, hi]``."""
    return 1.0 - busy_ns(events, lo, hi) / (hi - lo)


def total_ns(events: Sequence[Event], match) -> Tuple[float, int]:
    """Summed duration and count of the events whose name ``match`` takes."""
    tot, n = 0.0, 0
    for name, s, e in events:
        if match(name):
            tot += e - s
            n += 1
    return tot, n


def top_ops(events: Sequence[Event], k: int = 10) -> List[Tuple[str, float]]:
    """The ``k`` operation names with the most summed time, in seconds."""
    acc: Dict[str, float] = {}
    for name, s, e in events:
        acc[name] = acc.get(name, 0.0) + (e - s)
    best = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [(name, ns * 1e-9) for name, ns in best]


def short_name(hlo: str) -> str:
    """``%fusion.49 = f32[10158080]{...} fusion(...)`` as ``fusion.49
    fusion f32[10158080]``: an op's name, kind and result shape."""
    if " = " not in hlo:
        return hlo[:120]
    name, rhs = hlo.split(" = ", 1)
    kind = re.search(r" ([a-z][a-z0-9_-]*)\(", rhs)
    shape = "tuple" if rhs.startswith("(") else re.split(r"[{ ]", rhs, 1)[0]
    return f"{name.lstrip('%')} {kind.group(1) if kind else '?'} {shape}"[:120]


def idle_gaps(events: Sequence[Event], host: Sequence[Event], lo: float,
              hi: float, k: int = 10) -> List[Tuple[str, float]]:
    """The ``k`` longest device-idle gaps in ``[lo, hi]``, in seconds, each
    named by the innermost host span open at the gap's midpoint (``idle``
    where none is)."""
    busy = union(clip(events, lo, hi))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:k]:
        mid = 0.5 * (s + e)
        open_spans = [(hs, name) for name, hs, he in host
                      if hs <= mid < he and name != WINDOW_SPAN]
        label = max(open_spans)[1] if open_spans else "idle"
        out.append((label, (e - s) * 1e-9))
    return out


def _events(line) -> List[Event]:
    return [(ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
            for ev in line.events]


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            dev = int(plane.name[len("/device:TPU:"):])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[dev] = _events(line)
                elif line.name == "XLA Modules":
                    modules[dev] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(ev for ev in _events(line)
                            if ev[0].startswith(HOST_PREFIX))
    windows = [ev for ev in host if ev[0] == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    return Trace(ops=ops, modules=modules, host=host,
                 window=(windows[0][1], windows[0][2]))
