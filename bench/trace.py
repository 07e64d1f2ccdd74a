"""From a profiler trace and the benchmark's host spans to numbers.

``read_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
plain events; everything after that works on plain events, so it is tested
on a small recorded trace (``tests/bench/recorded_trace.json``).

Device events come from the planes ``/device:TPU:<n>``: the line
``XLA Modules`` holds one event per program execution (named after the
jitted function, ``jit__decode(...)``), the line ``XLA Ops`` one per
operation.  Busy time is the union of the operation intervals; idle is the
rest of the traced window.  Host spans are the benchmark's own records
(``bench.spans``), put on the trace's clock by one marker span recorded in
both.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
DEVICE_PLANE_PREFIX = "/device:TPU:"
SYNC_SPAN = "bench.clock_sync"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    line: str = ""
    device: int = 0

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass(frozen=True)
class Span:
    """A host span on the trace's clock; ``depth`` 0 is outermost."""
    name: str
    start_ns: float
    end_ns: float
    depth: int = 0


def read_xplane(trace_dir: str) -> Tuple[List[Event], Dict[str, float]]:
    """(device events on the modules and ops lines, {host event name:
    start_ns} of the benchmark's marker spans) from the newest
    ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    events: List[Event] = []
    markers: Dict[str, float] = {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            dev = int(plane.name[len(DEVICE_PLANE_PREFIX):].split()[0])
            for line in plane.lines:
                if line.name not in (MODULES_LINE, OPS_LINE):
                    continue
                for e in line.events:
                    events.append(Event(e.name, float(e.start_ns),
                                        float(e.duration_ns), line.name, dev))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        markers.setdefault(e.name, float(e.start_ns))
    return events, markers


def devices(events: Iterable[Event]) -> List[int]:
    return sorted({e.device for e in events})


def ops(events: Iterable[Event], device: Optional[int] = None) -> List[Event]:
    return [e for e in events if e.line == OPS_LINE
            and (device is None or e.device == device)]


def executions(events: Iterable[Event], program: str,
               device: Optional[int] = None) -> List[Event]:
    """Executions of the jitted function named ``program`` (e.g.
    ``_decode``): module events named ``jit_<program>`` or
    ``jit_<program>(<id>)``."""
    want = f"jit_{program}"
    return [e for e in events if e.line == MODULES_LINE
            and (device is None or e.device == device)
            and (e.name == want or e.name.startswith(want + "("))]


def _arrays(events: Sequence[Event]) -> Tuple[np.ndarray, np.ndarray]:
    n = len(events)
    starts = np.fromiter((e.start_ns for e in events), float, n)
    return starts, starts + np.fromiter((e.dur_ns for e in events), float, n)


def _union(starts: np.ndarray, ends: np.ndarray
           ) -> Tuple[np.ndarray, np.ndarray]:
    """The union of [start, end) intervals as sorted disjoint intervals."""
    if not len(starts):
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, reach = starts[order], np.maximum.accumulate(ends[order])
    first = np.flatnonzero(np.r_[True, s[1:] > reach[:-1]])
    last = np.r_[first[1:] - 1, len(s) - 1]
    return s[first], reach[last]


def _busy(events: Sequence[Event], window: Tuple[float, float]
          ) -> Tuple[np.ndarray, np.ndarray]:
    starts, ends = _arrays(events)
    s, e = np.maximum(starts, window[0]), np.minimum(ends, window[1])
    keep = e > s
    return _union(s[keep], e[keep])


def busy_ns(events: Sequence[Event], window: Tuple[float, float]) -> float:
    """Time in ``window`` in which some operation ran."""
    s, e = _busy(events, window)
    return float((e - s).sum())


def idle_gaps(events: Sequence[Event], window: Tuple[float, float]
              ) -> List[Tuple[float, float]]:
    """The stretches of ``window`` in which no operation ran."""
    s, e = _busy(events, window)
    lo = np.r_[window[0], e]
    hi = np.r_[s, window[1]]
    keep = hi > lo
    return list(zip(lo[keep].tolist(), hi[keep].tolist()))


def module_of(ops_: Sequence[Event], modules: Sequence[Event]
              ) -> List[Optional[str]]:
    """For each operation, the name of the program execution it ran in
    (``jit__decode``, without the id), or None outside every execution.
    A device runs one program at a time, so its executions do not
    overlap."""
    out: List[Optional[str]] = [None] * len(ops_)
    for dev in devices(ops_):
        mods = sorted((m for m in modules if m.device == dev),
                      key=lambda m: m.start_ns)
        idx = [i for i, e in enumerate(ops_) if e.device == dev]
        if not mods:
            continue
        m_start, m_end = _arrays(mods)
        o_start = np.fromiter((ops_[i].start_ns for i in idx), float, len(idx))
        j = np.searchsorted(m_start, o_start, side="right") - 1
        inside = (j >= 0) & (o_start < m_end[np.maximum(j, 0)])
        names = [m.name.split("(")[0] for m in mods]
        for i, jj, ok in zip(idx, j.tolist(), inside.tolist()):
            if ok:
                out[i] = names[jj]
    return out


def attribute_gaps(gaps: Sequence[Tuple[float, float]],
                   spans: Sequence[Span]) -> Dict[str, float]:
    """Idle seconds by what the host was doing: each gap goes to the
    innermost span that covers its midpoint, or to ``host.none`` where no
    span does.  Spans of one depth follow each other on the thread that
    records them, so at most one of each depth covers a point."""
    out: Dict[str, float] = defaultdict(float)
    if not len(gaps):
        return dict(out)
    g = np.array(gaps, float)
    mid, width = g.mean(axis=1), (g[:, 1] - g[:, 0]) * 1e-9
    owner = np.full(len(g), -1)
    ordered = sorted(spans, key=lambda sp: (sp.depth, sp.start_ns))
    for depth in sorted({sp.depth for sp in ordered}):
        pos = [i for i, sp in enumerate(ordered) if sp.depth == depth]
        starts = np.array([ordered[i].start_ns for i in pos], float)
        ends = np.array([ordered[i].end_ns for i in pos], float)
        j = np.searchsorted(starts, mid, side="right") - 1
        hit = (j >= 0) & (mid < ends[np.maximum(j, 0)])
        owner = np.where(hit, np.asarray(pos)[np.maximum(j, 0)], owner)
    for o, w in zip(owner.tolist(), width.tolist()):
        out[ordered[o].name if o >= 0 else "host.none"] += w
    return dict(out)


def short_name(name: str) -> str:
    """An operation's HLO instruction name without its text: the trace
    names an operation ``%fusion.3 = bf16[...] fusion(...)``."""
    return name.split(" = ", 1)[0].lstrip("%")


def self_ns(ops_: Sequence[Event]) -> List[float]:
    """Each operation's own time: its duration less that of the operations
    nested in it on the same device (a ``while`` holds its body's)."""
    out = [e.dur_ns for e in ops_]
    order = sorted(range(len(ops_)),
                   key=lambda i: (ops_[i].device, ops_[i].start_ns,
                                  -ops_[i].dur_ns))
    stack: List[int] = []
    for i in order:
        e = ops_[i]
        while stack and (ops_[stack[-1]].device != e.device
                         or ops_[stack[-1]].end_ns <= e.start_ns):
            stack.pop()
        if stack:
            out[stack[-1]] -= e.dur_ns
        stack.append(i)
    return out


def time_by_name(events: Iterable[Event]) -> Dict[str, float]:
    """Summed device seconds per event name."""
    out: Dict[str, float] = defaultdict(float)
    for e in events:
        out[e.name] += e.dur_ns * 1e-9
    return dict(out)


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def offset_ns(markers: Dict[str, float], host_sync_ns: float) -> float:
    """What to add to a host monotonic time (ns) to put it on the trace's
    clock, from the marker span recorded in both."""
    if SYNC_SPAN not in markers:
        raise ValueError(f"the trace holds no {SYNC_SPAN} marker")
    return markers[SYNC_SPAN] - host_sync_ns
