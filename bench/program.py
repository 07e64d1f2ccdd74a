"""The arithmetic behind metrics read from the program's own spans and
counters (``repro.tracing``), for a run that recorded them.

A ``Program`` holds what the tracer gave: every span drained after serving
(``repro.tracing.Record``: name, start and end in host monotonic ns, id,
parent id, request id, facts) and a snapshot of its counters taken when the
window opened.  The functions read only those attributes, so they run on
any records of that shape.  Each returns None where there is nothing to
read, never 0.

The spans they read: ``request.inbox``, ``request.queue`` and
``request.prefill`` (a request's stamps: received by the front end, taken
by the engine, first prefill dispatch, first token), ``decode.logits_copy``,
the stepper's host work (``engine.step`` and its children, and
``stepper.drain``) less its waits for the device (``*.device_wait``), and
``engine.kernel_plan``; the counters ``jit.<phase>_s``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace
from bench.measures import median

HOST_WORK = ("engine.step", "stepper.drain")
DEVICE_WAIT = ".device_wait"
KERNEL_PLAN = "engine.kernel_plan"

Intervals = List[Tuple[float, float]]


@dataclasses.dataclass
class Program:
    records: list                  # repro.tracing.Record, host monotonic ns
    counters_at_open: Dict[str, float]


def _window_ns(run) -> Tuple[float, float]:
    return run.t0 * 1e9, (run.t0 + run.seconds) * 1e9


def _ms(r) -> float:
    return (r.t1_ns - r.t0_ns) * 1e-6


def request_part_ms(prog: Program, run, part: str) -> List[float]:
    """``request.<part>`` of each request the front end received in the
    window, in ms."""
    lo, hi = _window_ns(run)
    rids = {r.rid for r in prog.records
            if r.name == "request.inbox" and lo <= r.t0_ns <= hi}
    return [_ms(r) for r in prog.records
            if r.name == f"request.{part}" and r.rid in rids]


def inbox_wait_p50_ms(prog: Program, run) -> Optional[float]:
    return median(request_part_ms(prog, run, "inbox"))


def queue_wait_p50_ms(prog: Program, run) -> Optional[float]:
    return median(request_part_ms(prog, run, "queue"))


def prefill_time_p50_ms(prog: Program, run) -> Optional[float]:
    return median(request_part_ms(prog, run, "prefill"))


def logits_copy_ms(prog: Program, run) -> Optional[float]:
    """Median copy of a decode step's logits to the host, over the steps
    that started it in the window."""
    lo, hi = _window_ns(run)
    return median([_ms(r) for r in prog.records
                   if r.name == "decode.logits_copy" and lo <= r.t0_ns <= hi])


def _union(pairs: Sequence[Tuple[float, float]]) -> Intervals:
    out: Intervals = []
    for a, b in sorted(pairs):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        elif b > a:
            out.append((a, b))
    return out


def _intersect(x: Intervals, y: Intervals) -> Intervals:
    """The intersection of two sorted lists of disjoint intervals."""
    out: Intervals = []
    i = j = 0
    while i < len(x) and j < len(y):
        a, b = max(x[i][0], y[j][0]), min(x[i][1], y[j][1])
        if b > a:
            out.append((a, b))
        if x[i][1] < y[j][1]:
            i += 1
        else:
            j += 1
    return out


def _length(x: Intervals) -> float:
    return sum(b - a for a, b in x)


def idle_host_share(prog: Program, run) -> Optional[float]:
    """Share of the traced window, in %, in which device 0 ran nothing
    while the stepper did host work: inside ``engine.step`` or
    ``stepper.drain`` and outside every ``*.device_wait``."""
    t = run.traced
    if t is None:
        return None
    devs = trace.devices(t.events)
    host = [(r.t0_ns, r.t1_ns) for r in prog.records if r.name in HOST_WORK]
    if not devs or not host:
        return None
    off = t.window_ns[0] - t.host_window_ns[0]

    def on_trace_clock(spans):
        return _union([(a + off, b + off) for a, b in spans])

    waits = [(r.t0_ns, r.t1_ns) for r in prog.records
             if r.name.endswith(DEVICE_WAIT)]
    idle = trace.idle_gaps(trace.ops(t.events, devs[0]), t.window_ns)
    in_host = _intersect(on_trace_clock(host), idle)
    idle_ns = _length(in_host) - _length(
        _intersect(in_host, on_trace_clock(waits)))
    width = t.window_ns[1] - t.window_ns[0]
    return 100.0 * idle_ns / width if width > 0 else None


def jit_s(prog: Program) -> Optional[float]:
    """Seconds JAX spent tracing, lowering and compiling before the
    window: the sum of the ``jit.<phase>_s`` counters at its opening."""
    got = [v for k, v in prog.counters_at_open.items()
           if k.startswith("jit.") and k.endswith("_s")]
    return float(sum(got)) if got else None


def kernel_plan_s(prog: Program, run) -> Optional[float]:
    """Seconds of the engine's e-graph kernel plan before the window."""
    lo, _ = _window_ns(run)
    got = [(r.t1_ns - r.t0_ns) * 1e-9 for r in prog.records
           if r.name == KERNEL_PLAN and r.t1_ns <= lo]
    return float(sum(got)) if got else None
