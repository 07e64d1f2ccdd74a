"""The arithmetic behind the metric readers in ``bench/metrics/``.

A reader gets one ``Run``: the client's records of the window, and in a
traced run the reduced trace.  Functions return None where there is
nothing to read (no trace, no execution of the program, no request), so
the harness leaves the metric out; a share of a peak is never made up.
"""
from __future__ import annotations

import dataclasses
import statistics
from types import ModuleType
from typing import Dict, List, Optional, Sequence

import numpy as np

from bench import flops, trace
from bench.spans import Dispatch

DECODE = "_decode"      # the jitted functions of ServeEngine
PREFILL = "_prefill"
PAGED_KERNEL = "paged"  # the Pallas kernel's operations carry this in name


@dataclasses.dataclass
class Traced:
    events: List[trace.Event]          # device modules and operations
    window_ns: tuple                   # the traced window, trace clock
    host_window_ns: tuple              # the same, host monotonic ns
    spans: List[trace.Span]            # host spans, trace clock
    dispatches: List[Dispatch]         # host monotonic ns
    requests: list                     # (prompt, t_submit, t_first)


@dataclasses.dataclass
class Run:
    conf: Dict
    peaks: Dict
    seconds: float
    setup_s: float
    t0: float                          # window start, monotonic s
    records: list                      # bench.client.Record
    reference: ModuleType              # the config's: FLOP and byte counts
    traced: Optional[Traced] = None


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    return float(np.percentile(values, q)) if len(values) else None


def median(values: Sequence[float]) -> Optional[float]:
    return float(statistics.median(values)) if len(values) else None


# -- the client's view ------------------------------------------------------

def window_tokens(run: Run) -> int:
    end = run.t0 + run.seconds
    return sum(1 for r in run.records for t in r.stamps if run.t0 <= t <= end)


def ttfts_ms(run: Run) -> List[float]:
    """Each request's first token, from when it was due."""
    return [(r.stamps[0] - r.due) * 1e3 for r in run.records if r.stamps]


def itls_ms(run: Run) -> List[float]:
    """Every gap between two streamed tokens whose later token arrived in
    the window."""
    end = run.t0 + run.seconds
    return [(b - a) * 1e3 for r in run.records
            for a, b in zip(r.stamps, r.stamps[1:]) if run.t0 <= b <= end]


def ttft_overhead_ms(run: Run) -> List[float]:
    """Client TTFT from send minus the engine's own t_first - t_submit,
    per request (same process, same monotonic clock)."""
    if run.traced is None:
        return []
    engine = {p: (sub, first) for p, sub, first in run.traced.requests}
    out = []
    for r in run.records:
        sub, first = engine.get(tuple(r.ask.prompt), (0.0, 0.0))
        if r.stamps and first > 0:
            out.append(((r.stamps[0] - r.sent) - (first - sub)) * 1e3)
    return out


# -- the trace ---------------------------------------------------------------

def _dispatched(run: Run, kind: str) -> List[Dispatch]:
    lo, hi = run.traced.host_window_ns
    return [d for d in run.traced.dispatches
            if d.kind == kind and lo <= d.t0_ns <= hi]


def _in_window(run: Run, events: List[trace.Event]) -> List[trace.Event]:
    lo, hi = run.traced.window_ns
    return [e for e in events if lo <= e.start_ns < hi]


def _execs(run: Run, program: str) -> List[trace.Event]:
    return _in_window(run, trace.executions(run.traced.events, program))


def decode_device_ms(run: Run) -> Optional[float]:
    if run.traced is None:
        return None
    ex = _execs(run, DECODE)
    return sum(e.dur_ns for e in ex) / len(ex) * 1e-6 if ex else None


def _step_flops(run: Run, d: Dispatch) -> int:
    ref = run.reference
    if d.kind == "decode":
        return ref.decode_step_flops(run.conf, d.spans)
    return ref.prefill_chunk_flops(run.conf, d.start, d.end, d.last)


def step_mfu(run: Run) -> Optional[float]:
    """Model FLOPs of the tokens the step programs processed over their
    device time at the chip's peak, in %.  FLOPs per execution are the
    mean over the window's dispatches of that program."""
    if run.traced is None:
        return None
    work = busy = 0.0
    for kind, program in (("decode", DECODE), ("prefill", PREFILL)):
        ex, ds = _execs(run, program), _dispatched(run, kind)
        if not ex or not ds:
            continue
        work += np.mean([_step_flops(run, d) for d in ds]) * len(ex)
        busy += sum(e.dur_ns for e in ex) * 1e-9
    if busy == 0:
        return None
    return 100.0 * work / (busy * float(run.peaks["bf16_flops_per_s"]))


def paged_decode_kernel(run: Run) -> List[trace.Event]:
    """The paged-attention kernel's operations inside decode executions."""
    t = run.traced
    modules = [e for e in t.events if e.line == trace.MODULES_LINE]
    op_events = _in_window(run, trace.ops(t.events))
    owner = trace.module_of(op_events, modules)
    return [e for e, m in zip(op_events, owner)
            if m == f"jit_{DECODE}" and PAGED_KERNEL in e.name]


def paged_decode_roofline(run: Run) -> Optional[tuple]:
    """(share in %, bound): the least time for the bytes and FLOPs decode
    attention needs (live KV only), over the kernel's device time."""
    if run.traced is None:
        return None
    kernel = paged_decode_kernel(run)
    ex, ds = _execs(run, DECODE), _dispatched(run, "decode")
    if not kernel or not ex or not ds:
        return None
    ref = run.reference
    need, bounds = [], []
    for d in ds:
        t, bound = flops.least_seconds(
            ref.paged_decode_attention_flops(run.conf, d.spans),
            ref.paged_decode_attention_bytes(run.conf, d.spans), run.peaks)
        need.append(t)
        bounds.append(bound)
    kernel_s = sum(e.dur_ns for e in kernel) * 1e-9
    share = 100.0 * float(np.mean(need)) * len(ex) / kernel_s
    return share, max(set(bounds), key=bounds.count)


def idle_share(run: Run) -> Optional[float]:
    """1 - busy / window in %, averaged over the devices in the trace."""
    if run.traced is None:
        return None
    t = run.traced
    devs = trace.devices(t.events)
    if not devs:
        return None
    width = t.window_ns[1] - t.window_ns[0]
    busy = [trace.busy_ns(trace.ops(t.events, d), t.window_ns) for d in devs]
    return 100.0 * (1.0 - float(np.mean(busy)) / width)


def prefill_dispatches_per_request(run: Run) -> Optional[float]:
    """Prefill executions in the trace over the requests whose prefill
    finished in it."""
    if run.traced is None:
        return None
    finished = sum(1 for d in _dispatched(run, "prefill") if d.last)
    ex = _execs(run, PREFILL)
    return len(ex) / finished if finished and ex else None
