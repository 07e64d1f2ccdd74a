"""The program's own spans and counters: off by default, in memory only.

    from repro import tracing
    tracing.enable()                 # spans from here on
    ...serve...
    records = tracing.drain()        # every span closed since, oldest first
    jit_s = tracing.counters()       # e.g. {"jit.backend_compile_s": 41.2}

A span is a name, a start and an end on ``time.monotonic_ns`` (the clock of
the engine's request stamps), the id of the span that was open on the same
thread when it opened (its parent; -1 for none, and for a span built from
stamps), the request id where there is one, and a small dict of facts.  Closed spans go to a bounded ring, so a tracer left
on cannot grow without limit; nothing is written out.  While tracing is on,
each scoped span also opens a ``jax.profiler.TraceAnnotation`` of the same
name, so a ``jax.profiler`` capture shows the program's spans beside the
device's operations, on the trace's own clock.

Off, ``span`` returns one shared no-op context and ``record``/``count``
return at once: one check of a module flag per call.

On, a ``jax.monitoring`` listener adds the time JAX spends tracing,
lowering and compiling (``/jax/core/compile/*``; a compile that the
persistent cache serves is the cache read, inside
``backend_compile_duration``) to the counters ``jit.<phase>_s``.  A phase
nested in another (a jitted function traced while its caller is traced)
counts once, in the outer one, so the counters add up to the wall time
spent.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional

_JIT_EVENTS = "/jax/core/compile/"

_on = False
_ring: collections.deque = collections.deque(maxlen=1)
_ids = itertools.count()
_counters: Dict[str, float] = {}
_lock = threading.Lock()


class _Local(threading.local):
    def __init__(self):
        self.stack: List[int] = []      # ids of the spans open, innermost last
        self.jit_done: list = []        # compile phases ended, see _on_jax_span


_local = _Local()
_annotate = None            # jax.profiler.TraceAnnotation once enabled
_listening = False


class Record(NamedTuple):
    name: str
    t0_ns: int
    t1_ns: int
    id: int
    parent: int             # id of the enclosing span, -1 at the top
    rid: Optional[int] = None
    facts: Optional[dict] = None


class _Off:
    """The span handed out while tracing is off: does nothing, and is
    falsy, so ``if sp:`` skips work done only to fill in facts."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def __bool__(self):
        return False


_OFF = _Off()


class _Span:
    __slots__ = ("name", "rid", "facts", "id", "parent", "t0", "ann")

    def __init__(self, name: str, rid: Optional[int], facts: dict):
        self.name, self.rid, self.facts = name, rid, facts

    def __enter__(self):
        stack = _local.stack
        self.parent = stack[-1] if stack else -1
        self.id = next(_ids)
        stack.append(self.id)
        self.ann = _annotate(self.name)
        self.ann.__enter__()
        self.t0 = time.monotonic_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.monotonic_ns()
        self.ann.__exit__(*exc)
        _local.stack.pop()
        # a plain tuple: drain() makes it a Record, off the hot path
        _ring.append((self.name, self.t0, t1, self.id, self.parent,
                      self.rid, self.facts or None))
        return False


def enabled() -> bool:
    return _on


def enable(capacity: int = 1 << 17) -> None:
    """Start recording, into a fresh ring of ``capacity`` spans (the
    oldest are dropped once it is full), with fresh counters."""
    global _on, _ring, _annotate, _listening
    from jax import monitoring
    from jax.profiler import TraceAnnotation

    _annotate = TraceAnnotation
    _ring = collections.deque(maxlen=capacity)
    with _lock:
        _counters.clear()
    if not _listening:
        monitoring.register_event_time_span_listener(_on_jax_span)
        _listening = True
    _on = True


def disable() -> None:
    """Stop recording; spans not yet drained stay until the next
    ``drain`` or ``enable``."""
    global _on
    _on = False


def drain() -> List[Record]:
    """Every span closed since the last drain, in the order they closed,
    and empty the ring."""
    out = []
    while True:
        try:
            out.append(Record._make(_ring.popleft()))
        except IndexError:
            return out


def span(name: str, rid: Optional[int] = None, **facts):
    """A context manager that records ``name`` from enter to exit."""
    if not _on:
        return _OFF
    return _Span(name, rid, facts)


def record(name: str, t0_ns: int, t1_ns: int, rid: Optional[int] = None,
           **facts) -> None:
    """A span built from two stamps already taken.  It has no parent:
    the span open when it is recorded did not cause it."""
    if not _on:
        return
    _ring.append((name, t0_ns, t1_ns, next(_ids), -1, rid, facts or None))


def count(name: str, value: float = 1) -> None:
    if not _on:
        return
    with _lock:
        _counters[name] = _counters.get(name, 0) + value


def counters() -> Dict[str, float]:
    """A snapshot of every counter."""
    with _lock:
        return dict(_counters)


def _on_jax_span(event: str, start: float, end: float, **_) -> None:
    """``jax.monitoring`` time-span listener: add a compile phase's time
    to ``jit.<phase>_s``, taking back the time of phases nested in it so
    that nothing counts twice.  Phases end inner first, on one thread."""
    if not _on or not event.startswith(_JIT_EVENTS):
        return
    done = _local.jit_done
    key = f"jit.{event[len(_JIT_EVENTS):].removesuffix('_duration')}_s"
    while done and done[-1][0] >= start:
        s, e, k = done.pop()
        count(k, -(e - s))
    count(key, end - start)
    done.append((start, end, key))
    # bounded memory: a phase that holds more nested phases than this
    # would count the oldest of them twice
    del done[:-1024]
