"""Host spans the benchmark records around its calls into the engine, in
traced runs only.

The program has no spans of its own yet, so the recorder wraps the
engine's methods on the instance (never the class): the engine step, its
admission pass, the prefill and decode dispatches and each sampling call.
``engine.logits_read`` is the stretch from a dispatch's return to the
sampling call after it: the wait for the device and the copy of the
logits to the host.  Each dispatch also notes what it computes (the rows
and spans of a decode step, the positions of a prefill chunk), which the
FLOP and byte functions read.  Those facts come from the engine's host
state (its slots' positions, its prefill token tally); no device buffer
is read back, so a traced run queues its dispatches as an untraced one
does.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import List, Optional


@dataclasses.dataclass
class Dispatch:
    kind: str                 # "decode" | "prefill"
    t0_ns: int
    t1_ns: int
    spans: List[int] = dataclasses.field(default_factory=list)  # decode
    start: int = 0            # prefill positions start..end-1
    end: int = 0
    last: bool = False        # prefill: the prompt's final chunk


class SpanRecorder:
    def __init__(self):
        self.spans: List[tuple] = []        # (name, t0_ns, t1_ns, depth)
        self.dispatches: List[Dispatch] = []
        self.requests: list = []            # the engine's Request objects
        self._local = threading.local()
        self._pending: Optional[Dispatch] = None

    @contextlib.contextmanager
    def span(self, name: str):
        depth = getattr(self._local, "depth", 0)
        self._local.depth = depth + 1
        t0 = time.monotonic_ns()
        try:
            yield
        finally:
            self.spans.append((name, t0, time.monotonic_ns(), depth))
            self._local.depth = depth

    def _wrap(self, engine, attr: str, name: str, before=None):
        """Wrap ``engine.<attr>`` in a span; ``before()`` returns the
        Dispatch the call makes, noted before it runs."""
        inner = getattr(engine, attr)

        def wrapped(*args, **kwargs):
            d = before() if before is not None else None
            t0 = time.monotonic_ns()
            with self.span(name):
                out = inner(*args, **kwargs)
            if d is not None:
                d.t0_ns, d.t1_ns = t0, time.monotonic_ns()
                self.dispatches.append(d)
                self._pending = d
            return out

        setattr(engine, attr, wrapped)

    def install(self, engine) -> None:
        """Wrap ``engine``'s methods; called before the engine serves."""
        submit = engine.submit

        def on_submit(req):
            self.requests.append(req)
            return submit(req)

        engine.submit = on_submit
        self._wrap(engine, "step", "engine.step")
        self._wrap(engine, "_admit", "engine.admit")
        self._wrap(engine, "_prefill_fn", "engine.prefill_dispatch",
                   before=lambda: Dispatch("prefill", 0, 0))
        self._wrap(engine, "_decode_fn", "engine.decode_dispatch",
                   before=lambda: Dispatch("decode", 0, 0,
                                                spans=_decode_spans(engine)))
        chunk_for = engine._prefill_chunk_for

        def prefill_chunk(a):
            # the engine tallies the prompt tokens each chunk writes and
            # moves the slot's next_prefill to the chunk's end
            n, tally = len(self.dispatches), engine._prefill_tokens
            out = chunk_for(a)
            if len(self.dispatches) > n:
                d = self.dispatches[-1]
                d.end = a.next_prefill
                d.start = d.end - (engine._prefill_tokens - tally)
                d.last = a.prefill_done
            return out

        engine._prefill_chunk_for = prefill_chunk
        sample = engine._sample

        def sampled(*args, **kwargs):
            p = self._pending
            if p is not None:
                self._pending = None
                self.spans.append(("engine.logits_read", p.t1_ns,
                                   time.monotonic_ns(), 1))
            with self.span("engine.sample"):
                return sample(*args, **kwargs)

        engine._sample = sampled

    def stamps(self) -> list:
        """(prompt, t_submit, t_first) of every request the engine saw;
        plain values, so nothing keeps the engine alive."""
        return [(tuple(q.prompt), q.t_submit, q.t_first)
                for q in self.requests]


def _decode_spans(engine) -> List[int]:
    """Each row a decode step computes, from the engine's slots: every
    prefilled slot writes its next position, so it attends over its
    written KV plus the new token."""
    return [a.pos + 1 for a in engine.slots
            if a is not None and a.prefill_done]
