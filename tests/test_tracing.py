"""The program's tracer (``repro.tracing``): nothing recorded while off,
spans nested by thread with their request ids, a bounded ring, the TTFT
split that adds up to the request's own stamps, dispatch facts equal to
what the benchmark's recorder notes, profiler annotations on the same
clock, compile time counted once, and each dispatch counted as having
consumed the KV pool it was given or not."""
import asyncio
import glob
import os
import sys
import time
from collections import Counter
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro import tracing
from repro.configs.base import get_config, reduced_config
from repro.models import build_model
from repro.serve.async_engine import AsyncServeEngine
from repro.serve.engine import Request, ServeEngine

ROOT = Path(__file__).resolve().parents[1]

STEP_CHILDREN = {"engine.reap", "engine.admit", "engine.prefill",
                 "engine.decode"}


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config(get_config("qwen3-0.6b"))
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture
def tracer():
    """The tracer, on for one test and off (and drained) after it."""
    tracing.enable()
    yield tracing
    tracing.disable()
    tracing.drain()


def _engine(cfg, params, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_len", 48)
    kw.setdefault("block_size", 4)
    kw.setdefault("plan_kernels", False)
    return ServeEngine(cfg, params, **kw)


def _serve(engine, prompts, max_new=4):
    reqs = [Request(rid=i, prompt=list(p), max_new=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        engine.submit(r)
    engine.run_until_done(max_steps=500)
    assert all(r.done for r in reqs)
    return reqs


PROMPTS = [[3 + i for i in range(11)], [40 + i for i in range(6)],
           [70 + i for i in range(9)]]


def test_off_records_nothing(setup):
    cfg, params = setup
    tracing.disable()
    tracing.drain()
    assert tracing.span("a") is tracing.span("b")
    assert not tracing.span("a")
    with tracing.span("a"):
        tracing.record("b", 1, 2, rid=3)
        tracing.count("c", 1.0)
    _serve(_engine(cfg, params), PROMPTS)
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(5)).block_until_ready()
    assert tracing.drain() == []
    assert tracing.counters() == {}


def test_spans_nest_by_thread_and_the_ring_is_bounded(setup, tracer):
    cfg, params = setup
    with tracer.span("outer", rid=7, kind="x"):
        with tracer.span("inner"):
            pass
        tracer.record("stamped", 10, 20, rid=7)
    inner, stamped, outer = tracer.drain()
    assert (outer.name, outer.parent, outer.rid, outer.facts) == \
        ("outer", -1, 7, {"kind": "x"})
    assert (inner.name, inner.parent, inner.rid) == ("inner", outer.id, None)
    assert (stamped.t0_ns, stamped.t1_ns, stamped.parent) == (10, 20, -1)
    assert outer.t0_ns <= inner.t0_ns <= inner.t1_ns <= outer.t1_ns

    _serve(_engine(cfg, params), PROMPTS)
    recs = tracer.drain()
    by_id = {r.id: r for r in recs}
    names = Counter(r.name for r in recs)
    steps = names["engine.step"]
    assert steps > 0 and all(names[n] == steps for n in STEP_CHILDREN)
    for r in recs:
        parent = by_id.get(r.parent)
        if r.name in STEP_CHILDREN:
            assert parent.name == "engine.step"
        elif r.name.startswith(("prefill.", "decode.")):
            assert parent.name == "engine." + r.name.split(".")[0]
        if parent is not None:
            assert parent.t0_ns <= r.t0_ns <= r.t1_ns <= parent.t1_ns
    assert {r.rid for r in recs if r.name == "prefill.dispatch"} == {0, 1, 2}
    # each decode step copies the logits once, after waiting for them
    assert names["decode.device_wait"] == names["decode.logits_copy"] \
        == names["decode.dispatch"] > 0
    assert names["prefill.logits_copy"] == len(PROMPTS)

    tracer.enable(capacity=4)
    for i in range(10):
        with tracer.span(f"s{i}"):
            pass
    assert [r.name for r in tracer.drain()] == ["s6", "s7", "s8", "s9"]


def test_ttft_is_inbox_plus_queue_plus_prefill(setup, tracer):
    cfg, params = setup
    prompts = PROMPTS + [[90 + i for i in range(13)], [5, 6, 7]]

    async def go():
        aeng = AsyncServeEngine(_engine(cfg, params))
        await aeng.start()
        try:
            streams = [aeng.submit(p, max_new=3) for p in prompts]
            await asyncio.gather(*[s.drain() for s in streams])
        finally:
            await aeng.stop()
        return [s.req for s in streams]

    reqs = asyncio.run(go())
    recs = tracer.drain()
    assert {"stepper.drain", "stepper.idle"} <= {r.name for r in recs}
    spans = {(r.name, r.rid): r for r in recs if r.name.startswith("request.")}
    ns = lambda t: round(t * 1e9)  # noqa: E731
    for q in reqs:
        assert 0 < q.t_received <= q.t_submit <= q.t_prefill0 <= q.t_first \
            <= q.t_done
        part = {k: spans[("request." + k, q.rid)]
                for k in ("inbox", "queue", "prefill", "decode")}
        assert sum(part[k].t1_ns - part[k].t0_ns
                   for k in ("inbox", "queue", "prefill")) == \
            ns(q.t_first) - ns(q.t_received)
        assert part["inbox"].t0_ns == ns(q.t_received)
        assert part["decode"].t1_ns == ns(q.t_done)


def test_dispatch_facts_equal_the_benchmark_recorder(setup, tracer):
    sys.path.insert(0, str(ROOT))
    from bench.spans import SpanRecorder

    cfg, params = setup
    engine = _engine(cfg, params, max_batch=3)
    rec = SpanRecorder()
    rec.install(engine)
    _serve(engine, PROMPTS, max_new=5)
    theirs = [(d.kind, d.start, d.end, d.last, d.spans)
              for d in rec.dispatches]
    mine = []
    for r in tracer.drain():
        if r.name == "prefill.dispatch":
            f = r.facts
            mine.append(("prefill", f["start"], f["end"], f["last"], []))
        elif r.name == "decode.dispatch":
            mine.append(("decode", 0, 0, False, r.facts["spans"]))
    assert len(theirs) > 10
    assert mine == theirs


def test_decode_consumes_the_pool_it_is_given(setup, tracer):
    """Decode donates the KV pool: after a decode dispatch the pool it was
    handed is deleted and only the returned one is live.  Prefill does not
    donate, so its input pool stays live.  The tracer counts each decode
    dispatch as donated and each prefill dispatch as copied."""
    cfg, params = setup
    engine = _engine(cfg, params)
    given = []
    for name in ("_prefill_fn", "_decode_fn"):
        def handed(p, c, b, _fn=getattr(engine, name), _name=name, **kw):
            given.append((_name, c))
            return _fn(p, c, b, **kw)
        setattr(engine, name, handed)
    _serve(engine, PROMPTS[:1], max_new=3)
    deleted = {n: [all(x.is_deleted() for x in jax.tree.leaves(c))
                   for m, c in given if m == n] for n, _ in given}
    assert deleted == {"_prefill_fn": [False] * 3, "_decode_fn": [True] * 2}
    assert not any(x.is_deleted() for x in jax.tree.leaves(engine.cache))
    dispatches = Counter(r.name for r in tracer.drain()
                         if r.name.endswith(".dispatch"))
    assert dispatches == {"prefill.dispatch": 3, "decode.dispatch": 2}
    assert tracer.counters().get("kv.pool_donated") == 2
    assert tracer.counters().get("kv.pool_copied") == 3


def test_annotations_agree_with_records_on_the_trace_clock(setup, tracer,
                                                          tmp_path):
    from jax.profiler import ProfileData

    cfg, params = setup
    engine = _engine(cfg, params)
    _serve(engine, PROMPTS[:1])                 # compile outside the capture
    tracer.drain()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracer.span("test.sync"):
            pass
        _serve(engine, PROMPTS)
        with tracer.span("test.sleep"):
            time.sleep(0.02)
    finally:
        jax.profiler.stop_trace()
    recs = [r for r in tracer.drain() if not r.name.startswith("request.")]
    path = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                     recursive=True)[0]
    names = {r.name for r in recs}
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in names:
                        events.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    sync = recs[0]
    assert sync.name == "test.sync"
    off = events["test.sync"][0][0] - sync.t0_ns
    seen = Counter()
    for r in recs:
        t0, t1 = events[r.name][seen[r.name]]
        seen[r.name] += 1
        assert abs(t0 - (r.t0_ns + off)) < 100_000, r.name
        assert abs(t1 - (r.t1_ns + off)) < 100_000, r.name
    assert len(recs) > 50 and seen["test.sleep"] == 1


def test_compile_time_counted_once_per_phase(tracer):
    def inner(x):
        time.sleep(0.05)        # while traced: the nested trace is slow
        return x + 1

    inner = jax.jit(inner)

    @jax.jit
    def outer(x):
        return inner(x) * 2

    t = time.monotonic()
    outer(jnp.arange(7.0)).block_until_ready()
    wall = time.monotonic() - t
    got = tracer.counters()
    assert {"jit.jaxpr_trace_s", "jit.jaxpr_to_mlir_module_s",
            "jit.backend_compile_s"} <= set(got)
    assert got["jit.jaxpr_trace_s"] >= 0.05
    # with the nested trace counted twice the phases would exceed the wall
    assert sum(got.values()) <= wall
