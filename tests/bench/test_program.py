"""The arithmetic of ``bench/program.py`` on a small hand-made set of the
program's spans and counters: requests picked by the window, the device's
idle time inside the stepper's host work, set-up's compile counters and
kernel plan, and None (never 0) where there is nothing to read."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import measures, program, trace  # noqa: E402
from repro.tracing import Record  # noqa: E402

S = 10**9
OFF = -10_000          # host monotonic ns + OFF = trace clock


def _rec(name, t0, t1, rid=None):
    return Record(name, t0, t1, 0, -1, rid)


def _run(traced=True):
    ops = [trace.Event("op", 100, 200, trace.OPS_LINE, 0),
           trace.Event("op", 500, 200, trace.OPS_LINE, 0),
           trace.Event("op", 0, 1000, trace.OPS_LINE, 1)]   # other device
    t = measures.Traced(events=ops, window_ns=(0, 1000),
                        host_window_ns=(10_000, 11_000), spans=[],
                        dispatches=[], requests=[]) if traced else None
    return measures.Run(conf={}, peaks={}, seconds=2.0, setup_s=0.0, t0=1.0,
                        records=[], reference=None, traced=t)


def _requests():
    """Requests received before, twice inside and after the window
    [1 s, 3 s]: (rid, received, queue, prefill) in ms."""
    out = []
    for rid, at, inbox, queue, prefill in ((1, 0.5, 1, 100, 700),
                                           (2, 1.5, 20, 200, 800),
                                           (3, 2.5, 30, 400, 1000),
                                           (4, 3.5, 9, 9000, 9000)):
        t = int(at * S)
        for part, ms in (("inbox", inbox), ("queue", queue),
                         ("prefill", prefill)):
            out.append(_rec(f"request.{part}", t, t + ms * 10**6, rid))
            t += ms * 10**6
    return out


def test_request_parts_of_requests_received_in_the_window():
    prog = program.Program(records=_requests(), counters_at_open={})
    run = _run()
    assert program.request_part_ms(prog, run, "queue") == [200, 400]
    assert program.inbox_wait_p50_ms(prog, run) == 25.0
    assert program.queue_wait_p50_ms(prog, run) == 300.0
    assert program.prefill_time_p50_ms(prog, run) == 900.0


def test_logits_copy_by_window():
    recs = [_rec("decode.logits_copy", int(t * S), int(t * S) + ms * 10**6)
            for t, ms in ((0.9, 50), (1.2, 2), (1.8, 4), (2.9, 3))]
    recs.append(_rec("prefill.logits_copy", 2 * S, 2 * S + 10**8))
    prog = program.Program(records=recs, counters_at_open={})
    assert program.logits_copy_ms(prog, _run()) == 3.0


def test_idle_inside_host_work_on_the_trace_clock():
    # device 0 busy [100, 300) and [500, 700) of the window [0, 1000);
    # host work [50, 450) with a device wait [200, 400), and [800, 900);
    # the stepper's idle wait [900, 990) is no host work
    h = lambda t: t - OFF  # noqa: E731
    recs = [_rec("engine.step", h(50), h(450)),
            _rec("decode.dispatch", h(60), h(190)),
            _rec("decode.device_wait", h(200), h(400)),
            _rec("stepper.drain", h(800), h(900)),
            _rec("stepper.idle", h(900), h(990))]
    prog = program.Program(records=recs, counters_at_open={})
    run = _run()
    # idle [0,100) u [300,500) u [700,1000) meets host work outside the
    # wait in [50,100), [400,450) and [800,900): 200 ns of 1000
    assert program.idle_host_share(prog, run) == pytest.approx(20.0)
    assert program.idle_host_share(prog, run) <= measures.idle_share(run)


def test_set_up_counters_and_kernel_plan_before_the_window():
    prog = program.Program(
        records=[_rec("engine.kernel_plan", 0, S // 4),
                 _rec("engine.kernel_plan", S // 2, S // 2 + S // 4),
                 _rec("engine.kernel_plan", 2 * S, 3 * S)],   # in the window
        counters_at_open={"jit.jaxpr_trace_s": 1.5,
                          "jit.backend_compile_s": 2.0, "other_s": 9.0})
    assert program.jit_s(prog) == 3.5
    assert program.kernel_plan_s(prog, _run()) == 0.5


def test_nothing_to_read_gives_none():
    empty = program.Program(records=[], counters_at_open={})
    run = _run()
    for f in (program.inbox_wait_p50_ms, program.queue_wait_p50_ms,
              program.prefill_time_p50_ms, program.logits_copy_ms,
              program.idle_host_share, program.kernel_plan_s):
        assert f(empty, run) is None
    assert program.jit_s(empty) is None
    busy = program.Program(records=[_rec("engine.step", 0, 10**6)],
                           counters_at_open={})
    assert program.idle_host_share(busy, _run(traced=False)) is None
    # requests only outside the window
    before = program.Program(records=_requests()[:3], counters_at_open={})
    assert program.queue_wait_p50_ms(before, run) is None
