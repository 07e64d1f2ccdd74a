"""One run of one cell: set-up, the measured window, the check that decides
``correct``, and the metrics.

The system under test is the OpenAI gateway booted in-process
(``repro.serve.gateway.Gateway``) over a ``Router`` built as
``repro.launch.gateway.build_router`` builds it, except that the weights
are the benchmark's, made on the device from the seed.  The engine is
given the deployment facts of the configuration file (arch, max_batch,
max_len, block_size) and nothing else, so its defaults run as shipped.
Every family-specific fact (the program's config, the weights, the plain
reference, FLOP and byte counts) comes from the module the configuration
names under ``reference`` (``spec.load_reference``).
"""
from __future__ import annotations

import asyncio
import dataclasses
import functools
import gc
import os
import shutil
import sys
import tempfile
import time
from types import ModuleType
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import client, measures, spec, trace
from bench.spans import SpanRecorder
from bench.traffic.generate import longest_request, make_schedule

GRACE_S = 60.0          # how long answers due in the window are awaited
WARMUP_S = 900.0        # the warm-up, which compiles every program cold
CHECK_TOKENS = 384      # served tokens the reference reads, at least
CHECK_REQUESTS = 12     # and requests, at most
TRACE_MAX_S = 5.0       # traced stretch of the window, centred
AT_LEAST = {"checked_tokens"}   # every other check's limit is a maximum


class SetupError(RuntimeError):
    pass


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def program_config(conf: Dict, reference: ModuleType, strict: bool = True):
    """The program's ModelConfig for a configuration file: the registry's
    entry with the file's numbers, laid on by the configuration's
    reference module, which refuses a family it does not cover.
    ``strict`` requires the two to agree, so a run at full size runs
    exactly the registered model."""
    from repro.configs.base import get_config

    base = get_config(conf["registry_id"])
    try:
        cfg = reference.program_config(conf, base)
    except ValueError as e:
        raise SetupError(str(e)) from e
    if strict and cfg != base:
        diff = {f.name: (getattr(base, f.name), getattr(cfg, f.name))
                for f in dataclasses.fields(cfg)
                if getattr(base, f.name) != getattr(cfg, f.name)}
        raise SetupError(f"{conf['registry_id']}: the configuration file "
                         f"differs from the registry (registry, file): {diff}")
    return cfg


def check_layout(cfg, params) -> None:
    """The benchmark's weights must have the program's own structure,
    shapes and types (read from its init without running it)."""
    from repro.models import build_model

    want = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    shape = lambda t: jax.tree.map(lambda a: (a.shape, str(a.dtype)), t)
    if shape(want) != shape(params):
        raise SetupError(f"weight layout differs from the program's: "
                         f"{shape(want)} vs {shape(params)}")


class CompileCounter:
    """Programs compiled or loaded from the cache while ``active``."""

    def __init__(self):
        self.active = False
        self.count = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if self.active and name == "/jax/compilation_cache/cache_hits":
            self.count += 1

    def _duration(self, name, _secs, **_):
        if self.active and name == "/jax/core/compile/backend_compile_duration":
            self.count += 1


@dataclasses.dataclass
class Session:
    records: List[client.Record]
    t0: float                   # the window opens
    t_ready: float              # the warm-up ended and the clients start
    trace_dir: Optional[str] = None
    host_window_ns: tuple = (0, 0)
    sync_ns: int = 0


async def _serve(router, sched, seconds: float, traced: bool,
                 counter: CompileCounter, grace: float) -> Session:
    from repro.serve.gateway import Gateway

    model_id = router.models()[0].model_id
    async with Gateway(router, port=0) as gw:
        try:
            warm = await asyncio.wait_for(
                client.run_asks(gw.port, model_id, sched.warmup), WARMUP_S)
        except asyncio.TimeoutError:
            await router.stop()
            raise SetupError(f"the warm-up got no answer in {WARMUP_S} s")
        bad = [r for r in warm if not r.ok]
        if bad:
            raise SetupError(f"warm-up request failed: status {bad[0].status} "
                             f"finish {bad[0].finish!r}")
        # a closed loop's clients start now and fill the batch before the
        # window opens; the ramp is serving, not set-up
        t_ready = time.monotonic()
        t0 = t_ready + sched.ramp_s
        counter.active = True
        loop = client.closed_loop if sched.clients else client.open_loop
        task = asyncio.ensure_future(loop(gw.port, model_id, sched, t0,
                                          seconds, grace))
        s = Session(records=[], t0=t0, t_ready=t_ready)
        if traced:
            await _trace_middle(s, t0, seconds)
        s.records = await task
        counter.active = False
        # end every stream before the server waits for its connections:
        # a request the engine never finishes would hold Gateway.stop
        await router.stop()
    if sched.clients and len(s.records) == len(sched.asks):
        raise SetupError(f"the closed loop used up all {len(sched.asks)} "
                         f"requests of the mix before the window closed")
    return s


async def _trace_middle(s: Session, t0: float, seconds: float) -> None:
    """Profile the middle of the window; the profiler starts and stops off
    the event loop so the clients keep their schedule."""
    loop = asyncio.get_running_loop()
    length = min(TRACE_MAX_S, seconds / 3)
    await asyncio.sleep(max(0.0, t0 + (seconds - length) / 2
                            - time.monotonic()))
    s.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    await loop.run_in_executor(None, jax.profiler.start_trace, s.trace_dir)
    with jax.profiler.TraceAnnotation(trace.SYNC_SPAN):
        s.sync_ns = time.monotonic_ns()
    lo = time.monotonic_ns()
    await asyncio.sleep(length)
    hi = time.monotonic_ns()
    s.host_window_ns = (lo, hi)
    await loop.run_in_executor(None, jax.profiler.stop_trace)


def _reduce_trace(s: Session, rec: SpanRecorder, stamps: list
                  ) -> measures.Traced:
    events, markers = trace.read_xplane(s.trace_dir)
    off = trace.offset_ns(markers, s.sync_ns)
    lo, hi = s.host_window_ns
    spans = [trace.Span(n, a + off, b + off, d) for n, a, b, d in rec.spans]
    return measures.Traced(events=events, window_ns=(lo + off, hi + off),
                           host_window_ns=(lo, hi), spans=spans,
                           dispatches=list(rec.dispatches),
                           requests=stamps)


def breakdown(t: measures.Traced) -> Dict:
    """The device operations that took most of their own time (labelled
    with their program), and the idle gaps by what the host was doing."""
    ops = [e for e in trace.ops(t.events) if
           t.window_ns[0] <= e.start_ns < t.window_ns[1]]
    owner = trace.module_of(
        ops, [e for e in t.events if e.line == trace.MODULES_LINE])
    own = trace.self_ns(ops)
    named = [dataclasses.replace(
        e, name=f"{m or 'none'}:{trace.short_name(e.name)}", dur_ns=d)
        for e, m, d in zip(ops, owner, own)]
    devs = trace.devices(t.events)
    gaps = trace.idle_gaps(trace.ops(t.events, devs[0]), t.window_ns) \
        if devs else []
    return {"device_ops": trace.top(trace.time_by_name(named)),
            "idle_gaps": trace.top(trace.attribute_gaps(gaps, t.spans))}


def pick_checked(records: List[client.Record], seed: int
                 ) -> List[client.Record]:
    """Answered requests the reference reads, greedy ones and sampled ones
    apart (so each kind is read whatever its share): of each, the longest
    one, then others in an order drawn from the seed, up to CHECK_TOKENS
    served tokens or CHECK_REQUESTS requests."""
    out: List[client.Record] = []
    for kind, greedy in enumerate((True, False)):
        pool = [r for r in records
                if r.ok and (r.ask.temperature <= 0) == greedy]
        if not pool:
            continue
        longest = max(pool, key=lambda r: (len(r.ask.prompt) + len(r.tokens),
                                           r.ask.index))
        rest = [r for r in pool if r is not longest]
        order = np.random.default_rng([seed, 2 + kind]).permutation(
            len(rest))
        picked, n = [longest], len(longest.tokens)
        for i in order:
            if n >= CHECK_TOKENS or len(picked) >= CHECK_REQUESTS:
                break
            picked.append(rest[i])
            n += len(rest[i].tokens)
        out += picked
    return out


@functools.partial(jax.jit, static_argnums=2)
def kth_gap(ref_rows, picked, k: int):
    """How far each picked token's reference logit lies below the
    reference's k-th best logit of its row, 0 where it lies above; at
    k = 1 the gap below the best."""
    kth = jax.lax.top_k(ref_rows, k)[0][:, -1]
    got = jnp.take_along_axis(ref_rows, picked[:, None], axis=-1)[:, 0]
    return jnp.maximum(kth - got, 0.0)


def served_rows(prompt: Sequence[int], served: Sequence[int]
                ) -> Tuple[List[int], List[int]]:
    """The sequence the reference reads and the rows whose logits chose
    each served token: token i was chosen at position len(prompt)-1+i."""
    seq = list(prompt) + list(served[:-1])
    return seq, [len(prompt) - 1 + i for i in range(len(served))]


def served_gaps(conf: Dict, reference: ModuleType, params,
                picked: List[client.Record], control: bool = False
                ) -> List[np.ndarray]:
    """Per checked request, each served token's gap below the reference's
    k-th best logit (k = 1 for a greedy request, its ``top_k`` for a
    sampled one); with ``control``, the gap of the token that the float8
    control puts first at the same position instead."""
    pad = int(conf["deployment"]["max_len"])
    out = []
    for r in picked:
        seq, rows = served_rows(r.ask.prompt, r.tokens)
        ref = reference.logits_at(conf, params, seq, rows, pad)
        if control:
            ctrl = reference.logits_at(conf, params, seq, rows, pad,
                                       quant="fp8")
            chosen = jnp.argmax(ctrl, axis=-1).astype(jnp.int32)
        else:
            chosen = jnp.asarray(r.tokens, jnp.int32)
        out.append(np.asarray(kth_gap(ref, chosen, r.ask.rank_allowed)))
    return out


def check(conf: Dict, records: List[client.Record],
          gaps: List[np.ndarray]) -> Dict:
    """The numbers ``correct`` compares, each with its limit."""
    return {
        "failed_requests": {"value": sum(not r.ok for r in records),
                            "limit": 0},
        "checked_tokens": {"value": int(sum(len(g) for g in gaps)),
                           "limit": 1},
        # None where no request could be checked: never a pass
        "served_gap_max": {"value": float(max(g.max() for g in gaps))
                           if gaps else None,
                           "limit": conf["correct"]["served_gap_max"]},
    }


def gap_readings(gaps: List[np.ndarray]) -> Dict:
    """The widest gap, the mean gap and the share of tokens below their
    allowed rank (``off_best_share``), over every checked token (control
    runs)."""
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    if not g.size:
        return {}
    return {"max": float(g.max()), "mean": float(g.mean()),
            "off_best_share": float((g > 0).mean())}


def passed(checks: Dict) -> bool:
    """Every check within its limit."""
    return all(c["value"] is not None and (
        c["value"] >= c["limit"] if name in AT_LEAST
        else c["value"] <= c["limit"])
        for name, c in checks.items())


def build(cell: spec.Cell, cfg, seed: int):
    """(weights, router): the benchmark's weights from ``seed`` and the
    gateway's router over one engine given only the deployment facts."""
    from repro.serve.gateway import Router, build_model

    dep = cell.config["deployment"]
    params = cell.reference.make_weights(cell.config, seed)
    jax.block_until_ready(params)
    check_layout(cfg, params)
    model = build_model(cfg, params, max_batch=int(dep["max_batch"]),
                        max_len=int(dep["max_len"]),
                        block_size=int(dep["block_size"]),
                        plan_kernels=True, mesh=None)
    e = model.engine
    log(f"engine: pages_per_fetch={e.pages_per_fetch} "
        f"pool_blocks={e.pool.usable_blocks} "
        f"prefill_chunk={e.prefill_chunk_tokens}")
    return params, Router([model])


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             t_start: float, program_cfg=None, devices=None,
             break_engine: Optional[Callable] = None,
             control: bool = False, grace: float = GRACE_S) -> Dict:
    """Run ``cell`` once and return the result line's content.

    ``program_cfg`` replaces the registry's config (small CPU tests);
    ``break_engine`` is called on the engine before it serves (the tests
    that plant faults); ``control`` puts the float8 control in the
    program's place for ``checks`` and ``correct``, and keeps the
    program's own checks of the same run in ``program_checks``;
    ``grace`` is how long answers due in the window are awaited."""
    conf, dep = cell.config, cell.config["deployment"]
    cfg = program_cfg or program_config(conf, cell.reference)
    if longest_request(cell.traffic) > int(dep["max_len"]):
        raise SetupError("the mix's longest request exceeds max_len")
    devices = devices or jax.devices()
    counter = CompileCounter()
    sched = make_schedule(cell.traffic, seed, seconds, cfg.vocab)

    params, router = build(cell, cfg, seed)
    log(f"setup: weights and engine built at {time.monotonic() - t_start:.1f} s")
    engine = router.models()[0].engine
    rec = SpanRecorder()
    if traced:
        rec.install(engine)
    if break_engine is not None:
        break_engine(engine)

    log(f"setup: engine ready at {time.monotonic() - t_start:.1f} s")
    s = asyncio.run(_serve(router, sched, seconds, traced, counter, grace))
    setup_s = s.t_ready - t_start
    log(f"setup: clients started at {setup_s:.1f} s, window opened at "
        f"{s.t0 - t_start:.1f} s")
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    # nothing of the program may outlive this point: the reference runs
    # beside the weights alone
    stamps = rec.stamps()
    rec.requests.clear()
    del router, engine
    gc.collect()
    log(f"device bytes in use once the program is freed: "
        f"{(devices[0].memory_stats() or {}).get('bytes_in_use', 0)}")

    run = measures.Run(conf=conf, peaks=spec.peaks_for(devices[0].device_kind)
                       if devices[0].platform == "tpu" else {},
                       seconds=seconds, setup_s=setup_s, t0=s.t0,
                       records=s.records, reference=cell.reference)
    out: Dict = {}
    if traced:
        try:
            run.traced = _reduce_trace(s, rec, stamps)
        finally:
            shutil.rmtree(s.trace_dir, ignore_errors=True)
        out["breakdown"] = breakdown(run.traced)
        roof = measures.paged_decode_roofline(run)
        if roof is not None:
            out["roofline_bound"] = roof[1]

    t_check = time.monotonic()
    picked = pick_checked(s.records, seed)
    gaps = served_gaps(conf, cell.reference, params, picked)
    checks = check(conf, s.records, gaps)
    if control:
        ctrl = served_gaps(conf, cell.reference, params, picked,
                           control=True)
        out["program_checks"] = checks
        out["readings"] = {"program": gap_readings(gaps),
                           "control": gap_readings(ctrl)}
        checks = check(conf, s.records, ctrl)
    entries = cell.per_layer if traced else cell.end_to_end
    metrics = {}
    for m in entries:
        v = spec.load_reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    late = [r.sent - r.due for r in s.records if r.sent]
    out.update({
        "correct": passed(checks),
        "attempted": len(s.records),
        "failed": checks["failed_requests"]["value"],
        "metrics": metrics,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "count": len(devices), "memory_peak_bytes": peak},
        "compiles_in_window": counter.count,
        "generator_late_p95_ms": measures.percentile(late, 95) * 1e3
        if late else None,
        "check_s": time.monotonic() - t_check,
        "checks": checks,
    })
    if traced:
        t = run.traced
        devs = trace.devices(t.events)
        busy = [trace.busy_ns(trace.ops(t.events, d), t.window_ns)
                for d in devs]
        out["device"]["busy_s"] = float(np.mean(busy)) * 1e-9 if busy else 0.0
        out["device"]["window_s"] = (t.window_ns[1] - t.window_ns[0]) * 1e-9
    return out


def use_caches() -> None:
    """Keep every compiled artefact in the checkout, at fixed paths, so a
    later run from the same checkout finds what an earlier one compiled and
    two checkouts share nothing: JAX's compilation cache in ``.jax_cache``
    (the program's own default, ``repro.launch.compile_cache``), every
    program however quick to compile, and the e-graph kernel plan in
    ``.jax_cache/egraph``."""
    os.environ["REPRO_CACHE_DIR"] = str(spec.ROOT / ".jax_cache" / "egraph")
    jax.config.update("jax_compilation_cache_dir",
                      str(spec.ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
