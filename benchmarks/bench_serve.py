"""Serving-throughput benchmark: the paged engine on a synthetic
multi-request workload, emitting a ``BENCH_serve.json`` trajectory point.

    PYTHONPATH=src python -m benchmarks.bench_serve --quick \
        --out BENCH_serve.json \
        --baseline benchmarks/baselines/serve.json --max-regress 0.2

Called from ``benchmarks.run`` it yields one CSV row per serving metric; the
CLI additionally writes the JSON point and gates on the committed baseline
(REASONING COMPILER's loop: serving metrics feed back into the compiler's CI,
so a pass that tanks tokens/sec fails the push that introduced it).

The workload is the acceptance scenario from the paged-engine PR: 12 requests
with mixed prompt/output lengths through ``max_batch=4``, which must all
finish, keep pool utilization under 100%, and peak strictly below the dense
``max_batch x max_len`` footprint.

``--mesh N`` measures the mesh-sharded pool instead (fake N-device CPU pod
when real devices are missing): the KV slab is sharded on the kv-heads axis
and the run is verified **token-identical** against an unsharded engine on
the same workload before the point is written.  Sharded points carry
``mesh_devices`` and are a separate trajectory series — the single-device
baseline gate does not apply to them (see benchmarks.aggregate_serve).

``--tp N`` additionally shards the **weights** over the same mesh using the
partition rules Auto Distribution emits (``repro.distributed.param_sharding``):
the point records per-device vs replicated param bytes and the run is gated on
per-device bytes landing at ~1/N of replicated (within a slop for the norms
and router tables that stay replicated).  Decode stays token-identical to the
single-device oracle because the default mode gathers weights at their use
site; the ``REPRO_TP_REDUCE_SCATTER=1`` compute mode is fp32-close rather
than bitwise and its closeness is asserted by tests/test_param_sharding.py,
not by this bench.  TP points default to ``BENCH_serve_tp.json`` and are a
separate trajectory series like ``--mesh`` points.

``--family ssm|hybrid`` serves a stateful model family (``FAMILY_ARCHS``
smoke archs) through the same paged workload: pure-ssm requests keep their
recurrent state in the StateSlab tier (zero KV blocks — gated), hybrids
carry the mixed layout (KV blocks + slab slots).  One preemption-by-swap is
forced mid-decode and every output is verified token-identical against the
family's dense prefill+decode oracle.  Family points default to
``BENCH_serve_<family>.json`` and are a separate trajectory series — the
transformer ratchet does not apply (prefix sharing is structurally off for
stateful families, so the reuse gates would be meaningless).

``--open-loop`` measures **latency under load** instead of closed-loop
throughput: an in-process OpenAI gateway (``repro.serve.gateway``) is booted
on an ephemeral port and a Poisson client fires the same workload at it at
``--qps`` arrivals/sec over real HTTP + SSE, recording per-request TTFT
(first streamed token) and per-token inter-token latency.  The point goes to
``BENCH_latency.json`` (p50/p99 TTFT and ITL, delivered tokens/sec) and the
``--baseline`` gate becomes an SLO ceiling check against
``benchmarks/baselines/latency.json`` — open-loop points are a separate
trajectory series; they never touch the throughput ratchet.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from typing import List, Optional, Tuple

WORKLOAD_REQUESTS = 12
MAX_BATCH = 4
MAX_LEN = 64
BLOCK_SIZE = 8

# the --family lane: one representative arch per stateful model family,
# served through the SAME engine/workload as the transformer lane and
# verified token-identical against the family's dense (unpaged) oracle
FAMILY_ARCHS = {
    "transformer": "qwen3-0.6b",
    "ssm": "falcon-mamba-7b",        # pure Mamba: StateSlab only, no KV
    "hybrid": "zamba2-2.7b",         # mixed layout: KV blocks + slab slots
}


def _knob_mesh_devices() -> int:
    """Effective REPRO_SERVE_MESH width (0 = off).  The bench resolves the
    knob itself so knob-sharded runs get the same kv-head widening and the
    same forced-single-device reference engine as --mesh runs."""
    import os
    knob = os.environ.get("REPRO_SERVE_MESH", "0")
    if knob in ("", "0", "off"):
        return 0
    if knob == "auto":
        import jax
        return len(jax.devices())
    return int(knob)


def _smoke_cfg(mesh_devices: int = 0, arch: str = "qwen3-0.6b"):
    """The bench arch.  A sharded run needs kv-heads divisible by the mesh:
    the qwen3 smoke config's GQA kv=2 is widened to the lcm (an explicitly
    different arch — which is why sharded points are a separate series)."""
    import dataclasses

    from repro.configs.base import get_config, reduced_config

    cfg = reduced_config(get_config(arch))
    if mesh_devices and cfg.n_kv_heads % mesh_devices:
        kv = math.lcm(cfg.n_kv_heads, mesh_devices)
        assert cfg.n_heads % kv == 0, \
            f"can't widen kv heads to {kv} under {cfg.n_heads} q heads"
        cfg = dataclasses.replace(cfg, n_kv_heads=kv)
    return cfg


def _build_engine(mesh_devices: int = 0, params=None, sharded: bool = True,
                  tp: bool = False, arch: str = "qwen3-0.6b",
                  **engine_kwargs):
    import jax

    from repro.models import build_model
    from repro.serve.engine import ServeEngine

    # the reference engine passes mesh=False so the token-identity oracle
    # can never be silently sharded by ambient env; run_workload resolves
    # REPRO_SERVE_MESH into an explicit mesh_devices before calling here,
    # so mesh=None (knob passthrough) only remains for direct callers
    mesh = False if not sharded else None
    if mesh_devices and sharded:
        from repro.launch.mesh import make_serve_mesh
        mesh = make_serve_mesh(mesh_devices)
    cfg = _smoke_cfg(mesh_devices, arch)
    fns = build_model(cfg)
    if params is None:
        params = fns.init(jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_batch=MAX_BATCH, max_len=MAX_LEN,
                      block_size=BLOCK_SIZE, mesh=mesh,
                      tp=True if (tp and sharded) else None,
                      **engine_kwargs)
    return cfg, eng, params


def _workload(cfg, n: int, seed: int = 0) -> List:
    """Mixed prompt lengths (3..20) and output lengths (4..14).  Every third
    request opens with a common 9-token prefix (a shared system prompt in
    miniature) so the tiered KVStore's prefix sharing / copy-on-write path is
    exercised by the measured run, not just by unit tests."""
    import numpy as np

    from repro.serve.engine import Request, SamplingParams

    rng = np.random.default_rng(seed)
    shared_prefix = rng.integers(1, cfg.vocab, size=9).tolist()
    reqs = []
    for i in range(n):
        plen = int(rng.integers(3, 21))
        max_new = int(rng.integers(4, 15))
        prompt = rng.integers(1, cfg.vocab, size=plen).tolist()
        if i % 3 == 0:
            prompt = (shared_prefix + prompt)[:20]
        sp = SamplingParams() if i % 3 else \
            SamplingParams(temperature=0.8, top_k=40, seed=i)
        reqs.append(Request(rid=i, prompt=prompt, max_new=max_new, sampling=sp))
    return reqs


def run_workload(quick: bool = False, mesh_devices: int = 0,
                 verify_identical: Optional[bool] = None,
                 tp: bool = False) -> Tuple[object, dict]:
    """Returns (ServeMetrics, workload descriptor).  ``quick`` is the CI
    smoke size; the full run pushes 3x the requests through the same pool so
    queueing/admission actually bites.  ``mesh_devices`` > 1 shards the KV
    pool; ``tp`` additionally shards the weights over the same mesh (rule-
    driven, see repro.distributed.param_sharding); ``verify_identical``
    replays the workload on a forced-unsharded engine (same params) and
    records whether outputs matched token-for-token — its default (None)
    means "whenever the engine's *effective* mesh is sharded", which also
    covers runs sharded by REPRO_SERVE_MESH rather than the --mesh flag.
    Exception: under REPRO_TP_REDUCE_SCATTER=1 compute follows the sharded
    layout and is only fp32-close, so identity is not asserted by default
    (tests/test_param_sharding.py owns the closeness check)."""
    # resolve the knob into an explicit width up front, so knob-sharded runs
    # get the widened smoke arch AND a matching-arch reference engine
    mesh_devices = mesh_devices or _knob_mesh_devices()
    cfg, eng, params = _build_engine(mesh_devices, tp=tp)
    n = WORKLOAD_REQUESTS if quick else 3 * WORKLOAD_REQUESTS

    # warm the prefill/decode jit caches outside the measured window (and
    # drop any prefixes it retained — the measured run starts cache-cold)
    for r in _workload(cfg, 2, seed=99):
        eng.submit(r)
    eng.run_until_done()
    eng.release_prefix_cache()
    eng.reset_metrics()

    reqs = _workload(cfg, n)
    for r in reqs:
        eng.submit(r)
    finished = eng.run_until_done()
    m = eng.metrics()
    desc = {
        "requests": n,
        "finished": len(finished),
        "max_batch": MAX_BATCH,
        "max_len": MAX_LEN,
        "block_size": BLOCK_SIZE,
        "arch": cfg.name,
        "quick": quick,
        "mesh_devices": m.mesh_devices,
        # a 1-device mesh still runs the shard_map configuration (CPU
        # dispatch overhead and all): it must skip the single-device gate
        # even though its width puts it in the single-device table series
        "sharded": eng.mesh is not None,
        "tp_devices": m.tp_devices,
        "param_bytes_per_device": m.param_bytes_per_device,
        "param_bytes_replicated": m.param_bytes_replicated,
    }
    if verify_identical is None:
        from repro.perf import perf
        verify_identical = m.mesh_devices > 1 and \
            not (eng.tp and perf().tp_reduce_scatter)
    if verify_identical:
        _, ref_eng, _ = _build_engine(mesh_devices, params=params,
                                      sharded=False)
        ref = _workload(cfg, n)
        for r in ref:
            ref_eng.submit(r)
        ref_eng.run_until_done()
        desc["token_identical"] = all(
            a.out == b.out for a, b in zip(reqs, ref))
    return m, desc


# ---------------------------------------------------------------------------
# Model-family lane: SSM / hybrid archs through the same paged engine
# ---------------------------------------------------------------------------


def _family_oracle(cfg, fns, params, req, max_len: int) -> List[int]:
    """The family's dense reference: whole-prompt ``prefill`` + per-token
    ``decode_step`` on an unpaged cache, sampled with the engine's own
    stateless sampler — what the paged run must match token-for-token."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serve.engine import ServeEngine

    cache, logits = fns.prefill(
        params, {"tokens": jnp.asarray([req.prompt], jnp.int32)})
    if cfg.family != "ssm":
        # grow the prompt-sized KV planes to max_len before decoding:
        # decode_step writes at cur_len, which would clamp against a
        # prompt-length cache and corrupt the final KV entry.  Pure-ssm
        # caches are fixed-size recurrent state — nothing to grow.
        def embed(small, big):
            if small.shape == big.shape:
                return small.astype(big.dtype)
            for ax in range(small.ndim):
                if small.shape[ax] != big.shape[ax]:
                    return jax.lax.dynamic_update_slice_in_dim(
                        big, small.astype(big.dtype), 0, axis=ax)
            return small
        cache = jax.tree.map(embed, cache, fns.make_cache(1, max_len))
    out = [ServeEngine._sample(np.asarray(logits[0]), req.sampling, 0)]
    cur = len(req.prompt)
    for _ in range(req.max_new - 1):
        batch = {"token": jnp.asarray([[out[-1]]], jnp.int32)}
        if cfg.family != "ssm":
            batch["cur_len"] = jnp.int32(cur)
        cache, lg = fns.decode_step(params, cache, batch)
        out.append(ServeEngine._sample(np.asarray(lg[0]), req.sampling,
                                       len(out)))
        cur += 1
    return out


def run_family_workload(family: str, quick: bool = False
                        ) -> Tuple[object, dict]:
    """The transformer lane's mixed workload served through a stateful-family
    arch (``FAMILY_ARCHS``), with one preemption-by-swap forced mid-decode so
    the measured run provably crosses the slab park/restore path, then every
    output verified token-identical against the family's dense oracle.

    Single-device by construction: stateful families refuse a mesh (the slab
    is not sharded), and ``sharded=False`` keeps ambient REPRO_SERVE_MESH
    from breaking the lane."""
    arch = FAMILY_ARCHS[family]
    cfg, eng, params = _build_engine(0, sharded=False, arch=arch)
    n = WORKLOAD_REQUESTS if quick else 3 * WORKLOAD_REQUESTS

    # warm the prefill/decode jit caches outside the measured window
    for r in _workload(cfg, 2, seed=99):
        eng.submit(r)
    eng.run_until_done()
    eng.release_prefix_cache()
    eng.reset_metrics()

    reqs = _workload(cfg, n)
    for r in reqs:
        eng.submit(r)
    # drive the loop by hand: once some request is mid-generation, park the
    # one with the most tokens out (state slab + any KV blocks to the host
    # tier) — it must resume and finish without changing a token
    forced = False
    while eng.step():
        if forced or not eng.swap_enabled:
            continue
        live = [s for s in eng.slots if s is not None]
        mid = [s for s in live if len(s.req.out) >= 2]
        if mid:
            eng._requeue(max(mid, key=lambda s: len(s.req.out)))
            forced = True
    finished = eng.run_until_done()
    m = eng.metrics()

    from repro.models import build_model
    fns = build_model(cfg)
    identical = all(r.out == _family_oracle(cfg, fns, params, r, MAX_LEN)
                    for r in reqs)
    desc = {
        "requests": n,
        "finished": len(finished),
        "max_batch": MAX_BATCH,
        "max_len": MAX_LEN,
        "block_size": BLOCK_SIZE,
        "arch": cfg.name,
        "family": family,
        "quick": quick,
        "mesh_devices": m.mesh_devices,
        "sharded": False,
        "tp_devices": m.tp_devices,
        "token_identical": identical,
        "forced_preemption": forced,
        "state_slots_peak": (eng.state_store.device.pool.peak_used
                             if eng.state_store is not None else 0),
    }
    return m, desc


def check_family(m, desc) -> List[str]:
    """The SSM/hybrid serving PR's acceptance assertions: the stateful
    families complete the same workload, match their dense oracle across a
    forced preemption-by-swap, and prove their memory layout (no KV blocks
    for pure ssm, below-dense KV for hybrid, slab slots actually used).
    Prefix-reuse gates do NOT apply: sharing is structurally off for
    stateful families (recurrent state summarizes the whole prefix)."""
    errs = []
    if desc["finished"] != desc["requests"]:
        errs.append(f"only {desc['finished']}/{desc['requests']} finished")
    if not desc["token_identical"]:
        errs.append(f"{desc['family']} paged run NOT token-identical to its "
                    "dense oracle")
    if not m.tokens_per_sec > 0:
        errs.append("tokens_per_sec not positive")
    if not m.ttft_mean_s > 0:
        errs.append("ttft not recorded")
    if desc["forced_preemption"]:
        if not m.preemptions >= 1:
            errs.append("forced preemption not recorded")
        if not (m.swap_out_blocks >= 1 and m.swap_in_blocks >= 1):
            errs.append("preemption never crossed the swap tier "
                        f"({m.swap_out_blocks} out/{m.swap_in_blocks} in)")
    if desc["state_slots_peak"] < 1:
        errs.append("no state-slab slot was ever allocated for a stateful "
                    "family")
    if desc["family"] == "ssm":
        if m.peak_blocks_used != 0:
            errs.append(f"pure-ssm run allocated {m.peak_blocks_used} KV "
                        "blocks (state must live in the slab, not the pool)")
    elif not m.peak_blocks_used < m.dense_equiv_blocks:
        errs.append(f"hybrid peak blocks {m.peak_blocks_used} not below "
                    f"dense footprint {m.dense_equiv_blocks}")
    return errs


def family_main(quick: bool = False):
    """benchmarks.run entry for the ssm lane: every stateful family in the
    zoo through the paged engine, one row per family headline."""
    for family in ("ssm", "hybrid"):
        m, desc = run_family_workload(family, quick=quick)
        errs = check_family(m, desc)
        if errs:
            raise RuntimeError(f"{family}: " + "; ".join(errs))
        us_per_tok = 1e6 / max(m.tokens_per_sec, 1e-9)
        yield (f"serve_{family}_decode", f"{us_per_tok:.1f}",
               f"{desc['arch']}: {m.tokens_per_sec:.1f} tok/s over "
               f"{desc['requests']} reqs, dense-oracle "
               f"{'OK' if desc['token_identical'] else 'MISMATCH'}")
        yield (f"serve_{family}_state", f"{desc['state_slots_peak']}",
               f"peak slab slots; KV peak {m.peak_blocks_used}/"
               f"{m.pool_blocks} blocks, {m.preemptions} preemptions "
               f"({m.swap_out_blocks} out / {m.swap_in_blocks} in)")


# ---------------------------------------------------------------------------
# Open-loop latency: Poisson arrivals over HTTP/SSE against a live gateway
# ---------------------------------------------------------------------------

OPEN_LOOP_QPS = 8.0
OPEN_LOOP_REQUESTS = 16      # --quick; the full run triples it


async def _sse_request(host: str, port: int, payload: dict):
    """One streamed /v1/completions over a raw socket.  Returns
    (ttft_s, itl_samples_s, n_tokens, finish_reason, wall_s) — timing is
    measured from the moment the request bytes are flushed, so TTFT includes
    the gateway's queueing + admission + prefill, exactly what a caller
    sees.  A load-shed 429/503 comes back as finish ``"shed"`` (any other
    non-200 as ``"http_<status>"``) so open-loop accounting can tell
    refused work from completed work."""
    import asyncio
    import json as _json

    body = _json.dumps(payload).encode("utf-8")
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write(
            b"POST /v1/completions HTTP/1.1\r\nHost: bench\r\n"
            b"Content-Type: application/json\r\n"
            + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        await writer.drain()
        t0 = time.monotonic()
        status_line = await reader.readline()        # HTTP/1.1 <code> ...
        parts = status_line.split()
        status = int(parts[1]) if len(parts) > 1 else 0
        await reader.readuntil(b"\r\n\r\n")          # rest of the headers
        if status != 200:
            finish = "shed" if status in (429, 503) else f"http_{status}"
            return None, [], 0, finish, time.monotonic() - t0
        ttft = None
        stamps = []
        n_tokens = 0
        finish = ""
        while True:
            line = await reader.readline()
            if not line:
                break
            if not line.startswith(b"data: "):
                continue
            data = line[len(b"data: "):].strip()
            if data == b"[DONE]":
                break
            chunk = _json.loads(data)
            if "error" in chunk:
                finish = f"error: {chunk['error']['message']}"
                break
            choice = chunk["choices"][0]
            ids = choice.get("token_ids") or []
            if ids:
                now = time.monotonic()
                if ttft is None:
                    ttft = now - t0
                stamps.extend([now] * len(ids))
                n_tokens += len(ids)
            if choice.get("finish_reason"):
                finish = choice["finish_reason"]
        itls = [b - a for a, b in zip(stamps, stamps[1:])]
        return ttft, itls, n_tokens, finish, time.monotonic() - t0
    finally:
        writer.close()
        try:
            await writer.wait_closed()
        except (ConnectionError, RuntimeError):
            pass


def run_open_loop(quick: bool = False, qps: float = OPEN_LOOP_QPS,
                  n_requests: int = 0, seed: int = 0,
                  deadline_ms: float = 0.0) -> dict:
    """Boot the gateway in-process, replay the serve workload as Poisson
    arrivals at ``qps``, and return a BENCH_latency.json point.

    ``deadline_ms`` > 0 attaches a per-request ``timeout`` so the engine's
    deadline reaper is part of the measured system; the point then reports
    **goodput** (tokens of requests that completed within their deadline)
    alongside raw delivered throughput, plus shed/expired/errored tallies."""
    import asyncio

    import numpy as np

    from repro.serve.async_engine import AsyncServeEngine
    from repro.serve.gateway import (ByteTokenizer, Gateway, GatewayModel,
                                     Router)

    n = n_requests or (OPEN_LOOP_REQUESTS if quick else 3 * OPEN_LOOP_REQUESTS)
    cfg, eng, params = _build_engine(0)
    model = GatewayModel(model_id=cfg.name,
                         async_engine=AsyncServeEngine(eng, model_id=cfg.name),
                         tokenizer=ByteTokenizer(cfg.vocab))

    reqs = _workload(cfg, n, seed=seed)
    rng = np.random.default_rng(seed + 1)
    arrivals = np.cumsum(rng.exponential(1.0 / qps, size=n))

    async def drive():
        async with Gateway(Router([model]), port=0) as gw:
            # warm the jit caches through the same HTTP path, then drop the
            # retained prefixes so the measured window starts cache-cold
            for r in _workload(cfg, 2, seed=99):
                await _sse_request(gw.host, gw.port, {
                    "model": cfg.name, "prompt": r.prompt,
                    "max_tokens": r.max_new, "stream": True})
            eng.release_prefix_cache()

            t_start = time.monotonic()

            async def one(i):
                await asyncio.sleep(float(arrivals[i]))
                r = reqs[i]
                sp = r.sampling
                payload = {
                    "model": cfg.name, "prompt": r.prompt,
                    "max_tokens": r.max_new, "stream": True,
                    "temperature": sp.temperature, "top_k": sp.top_k,
                    "seed": sp.seed}
                if deadline_ms > 0:
                    payload["timeout"] = deadline_ms / 1e3
                return await _sse_request(gw.host, gw.port, payload)

            results = await asyncio.gather(*[one(i) for i in range(n)])
            wall = time.monotonic() - t_start
            return results, wall

    results, wall = asyncio.run(drive())
    ttfts = [r[0] for r in results if r[0] is not None]
    itls = [x for r in results for x in r[1]]
    total_tokens = sum(r[2] for r in results)
    completed = sum(1 for r in results if r[3] in ("stop", "length"))
    shed = sum(1 for r in results if r[3] == "shed")
    expired = sum(1 for r in results if r[3] == "expired")
    errored = sum(1 for r in results
                  if r[3].startswith(("error", "http_")))
    # goodput: only tokens of requests that actually completed count —
    # work burned on expired/errored streams is throughput, not goodput
    good_tokens = sum(r[2] for r in results if r[3] in ("stop", "length"))

    def pct(xs, q):
        return float(np.percentile(xs, q)) if xs else 0.0

    return {
        "bench": "serve_latency",
        "open_loop": True,
        "unix_time": time.time(),
        "qps": qps,
        "requests": n,
        "completed": completed,
        "requests_shed": shed,
        "requests_expired": expired,
        "requests_errored": errored,
        "deadline_ms": deadline_ms,
        "mesh_devices": 1,
        "workload": {"requests": n, "max_batch": MAX_BATCH,
                     "max_len": MAX_LEN, "block_size": BLOCK_SIZE,
                     "arch": cfg.name, "quick": quick, "qps": qps},
        "wall_s": wall,
        "tokens_per_sec": total_tokens / wall if wall > 0 else 0.0,
        "goodput_tokens_per_sec": good_tokens / wall if wall > 0 else 0.0,
        "ttft_mean_s": float(np.mean(ttfts)) if ttfts else 0.0,
        "ttft_p50_ms": pct(ttfts, 50) * 1e3,
        "ttft_p99_ms": pct(ttfts, 99) * 1e3,
        "itl_mean_s": float(np.mean(itls)) if itls else 0.0,
        "itl_p50_ms": pct(itls, 50) * 1e3,
        "itl_p99_ms": pct(itls, 99) * 1e3,
    }


# ---------------------------------------------------------------------------
# Multi-LoRA: N tenants over one shared paged base, through the live gateway
# ---------------------------------------------------------------------------

MULTILORA_TENANTS = ["tenant-a", "tenant-b", "tenant-c", "tenant-d"]


async def _sse_collect(host: str, port: int, payload: dict):
    """One streamed /v1/completions, returning (token_ids, model_tag,
    finish_reason) — the multi-LoRA lane checks *which tenant* answered a
    stream, not just how fast."""
    import asyncio
    import json as _json

    body = _json.dumps(payload).encode("utf-8")
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write(
            b"POST /v1/completions HTTP/1.1\r\nHost: bench\r\n"
            b"Content-Type: application/json\r\n"
            + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        await writer.drain()
        status_line = await reader.readline()
        parts = status_line.split()
        status = int(parts[1]) if len(parts) > 1 else 0
        await reader.readuntil(b"\r\n\r\n")
        if status != 200:
            return [], "", f"http_{status}"
        ids: List[int] = []
        model_tag = ""
        finish = ""
        while True:
            line = await reader.readline()
            if not line:
                break
            if not line.startswith(b"data: "):
                continue
            data = line[len(b"data: "):].strip()
            if data == b"[DONE]":
                break
            chunk = _json.loads(data)
            if "error" in chunk:
                finish = f"error: {chunk['error']['message']}"
                break
            model_tag = chunk.get("model", model_tag)
            choice = chunk["choices"][0]
            ids.extend(choice.get("token_ids") or [])
            if choice.get("finish_reason"):
                finish = choice["finish_reason"]
        return ids, model_tag, finish
    finally:
        writer.close()
        try:
            await writer.wait_closed()
        except (ConnectionError, RuntimeError):
            pass


def run_multilora(quick: bool = False, seed: int = 0) -> dict:
    """Mixed-tenant serving through a live gateway: 4 LoRA tenants + the
    base model multiplexed over ONE engine, ONE block pool, ONE set of base
    weights.  Returns a ``BENCH_multilora.json`` point.

    The workload is adversarial for isolation: every tenant asks the SAME
    prompt (greedy), so any cross-tenant KV leak is observable.

    * phase 1 — one identical-prompt request per tenant, empty prefix
      registry: any prefix adoption here would necessarily be cross-tenant,
      so the gate is ``re_prefill_avoided == 0``;
    * phase 2 — the same five asks again: now each tenant owns a registered
      prefix in its own namespace, so reuse MUST happen
      (``re_prefill_avoided > 0``) and every stream must still be
      token-identical to its phase-1 run;
    * oracle — each tenant's stream is replayed on a fresh single-tenant
      reference engine (same params, same adapter name -> same
      deterministic factors) and must match token-for-token;
    * throughput — a mixed 5-way workload is timed against a base-only
      workload of the same size on the same engine (ratio recorded, loose
      floor gated: per-row adapter gathers must not crater decode).
    """
    import asyncio

    from repro.serve.async_engine import AsyncServeEngine
    from repro.serve.gateway import (ByteTokenizer, Gateway, GatewayModel,
                                     Router)

    import numpy as np

    # multi-LoRA is single-device (the engine refuses adapters on a mesh);
    # sharded=False keeps ambient REPRO_SERVE_MESH from breaking the lane.
    # The pool gets explicit registry headroom: conservative admission
    # reserves max_blocks_per_seq per slot, and the default pool is sized
    # exactly to those reservations — phase 2's adoption gate needs the 5
    # per-tenant prefix entries (2 blocks each) to SURVIVE a full batch.
    n_prefix = (len(MULTILORA_TENANTS) + 1) * ((16 + BLOCK_SIZE - 1)
                                               // BLOCK_SIZE)
    cfg, eng, params = _build_engine(
        0, sharded=False,
        num_blocks=MAX_BATCH * (MAX_LEN // BLOCK_SIZE) + n_prefix + 1,
        prefix_cache_blocks=n_prefix)
    model = GatewayModel(
        model_id=cfg.name,
        async_engine=AsyncServeEngine(eng, model_id=cfg.name),
        tokenizer=ByteTokenizer(cfg.vocab),
        adapters=list(MULTILORA_TENANTS))

    rng = np.random.default_rng(seed)
    prompt = rng.integers(1, cfg.vocab, size=16).tolist()
    max_new = 8 if quick else 12
    asks = [None] + list(MULTILORA_TENANTS)      # base + 4 tenants

    def tag(t):
        return cfg.name if t is None else f"{cfg.name}:{t}"

    async def drive():
        async with Gateway(Router([model]), port=0) as gw:
            async def ask_one(t):
                return await _sse_collect(gw.host, gw.port, {
                    "model": tag(t), "prompt": prompt,
                    "max_tokens": max_new, "stream": True})

            # warm the jit caches (base and lora graphs), then reset
            await ask_one(None)
            await ask_one(MULTILORA_TENANTS[0])
            eng.release_prefix_cache()
            eng.reset_metrics()

            # phase 1: identical prompt, one request per tenant, cold
            # registry — any prefix hit would be cross-tenant
            phase1 = await asyncio.gather(*[ask_one(t) for t in asks])
            cross_tenant_hits = eng.metrics().re_prefill_avoided

            # phase 2: same asks again — now reuse must happen, within
            # namespace only, without changing a single token
            phase2 = await asyncio.gather(*[ask_one(t) for t in asks])
            reuse_tokens = eng.metrics().re_prefill_avoided

            # throughput: mixed 5-tenant round-robin vs base-only, same
            # size, same engine (prefixes dropped so neither is favored)
            n_tput = 10 if quick else 20
            eng.release_prefix_cache()

            async def timed(tenants):
                t0 = time.monotonic()
                rs = await asyncio.gather(*[
                    ask_one(tenants[i % len(tenants)]) for i in range(n_tput)])
                toks = sum(len(r[0]) for r in rs)
                return toks / max(time.monotonic() - t0, 1e-9)

            base_tps = await timed([None])
            eng.release_prefix_cache()
            mixed_tps = await timed(asks)
            return phase1, phase2, cross_tenant_hits, reuse_tokens, \
                base_tps, mixed_tps

    phase1, phase2, cross_hits, reuse_tokens, base_tps, mixed_tps = \
        asyncio.run(drive())

    # oracle: replay each tenant on a fresh single-tenant reference engine
    from repro.serve.engine import Request
    oracle_match = {}
    for t, (ids, _, _) in zip(asks, phase1):
        _, ref, _ = _build_engine(0, params=params, sharded=False)
        if t is not None:
            ref.load_adapter(t)
        r = Request(rid=0, prompt=list(prompt), max_new=max_new, adapter_id=t)
        ref.submit(r)
        ref.run_until_done()
        oracle_match[t or "base"] = (r.out == ids)

    m = eng.metrics()
    am = eng.adapters.metrics()
    slab_cap_bytes = eng.adapters.per_adapter_bytes() \
        * eng.adapters.max_adapters
    distinct = len({tuple(ids) for ids, _, _ in phase1})
    return {
        "bench": "multilora",
        "unix_time": time.time(),
        "quick": quick,
        "tenants": len(MULTILORA_TENANTS),
        "workload": {"arch": cfg.name, "prompt_tokens": len(prompt),
                     "max_new": max_new, "max_batch": MAX_BATCH,
                     "block_size": BLOCK_SIZE},
        "model_tags_ok": all(mt == tag(t)
                             for t, (_, mt, _) in zip(asks, phase1)),
        "streams_completed": all(f == "length"
                                 for _, _, f in phase1 + phase2),
        "distinct_streams": distinct,
        "cross_tenant_prefix_hits": int(cross_hits),
        "within_tenant_reuse_tokens": int(reuse_tokens - cross_hits),
        "phase2_token_identical": all(
            a[0] == b[0] for a, b in zip(phase1, phase2)),
        "oracle_match": oracle_match,
        "per_tenant": m.per_tenant,
        "adapters_loaded": am["adapters_loaded"],
        "adapter_device_bytes": am["adapter_device_bytes"],
        "adapter_host_bytes": am["adapter_host_bytes"],
        "adapter_slab_cap_bytes": slab_cap_bytes,
        "base_tokens_per_sec": base_tps,
        "mixed_tokens_per_sec": mixed_tps,
        "mixed_vs_base_ratio": mixed_tps / max(base_tps, 1e-9),
    }


def check_multilora(point: dict) -> List[str]:
    """The multi-LoRA PR's acceptance assertions, gated by the
    ``multilora-smoke`` CI lane."""
    errs = []
    if not point["model_tags_ok"]:
        errs.append("a stream's model tag did not echo the asked tenant")
    if not point["streams_completed"]:
        errs.append("not every tenant stream ran to completion")
    # base + 4 tenants with distinct adapters must produce distinct streams
    want = point["tenants"] + 1
    if point["distinct_streams"] != want:
        errs.append(f"only {point['distinct_streams']}/{want} distinct "
                    "streams for an identical prompt across tenants "
                    "(adapters not actually applied, or leaking)")
    if point["cross_tenant_prefix_hits"] != 0:
        errs.append(f"{point['cross_tenant_prefix_hits']} prefill tokens "
                    "adopted across tenant namespaces (KV isolation broken)")
    if not point["within_tenant_reuse_tokens"] > 0:
        errs.append("no within-tenant prefix reuse on repeated prompts "
                    "(namespacing is over-isolating)")
    if not point["phase2_token_identical"]:
        errs.append("prefix-reusing rerun changed tokens")
    bad = [t for t, ok in point["oracle_match"].items() if not ok]
    if bad:
        errs.append(f"streams diverged from single-tenant oracle: {bad}")
    if point["adapter_device_bytes"] > point["adapter_slab_cap_bytes"]:
        errs.append(f"adapter slab {point['adapter_device_bytes']}B exceeds "
                    f"its cap {point['adapter_slab_cap_bytes']}B")
    if point["adapters_loaded"] > point["tenants"]:
        errs.append(f"{point['adapters_loaded']} adapters resident for "
                    f"{point['tenants']} tenants")
    # per-row gathers cost something, but multiplexing must not crater the
    # shared engine (generous floor: CPU interpret-mode kernels + CI noise)
    if point["mixed_vs_base_ratio"] < 0.15:
        errs.append(f"mixed-tenant throughput is only "
                    f"{point['mixed_vs_base_ratio']:.1%} of base-only")
    return errs


def multilora_main(quick: bool = False):
    """benchmarks.run entry for the multi-LoRA lane: one row per isolation/
    cost headline, gated on the acceptance assertions."""
    point = run_multilora(quick=quick)
    errs = check_multilora(point)
    if errs:
        raise RuntimeError("; ".join(errs))
    yield ("multilora_isolation", f"{point['cross_tenant_prefix_hits']}",
           f"cross-tenant prefix hits over {point['tenants']} tenants "
           f"({point['within_tenant_reuse_tokens']} within-tenant reuse)")
    yield ("multilora_slab_mb",
           f"{point['adapter_device_bytes'] / 1e6:.2f}",
           f"{point['adapters_loaded']} adapters resident "
           f"(cap {point['adapter_slab_cap_bytes'] / 1e6:.2f} MB)")
    yield ("multilora_tput_ratio", f"{point['mixed_vs_base_ratio']:.3f}",
           f"mixed {point['mixed_tokens_per_sec']:.1f} vs base "
           f"{point['base_tokens_per_sec']:.1f} tok/s on one engine")


def check_latency(point: dict, baseline: Optional[dict] = None,
                  faulty: bool = False) -> List[str]:
    """Open-loop acceptance: everything reached a terminal outcome, latency
    was recorded, and the committed SLO ceilings (when given) held.
    ``faulty`` relaxes the all-completed check to all-*terminal* — under
    injected faults or tight deadlines some requests legitimately end shed/
    expired/errored, but none may vanish."""
    errs = []
    terminal = point["completed"] + point.get("requests_shed", 0) \
        + point.get("requests_expired", 0) + point.get("requests_errored", 0)
    if terminal != point["requests"]:
        errs.append(f"only {terminal}/{point['requests']} open-loop "
                    "requests reached a terminal outcome")
    if not faulty and point["completed"] != point["requests"]:
        errs.append(f"only {point['completed']}/{point['requests']} "
                    "open-loop requests completed")
    if faulty and point["completed"] == 0:
        errs.append("no open-loop request completed under faults "
                    "(zero goodput)")
    if not point["ttft_p50_ms"] > 0:
        errs.append("no TTFT samples recorded")
    if point["requests"] > 1 and not point["itl_p50_ms"] > 0:
        errs.append("no inter-token latency samples recorded")
    if baseline:
        for key in ("ttft_p99_ms", "itl_p99_ms"):
            ceil = baseline.get(key)
            if ceil is not None and point[key] > ceil:
                errs.append(f"SLO violation: {key} {point[key]:.1f}ms "
                            f"above ceiling {ceil:.1f}ms")
    return errs


def latency_main(quick: bool = False):
    """benchmarks.run entry for the open-loop lane: one row per percentile,
    gated on the committed SLO ceilings."""
    import json as _json
    import os
    point = run_open_loop(quick=quick)
    base_path = os.path.join(os.path.dirname(__file__), "baselines",
                             "latency.json")
    baseline = None
    if os.path.exists(base_path):
        with open(base_path) as f:
            baseline = _json.load(f)
    errs = check_latency(point, baseline)
    if errs:
        raise RuntimeError("; ".join(errs))
    yield ("serve_ttft_p50", f"{point['ttft_p50_ms'] * 1e3:.0f}",
           f"open-loop @ {point['qps']:g} qps; p99 "
           f"{point['ttft_p99_ms']:.1f}ms")
    yield ("serve_ttft_p99", f"{point['ttft_p99_ms'] * 1e3:.0f}",
           f"time-to-first-token p99 over {point['requests']} reqs")
    yield ("serve_itl_p50", f"{point['itl_p50_ms'] * 1e3:.0f}",
           f"inter-token latency; p99 {point['itl_p99_ms']:.1f}ms")
    yield ("serve_open_loop_tput", f"{1e6 / max(point['tokens_per_sec'], 1e-9):.1f}",
           f"{point['tokens_per_sec']:.1f} delivered tok/s under open loop")


def main(quick: bool = False):
    """benchmarks.run entry: one row per headline serving metric."""
    m, desc = run_workload(quick)
    if desc["finished"] != desc["requests"]:
        raise RuntimeError(
            f"serve workload incomplete: {desc['finished']}/{desc['requests']}")
    us_per_tok = 1e6 / max(m.tokens_per_sec, 1e-9)
    yield ("serve_paged_decode", f"{us_per_tok:.1f}",
           f"{m.tokens_per_sec:.1f} tok/s over {desc['requests']} reqs")
    yield ("serve_paged_ttft", f"{m.ttft_mean_s * 1e6:.0f}",
           f"mean time-to-first-token; max {m.ttft_max_s * 1e3:.0f}ms")
    yield ("serve_paged_pool", f"{m.peak_pool_utilization:.3f}",
           f"peak {m.peak_blocks_used}/{m.pool_blocks} blocks "
           f"(dense equiv {m.dense_equiv_blocks})")
    yield ("serve_prefix_reuse", f"{m.re_prefill_avoided}",
           f"prompt tokens not re-prefilled; {m.shared_blocks} shared / "
           f"{m.cow_copies} CoW blocks")
    yield ("serve_swap_traffic", f"{m.swap_out_blocks + m.swap_in_blocks}",
           f"host-tier blocks: {m.swap_out_blocks} out / "
           f"{m.swap_in_blocks} in ({m.preemptions} preemptions)")


def _check(m, desc) -> List[str]:
    """The PR's acceptance assertions, enforced on every bench run."""
    errs = []
    if desc["finished"] != desc["requests"]:
        errs.append(f"only {desc['finished']}/{desc['requests']} finished")
    if desc.get("token_identical") is False:
        errs.append("sharded run NOT token-identical to single-device run")
    if not m.tokens_per_sec > 0:
        errs.append("tokens_per_sec not positive")
    if not m.ttft_mean_s > 0:
        errs.append("ttft not recorded")
    if not m.peak_pool_utilization < 1.0:
        errs.append(f"pool peaked at {m.peak_pool_utilization:.0%} (expected <100%)")
    if not m.peak_blocks_used < m.dense_equiv_blocks:
        errs.append(f"peak blocks {m.peak_blocks_used} not below dense "
                    f"footprint {m.dense_equiv_blocks}")
    if not m.re_prefill_avoided > 0:
        errs.append("prefix sharing saved no prefill tokens on a workload "
                    "with shared prompt prefixes")
    tp_n = desc.get("tp_devices", 1)
    if tp_n > 1:
        # the PR's memory acceptance: sharding must actually shrink the
        # per-device footprint to ~1/N (+5pt slop for the replicated norms,
        # router tables and any fallback-replicated weights)
        per_dev = desc.get("param_bytes_per_device", 0)
        total = desc.get("param_bytes_replicated", 0)
        ratio = per_dev / total if total else 1.0
        ceiling = 1.0 / tp_n + 0.05
        if not 0 < ratio <= ceiling:
            errs.append(f"TP x{tp_n} per-device param bytes {per_dev} are "
                        f"{ratio:.1%} of replicated {total} "
                        f"(ceiling {ceiling:.1%})")
    return errs


def cli() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--baseline", default="")
    ap.add_argument("--max-regress", type=float, default=0.2,
                    help="fail if tokens/sec drops more than this fraction "
                         "below the committed baseline")
    ap.add_argument("--mesh", type=int, default=0,
                    help="shard the KV pool over this many devices (a CPU "
                         "fake pod under JAX_PLATFORMS=cpu); the run is verified "
                         "token-identical against an unsharded engine")
    ap.add_argument("--tp", type=int, default=0,
                    help="tensor-parallel: shard the weights AND the KV pool "
                         "over this many devices (implies --mesh N); the "
                         "point records per-device param bytes and defaults "
                         "to BENCH_serve_tp.json")
    ap.add_argument("--open-loop", action="store_true",
                    help="measure latency under Poisson load through the "
                         "HTTP gateway instead of closed-loop throughput; "
                         "writes BENCH_latency.json and gates on the SLO "
                         "ceilings in --baseline (see "
                         "benchmarks/baselines/latency.json)")
    ap.add_argument("--qps", type=float, default=OPEN_LOOP_QPS,
                    help="open-loop Poisson arrival rate")
    ap.add_argument("--qps-sweep", default="",
                    help="comma-separated arrival rates (e.g. 1,2,4,8): run "
                         "the open-loop lane once per rate and write the "
                         "goodput-vs-QPS curve into the point's qps_sweep "
                         "list (implies --open-loop)")
    ap.add_argument("--multi-lora", action="store_true",
                    help="mixed-tenant multi-LoRA lane: 4 tenants + base "
                         "through the live gateway on ONE engine; gates "
                         "per-tenant isolation (zero cross-tenant prefix "
                         "hits, oracle-identical streams) and throughput "
                         "vs the shared base.  Writes BENCH_multilora.json")
    ap.add_argument("--family", default="", choices=["", "ssm", "hybrid"],
                    help="serve a stateful model family (falcon-mamba-7b / "
                         "zamba2-2.7b smoke archs) through the same paged "
                         "workload, verified token-identical to the family's "
                         "dense oracle across a forced preemption-by-swap; "
                         "writes BENCH_serve_<family>.json (a separate "
                         "trajectory series — the transformer ratchet does "
                         "not apply)")
    ap.add_argument("--requests", type=int, default=0,
                    help="open-loop request count override (0 = workload "
                         "default)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="open-loop per-request deadline (engine reaper); "
                         "0 = none.  Relaxes the all-completed gate to "
                         "all-terminal and reports goodput")
    args = ap.parse_args()

    mesh_n = max(args.mesh, args.tp)
    # must land before the jax backend initializes (the first jax import is
    # inside _build_engine, so this is early enough)
    from repro.launch.mesh import ensure_fake_pod
    ensure_fake_pod(mesh_n)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    if args.family:
        if mesh_n:
            print("bench_serve: FAIL: --family does not take --mesh/--tp "
                  "(stateful families are single-device; the slab is not "
                  "sharded)", file=sys.stderr)
            return 2
        out = args.out if args.out != "BENCH_serve.json" \
            else f"BENCH_serve_{args.family}.json"
        m, desc = run_family_workload(args.family, quick=args.quick)
        point = {
            "bench": "serve",
            "unix_time": time.time(),
            "family": args.family,
            "workload": desc,
            "mesh_devices": desc["mesh_devices"],
            "tp_devices": desc["tp_devices"],
            "tokens_per_sec": m.tokens_per_sec,
            "ttft_mean_s": m.ttft_mean_s,
            "itl_mean_s": m.itl_mean_s,
            "peak_pool_utilization": m.peak_pool_utilization,
            "peak_blocks_used": m.peak_blocks_used,
            "dense_equiv_blocks": m.dense_equiv_blocks,
            "state_slots_peak": desc["state_slots_peak"],
            "preemptions": m.preemptions,
            "swap_out_blocks": m.swap_out_blocks,
            "swap_in_blocks": m.swap_in_blocks,
            "metrics": m.to_dict(),
        }
        with open(out, "w") as f:
            json.dump(point, f, indent=2)
        print(m.summary())
        print(f"{args.family} ({desc['arch']}): dense-oracle token identity "
              f"{'OK' if desc['token_identical'] else 'MISMATCH'}, slab peak "
              f"{desc['state_slots_peak']} slots, {m.preemptions} "
              f"preemptions ({m.swap_out_blocks} out / {m.swap_in_blocks} "
              f"in)")
        print(f"{args.family} trajectory point written to {out}")
        if args.baseline:
            print("baseline gate skipped: family points are a separate "
                  "series (transformer ratchet does not apply)")
        errs = check_family(m, desc)
        for e in errs:
            print(f"bench_serve: FAIL: {e}", file=sys.stderr)
        return 1 if errs else 0

    if args.multi_lora:
        if mesh_n:
            print("bench_serve: FAIL: --multi-lora does not take --mesh/--tp"
                  " (multi-LoRA serving is single-device)", file=sys.stderr)
            return 2
        out = args.out if args.out != "BENCH_serve.json" \
            else "BENCH_multilora.json"
        point = run_multilora(quick=args.quick)
        with open(out, "w") as f:
            json.dump(point, f, indent=2)
        print(f"multi-lora: {point['tenants']} tenants + base, "
              f"{point['cross_tenant_prefix_hits']} cross-tenant prefix "
              f"hits, {point['within_tenant_reuse_tokens']} within-tenant "
              f"reuse tokens, {point['adapters_loaded']} adapters resident "
              f"({point['adapter_device_bytes'] / 1e6:.2f} MB slab <= "
              f"{point['adapter_slab_cap_bytes'] / 1e6:.2f} MB cap), mixed "
              f"{point['mixed_tokens_per_sec']:.1f} vs base "
              f"{point['base_tokens_per_sec']:.1f} tok/s "
              f"({point['mixed_vs_base_ratio']:.0%})")
        print(f"multi-lora trajectory point written to {out}")
        errs = check_multilora(point)
        for e in errs:
            print(f"bench_serve: FAIL: {e}", file=sys.stderr)
        return 1 if errs else 0

    if args.open_loop or args.qps_sweep:
        if mesh_n:
            print("bench_serve: FAIL: --open-loop does not take --mesh/--tp "
                  "(the latency lane is single-device)", file=sys.stderr)
            return 2
        out = args.out if args.out != "BENCH_serve.json" \
            else "BENCH_latency.json"
        rates = [float(x) for x in args.qps_sweep.split(",") if x.strip()] \
            if args.qps_sweep else [args.qps]
        sweep = []
        for q in rates:
            sweep.append(run_open_loop(quick=args.quick, qps=q,
                                       n_requests=args.requests,
                                       deadline_ms=args.deadline_ms))
        # the written point is the HIGHEST-rate measurement (the most
        # loaded, the one an SLO ceiling should bite on) and carries the
        # whole goodput-vs-QPS curve for aggregate_serve to render
        point = dict(sweep[-1])
        if len(sweep) > 1:
            point["qps_sweep"] = sweep
        with open(out, "w") as f:
            json.dump(point, f, indent=2)
        for p in sweep:
            print(f"open-loop @ {p['qps']:g} qps over {p['requests']} "
                  f"requests ({p['completed']} completed, "
                  f"{p['requests_shed']} shed / {p['requests_expired']} "
                  f"expired / {p['requests_errored']} errored): "
                  f"TTFT p50/p99 {p['ttft_p50_ms']:.1f}/"
                  f"{p['ttft_p99_ms']:.1f}ms, ITL p50/p99 "
                  f"{p['itl_p50_ms']:.1f}/{p['itl_p99_ms']:.1f}ms, "
                  f"{p['tokens_per_sec']:.1f} delivered tok/s "
                  f"({p['goodput_tokens_per_sec']:.1f} goodput)")
        print(f"latency trajectory point written to {out}")
        baseline = None
        if args.baseline:
            with open(args.baseline) as f:
                baseline = json.load(f)
        import os as _os
        faulty = args.deadline_ms > 0 or bool(_os.environ.get("REPRO_FAULT"))
        errs = []
        for p in sweep:
            for e in check_latency(p, baseline, faulty=faulty):
                errs.append(f"@ {p['qps']:g} qps: {e}"
                            if len(sweep) > 1 else e)
        for e in errs:
            print(f"bench_serve: FAIL: {e}", file=sys.stderr)
        return 1 if errs else 0

    m, desc = run_workload(quick=args.quick, mesh_devices=mesh_n,
                           tp=args.tp >= 1)
    out = args.out
    if args.tp >= 1 and out == "BENCH_serve.json":
        out = "BENCH_serve_tp.json"
    point = {
        "bench": "serve",
        "unix_time": time.time(),
        "workload": desc,
        "mesh_devices": desc["mesh_devices"],
        "tp_devices": desc["tp_devices"],
        "param_bytes_per_device": desc["param_bytes_per_device"],
        "param_bytes_replicated": desc["param_bytes_replicated"],
        "tokens_per_sec": m.tokens_per_sec,
        "ttft_mean_s": m.ttft_mean_s,
        "itl_mean_s": m.itl_mean_s,
        "peak_pool_utilization": m.peak_pool_utilization,
        "peak_blocks_used": m.peak_blocks_used,
        "dense_equiv_blocks": m.dense_equiv_blocks,
        "preemptions": m.preemptions,
        "shared_blocks": m.shared_blocks,
        "cow_copies": m.cow_copies,
        "swap_out_blocks": m.swap_out_blocks,
        "swap_in_blocks": m.swap_in_blocks,
        "re_prefill_avoided": m.re_prefill_avoided,
        "metrics": m.to_dict(),
    }
    with open(out, "w") as f:
        json.dump(point, f, indent=2)
    print(m.summary())
    print(f"trajectory point written to {out}")

    if desc["tp_devices"] > 1:
        ratio = desc["param_bytes_per_device"] / desc["param_bytes_replicated"]
        print(f"tensor parallel x{desc['tp_devices']}: "
              f"{desc['param_bytes_per_device'] / 1e6:.2f} MB/device of "
              f"{desc['param_bytes_replicated'] / 1e6:.2f} MB params "
              f"({ratio:.1%} of replicated)")
    if desc.get("token_identical") is not None:
        print(f"sharded-vs-single token identity: "
              f"{'OK' if desc['token_identical'] else 'MISMATCH'}")
    errs = _check(m, desc)
    # classify by the engine's EFFECTIVE mesh (the --mesh flag and the
    # REPRO_SERVE_MESH knob both count): a sharded point must never be
    # gated against — nor ratcheted into — the single-device series
    if args.baseline and desc.get("sharded"):
        print("baseline gate skipped: sharded points are a separate series "
              "(single-device floor does not apply)")
    elif args.baseline:
        with open(args.baseline) as f:
            base = json.load(f)
        floor = base["tokens_per_sec"] * (1.0 - args.max_regress)
        verdict = "OK" if m.tokens_per_sec >= floor else "REGRESSION"
        print(f"baseline gate: {m.tokens_per_sec:.1f} tok/s vs floor "
              f"{floor:.1f} (baseline {base['tokens_per_sec']:.1f} "
              f"- {args.max_regress:.0%}) -> {verdict}")
        if m.tokens_per_sec < floor:
            errs.append(f"throughput regression: {m.tokens_per_sec:.1f} < {floor:.1f}")
    for e in errs:
        print(f"bench_serve: FAIL: {e}", file=sys.stderr)
    return 1 if errs else 0


if __name__ == "__main__":
    sys.exit(cli())
