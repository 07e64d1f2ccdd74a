#!/usr/bin/env python3
"""Smoke run of the paged serving path on TPU at qwen3-0.6b's published
widths: 28 layers, d_model 1024, 16 query / 8 KV heads of 128, d_ff 3072,
vocab 151936, bf16, random weights from a seed.

    python3 chip_smoke.py            # one chip
    python3 chip_smoke.py --chips 4  # tensor-parallel engine over four chips

One chip, in one process:

  1. each main-path Pallas kernel (paged decode, paged prefill chunk, LoRA
     shrink and expand) against its ``repro.kernels.ref`` oracle;
  2. the compiled decode step of the serving engine must hold a Mosaic
     kernel (``tpu_custom_call``);
  3. the OpenAI gateway, booted in-process, answers 8 HTTP requests with at
     least 4 in flight together, so decode runs batched;
  4. the engine's paged prefill logits against the model's dense prefill.

Four chips (``--chips 4``) runs only this: a tensor-parallel engine on
``make_serve_mesh(4)`` against a one-chip engine on device 0, greedy tokens
identical, every KV-pool and weight leaf on four devices.

Times printed come from one smoke run; they are not measurements.  Every
check raises on failure, so the exit status is non-zero and no result line
is printed.  A passing run ends with one JSON line:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Any platform but TPU is refused.
"""
from __future__ import annotations

import argparse
import asyncio
import contextlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen3-0.6b"
SEED = 0
# the gateway engine's shape: 8 decode rows, 512-token tables of 16-token pages
MAX_BATCH, MAX_LEN, BLOCK_SIZE = 8, 512, 16


def require_tpu(chips: int):
    """The devices JAX found, or SystemExit unless they are ``chips`` or
    more TPUs: a smoke run that fell back to the CPU proves nothing."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{devices[0].platform!r} devices")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return devices


def _check(name: str, err: float, bound: float) -> None:
    print(f"  {name}: max_abs_err={err:.3e} bound={bound:.3e}", flush=True)
    if not err <= bound:             # also catches a NaN error
        raise AssertionError(f"{name}: error {err} above bound {bound}")


def _max_abs_diff(a, b) -> float:
    import jax.numpy as jnp

    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def check_kernels(cfg, seed: int = SEED) -> None:
    """Each main-path kernel at ``cfg``'s widths against its oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    rng = np.random.default_rng(seed)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    bf16 = jnp.bfloat16
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    b, bs, m = MAX_BATCH, BLOCK_SIZE, MAX_LEN // BLOCK_SIZE
    n_pages = b * m + 1
    k_pages = jax.random.normal(next(keys), (n_pages, bs, kv, hd), bf16)
    v_pages = jax.random.normal(next(keys), (n_pages, bs, kv, hd), bf16)
    # every row owns distinct pages, null (0) past its length; lengths run
    # from one token to the whole table
    lens = rng.integers(1, m * bs + 1, size=b)
    lens[0], lens[1] = 1, m * bs
    perm = rng.permutation(np.arange(1, n_pages))
    tables = np.zeros((b, m), np.int32)
    for i, n in enumerate(lens):
        used = -(-int(n) // bs)
        tables[i, :used] = perm[i * m:i * m + used]
    tables, lens = jnp.asarray(tables), jnp.asarray(lens, jnp.int32)
    # Both sides see the same bf16 inputs and f32 scores.  The kernel rounds
    # each softmax weight to bf16 before the PV matmul (2^-9 relative, at
    # most 2^-9 * max|v| over weights summing to 1) and both round the
    # output to bf16 (2^-9 * |o| <= 2^-9 * max|v| each): 2^-8 * max|v|
    # in all, bounded at four times that.
    attn_bound = 4 * 2.0 ** -8 * float(jnp.max(jnp.abs(v_pages)))

    q = jax.random.normal(next(keys), (b, 1, h, hd), bf16)
    want = ref.paged_attention_ref(q, k_pages, v_pages, tables, lens)
    for ppf in (1, m):
        got = ops.paged_attention(q, k_pages, v_pages, tables, lens,
                                  pages_per_fetch=ppf)
        _check(f"paged decode B={b} pages_per_fetch={ppf}",
               _max_abs_diff(got, want), attn_bound)

    # one prefill chunk in the middle of the full-table row
    c, start = bs, 5 * bs
    qc = jax.random.normal(next(keys), (1, c, h, hd), bf16)
    cpos = jnp.arange(start, start + c, dtype=jnp.int32)
    kv_lens = jnp.asarray([start + c], jnp.int32)
    want = ref.paged_attention_chunk_ref(qc, k_pages, v_pages, tables[1:2],
                                         cpos, kv_lens)
    for ppf in (1, m):
        got = ops.paged_attention_chunk(qc, k_pages, v_pages, tables[1:2],
                                        cpos, kv_lens, pages_per_fetch=ppf)
        _check(f"paged prefill chunk C={c} pages_per_fetch={ppf}",
               _max_abs_diff(got, want), attn_bound)

    # segmented LoRA over 4 adapter slots of rank 16 on the widest attention
    # projection, base rows (-1) mixed in
    d, r, o = cfg.d_model, 16, cfg.q_dim
    x = jax.random.normal(next(keys), (b, d), bf16)
    a_slab = jax.random.normal(next(keys), (4, d, r), bf16)
    b_slab = jax.random.normal(next(keys), (4, r, o), bf16)
    idx = jnp.asarray([0, 1, -1, 2, 3, -1, 1, 0][:b], jnp.int32)
    hi = jax.lax.Precision.HIGHEST
    slot = jnp.maximum(idx, 0)
    # bf16 x bf16 products are exact in f32 on both sides; only the order
    # of the d f32 additions differs, each rounding at 2^-24 relative, so
    # d * 2^-24 * sum|x*a| bounds the error, doubled for room
    h_want = ref.lora_shrink_ref(x, a_slab, idx)
    h_abs = jnp.einsum("td,tdr->tr", jnp.abs(x).astype(jnp.float32),
                       jnp.abs(a_slab[slot]).astype(jnp.float32), precision=hi)
    _check(f"lora shrink T={b} d={d} R={r}",
           _max_abs_diff(ops.lora_shrink(x, a_slab, idx), h_want),
           2 * d * 2.0 ** -24 * float(jnp.max(h_abs)))
    # the f32 activations may be rounded to bf16 for the matmul on either
    # side (2^-9 relative each) and both outputs round to bf16 (2^-9 each):
    # 2^-7 * sum|h*b| in all, doubled for room
    y_abs = jnp.einsum("tr,tro->to", jnp.abs(h_want),
                       jnp.abs(b_slab[slot]).astype(jnp.float32), precision=hi)
    _check(f"lora expand T={b} R={r} O={o}",
           _max_abs_diff(ops.lora_expand(h_want, b_slab, idx),
                         ref.lora_expand_ref(h_want, b_slab, idx, bf16)),
           2 * 2.0 ** -7 * float(jnp.max(y_abs)))


def check_decode_kernel(engine) -> float:
    """Compile the engine's decode step as it serves and require a Mosaic
    kernel in it; returns the compile seconds."""
    import jax.numpy as jnp

    b, m = engine.max_batch, engine.max_blocks_per_seq
    batch = {"token": jnp.zeros((b, 1), jnp.int32),
             "block_tables": jnp.zeros((b, m), jnp.int32),
             "seq_lens": jnp.zeros((b,), jnp.int32)}
    t0 = time.perf_counter()
    hlo = engine._decode_fn.lower(engine.params, engine.cache,
                                  batch).compile().as_text()
    secs = time.perf_counter() - t0
    if "tpu_custom_call" not in hlo:
        raise AssertionError("the compiled decode step holds no "
                             "tpu_custom_call: the paged kernel is not in it")
    return secs


# (endpoint, stream, prompt bytes, max_tokens, temperature): mixed endpoints
# and streaming, prompts from a few dozen to a few hundred bytes
REQUESTS = [
    ("/v1/completions", False, 40, 16, 0.0),
    ("/v1/completions", True, 120, 32, 0.8),
    ("/v1/chat/completions", False, 60, 24, 0.0),
    ("/v1/chat/completions", True, 200, 16, 0.8),
    ("/v1/completions", True, 300, 32, 0.0),
    ("/v1/completions", False, 80, 24, 0.8),
    ("/v1/chat/completions", True, 150, 32, 0.0),
    ("/v1/completions", False, 250, 16, 0.0),
]
_WORDS = ("paged attention streams kv pages through the block table while "
          "the gateway batches decode rows across tenants and the compiler "
          "picks tiles for the chip").split()


def _text(n_bytes: int, rng) -> str:
    out = ""
    while len(out) < n_bytes:
        out += _WORDS[int(rng.integers(len(_WORDS)))] + " "
    return out[:n_bytes]


async def _http(port: int, path: str, payload: dict):
    """One POST on a raw socket -> (status, body bytes)."""
    body = json.dumps(payload).encode()
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        writer.write(f"POST {path} HTTP/1.1\r\nHost: smoke\r\n"
                     "Content-Type: application/json\r\n"
                     f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        await writer.drain()
        status = int((await reader.readline()).split()[1])
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        return status, await reader.read()
    finally:
        writer.close()
        await writer.wait_closed()


def _parse_reply(stream: bool, data: bytes):
    """-> (finish_reason, completion_tokens) from either response shape."""
    if not stream:
        obj = json.loads(data)
        return (obj["choices"][0]["finish_reason"],
                obj["usage"]["completion_tokens"])
    events = [ln for ln in data.split(b"\n") if ln.strip()]
    if not all(e.startswith(b"data: ") for e in events) \
            or events[-1] != b"data: [DONE]":
        raise AssertionError(f"malformed SSE stream: {data[-300:]!r}")
    last = json.loads(events[-2][len(b"data: "):])
    return (last["choices"][0]["finish_reason"],
            last["usage"]["completion_tokens"])


async def _serve_requests(router, seed: int):
    """Boot the gateway on an ephemeral port, fire every request at once,
    and return the replies and the most requests seen in flight together."""
    import numpy as np

    from repro.serve.gateway import Gateway

    rng = np.random.default_rng(seed)
    model = router.models()[0]
    asks = []
    for i, (path, stream, n_bytes, max_tokens, temp) in enumerate(REQUESTS):
        text = _text(n_bytes, rng)
        payload = {"model": model.model_id, "max_tokens": max_tokens,
                   "stream": stream, "temperature": temp, "top_k": 40,
                   "seed": seed + i}
        if path.endswith("chat/completions"):
            payload["messages"] = [{"role": "user", "content": text}]
        else:
            payload["prompt"] = text
        asks.append((path, stream, max_tokens, payload))

    peak = 0
    async with Gateway(router, port=0) as gw:
        async def watch():
            nonlocal peak
            while True:
                peak = max(peak, model.async_engine.stats()["live_requests"])
                await asyncio.sleep(0.01)

        watcher = asyncio.ensure_future(watch())
        try:
            replies = await asyncio.gather(
                *[_http(gw.port, path, payload)
                  for path, _, _, payload in asks])
        finally:
            watcher.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await watcher
    return [(ask, status, data) for ask, (status, data)
            in zip(asks, replies)], peak


def build_gateway_router(arch: str = ARCH, smoke: bool = False):
    """The router ``repro.launch.gateway`` builds, with the kernel plan
    compiled; -> (router, its one engine)."""
    from repro.launch.gateway import build_router

    t0 = time.perf_counter()
    router = build_router([arch], smoke=smoke, mesh_devices=0,
                          max_batch=MAX_BATCH, max_len=MAX_LEN,
                          block_size=BLOCK_SIZE, plan_kernels=True)
    engine = router.models()[0].engine
    print(f"engine built in {time.perf_counter() - t0:.1f} s "
          f"(pages_per_fetch={engine.pages_per_fetch}, "
          f"pool_blocks={engine.pool.usable_blocks})", flush=True)
    return router, engine


def run_requests(router, engine, seed: int = SEED) -> None:
    """Serve ``REQUESTS`` over HTTP through an in-process gateway and fail
    on any request that did not end in ``length``/``stop``, any quarantined
    request or step crash, any KV-ledger violation, or fewer than 4
    requests in flight together."""
    t0 = time.perf_counter()
    results, peak = asyncio.run(_serve_requests(router, seed))
    wall = time.perf_counter() - t0
    tokens = 0
    for (path, stream, max_tokens, _), status, data in results:
        if status != 200:
            raise AssertionError(f"{path} answered {status}: {data[:300]!r}")
        reason, n = _parse_reply(stream, data)
        print(f"  {path} stream={stream}: finish_reason={reason} "
              f"tokens={n}", flush=True)
        if reason not in ("length", "stop"):
            raise AssertionError(f"{path} ended with {reason!r}")
        if reason == "length" and n != max_tokens:
            raise AssertionError(f"{path}: {n} tokens, asked {max_tokens}")
        tokens += n
    m = engine.metrics()
    print(f"smoke serve: {len(results)} requests, {tokens} tokens generated, "
          f"{peak} in flight at most, wall {wall:.1f} s including compiles "
          "(one smoke run, not a measurement)", flush=True)
    if m.requests_errored or m.step_crashes:
        raise AssertionError(f"{m.requests_errored} requests quarantined, "
                             f"{m.step_crashes} step crashes")
    violations = engine.check_invariants()
    if violations:
        raise AssertionError(f"KV ledger violations: {violations}")
    if peak < 4:
        raise AssertionError(f"only {peak} requests in flight together; "
                             "decode never ran batched at 4 or more")


def check_prefill_logits(engine, seed: int = SEED) -> None:
    """One prompt's last-position logits: the engine's chunked paged
    prefill (the kernel path on the chip) against the model's dense
    ``prefill`` on the same weights, both upcast to f32."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serve.engine import Request

    rng = np.random.default_rng(seed + 1)
    prompt = rng.integers(1, engine.cfg.vocab, size=3 * BLOCK_SIZE - 5)
    seen = []
    paged = engine._prefill_fn

    def record(params, cache, batch, m_used):
        cache, logits = paged(params, cache, batch, m_used=m_used)
        seen.append((int(batch["start"]), logits))
        return cache, logits

    engine._prefill_fn = record
    try:
        engine.submit(Request(rid=-1, prompt=prompt.tolist(), max_new=1))
        engine.run_until_done()
    finally:
        engine._prefill_fn = paged
    start, logits = seen[-1]
    got = logits[0, len(prompt) - 1 - start]
    tokens = {"tokens": jnp.asarray(prompt[None], jnp.int32)}
    prefill = jax.jit(engine.fns.prefill)
    want = prefill(engine.params, tokens)[1][0].astype(jnp.float32)
    # the same weights upcast, every matmul at full f32 precision
    with jax.default_matmul_precision("highest"):
        exact = prefill(jax.tree.map(lambda w: w.astype(jnp.float32),
                                     engine.params), tokens)[1][0]
    floor = _max_abs_diff(want, exact)
    print(f"  dense prefill vs its f32 reference: max_abs_err={floor:.3e}",
          flush=True)
    # The paged and dense paths both compute in bf16 but round at different
    # points (the kernel keeps its online softmax in f32 and rounds once;
    # the dense path rounds scores and probabilities).  Each sits about as
    # far from the f32 reference as the dense path does, so their gap is
    # at most about twice that: bounded at three times, plus one bf16 ulp
    # (2^-8) of the largest logit, as both round their logits to bf16.
    bound = 3 * floor + 2.0 ** -8 * float(jnp.max(jnp.abs(exact)))
    _check(f"engine vs dense prefill logits, {len(prompt)}-token prompt",
           _max_abs_diff(got, want), bound)


def check_tensor_parallel(arch: str = ARCH, smoke: bool = False,
                          n: int = 4, seed: int = SEED) -> None:
    """A tensor-parallel engine on ``n`` devices against a one-device
    engine: identical greedy tokens, every KV-pool and weight leaf on ``n``
    distinct devices, about 1/n of the parameter bytes on each."""
    import jax
    import numpy as np

    from repro.configs.base import get_config, reduced_config
    from repro.launch.mesh import make_serve_mesh
    from repro.models import build_model
    from repro.serve.engine import Request, ServeEngine

    cfg = get_config(arch)
    if smoke:
        cfg = reduced_config(cfg)
    params = jax.jit(build_model(cfg).init)(jax.random.PRNGKey(seed))
    kw = dict(max_batch=4, max_len=128, block_size=BLOCK_SIZE,
              fault_injector=False)
    engines = {"tp": ServeEngine(cfg, params, mesh=make_serve_mesh(n),
                                 tp=True, **kw),
               "one": ServeEngine(cfg, params, mesh=False, **kw)}
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab, size=k).tolist()
               for k in (12, 30, 47, 64)]
    outs = {}
    for name, eng in engines.items():
        t0 = time.perf_counter()
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new=16))
        done = eng.run_until_done()
        m = eng.metrics()
        print(f"  {name}: {m.decode_tokens + len(done)} tokens, wall "
              f"{time.perf_counter() - t0:.1f} s including compiles (one "
              "smoke run, not a measurement)", flush=True)
        if m.requests_errored or m.step_crashes or eng.check_invariants() \
                or len(done) != len(prompts):
            raise AssertionError(f"{name} engine: {m.requests_errored} "
                                 f"quarantined, {m.step_crashes} crashes, "
                                 f"{len(done)}/{len(prompts)} finished")
        outs[name] = {r.rid: r.out for r in done}
    if outs["tp"] != outs["one"]:
        raise AssertionError(f"tensor-parallel tokens differ: {outs}")
    print(f"  greedy tokens identical over {len(prompts)} requests",
          flush=True)

    tp, one = engines["tp"], engines["one"]
    kv_shard = cfg.n_kv_heads // n
    for leaf in jax.tree.leaves(tp.cache):
        shards = leaf.addressable_shards
        if len({s.device for s in shards}) != n \
                or shards[0].data.shape[3] != kv_shard:
            raise AssertionError(f"KV pool leaf {leaf.shape} not split over "
                                 f"{n} devices by KV head")
    split = 0
    for leaf in jax.tree.leaves(tp.params):
        shards = leaf.addressable_shards
        if len({s.device for s in shards}) != n:
            raise AssertionError(f"weight {leaf.shape} not on {n} devices")
        split += shards[0].data.shape != leaf.shape
    dev0 = {jax.devices()[0]}
    for leaf in jax.tree.leaves((one.params, one.cache)):
        if {s.device for s in leaf.addressable_shards} != dev0:
            raise AssertionError("the one-chip engine left device 0")
    ratio = tp.param_bytes_per_device / tp.param_bytes_replicated
    print(f"  per-device parameter bytes {tp.param_bytes_per_device} of "
          f"{tp.param_bytes_replicated} ({ratio:.4f}); {split} weight "
          f"leaves split, KV pool split {n} ways by KV head", flush=True)
    if ratio > 1 / n + 0.05:
        raise AssertionError(f"per-device parameter share {ratio:.3f} is "
                             f"not about 1/{n}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the tensor-parallel phase on four chips")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    import jax

    print(f"jax {jax.__version__}; devices {jax.devices()}", flush=True)
    devices = require_tpu(args.chips)
    print(f"device kind {devices[0].device_kind}", flush=True)
    from repro.launch.compile_cache import use_compile_cache
    print(f"compile cache {use_compile_cache()}", flush=True)

    from repro.configs.base import get_config
    cfg = get_config(ARCH)
    print(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads}/{cfg.n_kv_heads} heads of "
          f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab}, "
          f"{cfg.dtype}", flush=True)
    if args.chips == 4:
        print("tensor parallel over 4 chips vs one chip:", flush=True)
        check_tensor_parallel(n=4)
    else:
        print("kernels vs kernels/ref.py oracles:", flush=True)
        check_kernels(cfg)
        router, engine = build_gateway_router()
        secs = check_decode_kernel(engine)
        print(f"decode step compiled in {secs:.1f} s with tpu_custom_call "
              "(one smoke run, not a measurement)", flush=True)
        print("gateway requests:", flush=True)
        run_requests(router, engine)
        check_prefill_logits(engine)
    print(f"smoke wall {time.perf_counter() - t_start:.1f} s "
          "(one smoke run, not a measurement)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
