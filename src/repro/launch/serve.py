"""Serving driver: paged-KV continuous batching over synthetic prompts.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
        --requests 8 --max-new 16 --block-size 8 --temperature 0.8 --top-k 40

Family-agnostic: any registered arch serves through the same engine —
attention families (dense/moe) page their KV through the block pool, ssm
archs (``--arch falcon-mamba-7b``) keep per-request recurrent state in the
StateSlab tier, and hybrid archs (``--arch zamba2-2.7b``) carry the mixed
layout (KV blocks for the shared attention, slab slots for the Mamba2
backbone).

``--mesh N`` shards the KV block pool over N devices on the kv-heads axis
(under ``JAX_PLATFORMS=cpu`` it forces an N-device CPU fake pod first);
outputs are token-identical to the single-device run.  ``--tp N``
additionally shards the WEIGHTS over the same mesh using the partition rules
Auto Distribution emits (~1/N param bytes per device; see docs/sharding.md
and the REPRO_TP_REDUCE_SCATTER knob).  Prints per-run ServeMetrics;
``--metrics-out`` dumps them as JSON (the same shape bench_serve emits into
BENCH_serve.json).
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.configs.base import get_config, reduced_config
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import ensure_fake_pod
from repro.models import build_model
from repro.serve.engine import Request, SamplingParams, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="KV pool size in blocks (0 = dense-capacity parity)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="prompt tokens prefetched per engine step "
                         "(0 = one block)")
    ap.add_argument("--admission", choices=["conservative", "optimistic"],
                    default="conservative")
    ap.add_argument("--host-blocks", type=int, default=-1,
                    help="host swap-tier size in blocks (-1 = pool-sized, "
                         "0 = no swap tier; see REPRO_KV_SWAP)")
    ap.add_argument("--prefix-cache-blocks", type=int, default=-1,
                    help="blocks retained for prompt-prefix sharing "
                         "(-1 = pool/4, 0 = sharing off)")
    ap.add_argument("--mesh", type=int, default=0,
                    help="shard the KV pool over this many devices on the "
                         "kv-heads axis (1 = explicit 1-device mesh; 0 = "
                         "defer to REPRO_SERVE_MESH; a CPU fake pod under "
                         "JAX_PLATFORMS=cpu)")
    ap.add_argument("--tp", type=int, default=0,
                    help="tensor-parallel: shard the weights AND the KV pool "
                         "over this many devices (implies --mesh N; 0 = "
                         "defer to REPRO_SERVE_TP)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-out", default="")
    args = ap.parse_args()

    mesh_n = max(args.mesh, args.tp)
    ensure_fake_pod(mesh_n)
    use_compile_cache()
    mesh = None          # 0: defer to the REPRO_SERVE_MESH knob
    if mesh_n >= 1:      # an explicit CLI width always beats the env knob
        from repro.launch.mesh import make_serve_mesh
        mesh = make_serve_mesh(mesh_n)
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced_config(cfg)
    fns = build_model(cfg)
    params = fns.init(jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_batch=args.max_batch,
                      max_len=args.max_len, block_size=args.block_size,
                      num_blocks=args.num_blocks or None,
                      prefill_chunk_tokens=args.prefill_chunk or None,
                      admission=args.admission,
                      host_blocks=None if args.host_blocks < 0 else args.host_blocks,
                      prefix_cache_blocks=None if args.prefix_cache_blocks < 0
                      else args.prefix_cache_blocks,
                      mesh=mesh, tp=True if args.tp >= 1 else None)
    if eng.tp:
        print(f"tensor parallel x{eng.metrics().tp_devices}: "
              f"{eng.param_bytes_per_device / 1e6:.2f} MB/device of "
              f"{eng.param_bytes_replicated / 1e6:.2f} MB params "
              f"({eng.param_bytes_per_device / eng.param_bytes_replicated:.0%})")
    rng = np.random.default_rng(args.seed)
    for i in range(args.requests):
        prompt = rng.integers(1, cfg.vocab, size=int(rng.integers(4, 12))).tolist()
        eng.submit(Request(rid=i, prompt=prompt, max_new=args.max_new,
                           sampling=SamplingParams(temperature=args.temperature,
                                                   top_k=args.top_k,
                                                   seed=args.seed + i)))
    eng.run_until_done()
    m = eng.metrics()
    print(m.summary())
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(m.to_dict(), f, indent=2)
        print(f"metrics written to {args.metrics_out}")


if __name__ == "__main__":
    main()
