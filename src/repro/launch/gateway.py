"""Boot the OpenAI-compatible HTTP gateway over one or more serve engines.

    PYTHONPATH=src python -m repro.launch.gateway --arch qwen3-0.6b --smoke \
        --port 8011

    # two models multiplexed by one router (ids default to the cfg names):
    PYTHONPATH=src python -m repro.launch.gateway --smoke \
        --arch qwen3-0.6b --arch stablelm-3b --port 8011

Prints ``gateway listening on http://HOST:PORT`` once ready (CI polls
``/health``), serves until SIGINT/SIGTERM, then prints ``gateway shut down
cleanly`` and exits 0 — the gateway-smoke CI job asserts both lines.
``--mesh N`` builds the engines over a mesh-sharded KV pool, same semantics
as ``repro.launch.serve``.
"""
from __future__ import annotations

import argparse
import asyncio
import signal


def build_router(archs, smoke: bool, mesh_devices: int, max_batch: int,
                 max_len: int, block_size: int, plan_kernels: bool):
    import jax

    from repro.configs.base import get_config, reduced_config
    from repro.models import build_model as build_model_fns
    from repro.serve.gateway import build_model, Router

    mesh = None          # defer to REPRO_SERVE_MESH
    if mesh_devices >= 1:
        from repro.launch.mesh import make_serve_mesh
        mesh = make_serve_mesh(mesh_devices)
    models = []
    for arch in archs:
        cfg = get_config(arch)
        if smoke:
            cfg = reduced_config(cfg)
        fns = build_model_fns(cfg)
        params = fns.init(jax.random.PRNGKey(0))
        models.append(build_model(
            cfg, params, max_batch=max_batch, max_len=max_len,
            block_size=block_size, plan_kernels=plan_kernels, mesh=mesh))
    return Router(models)


async def serve(args) -> None:
    from repro.serve.gateway import Gateway

    router = build_router(
        args.arch or ["qwen3-0.6b"], smoke=args.smoke,
        mesh_devices=args.mesh, max_batch=args.max_batch,
        max_len=args.max_len, block_size=args.block_size,
        plan_kernels=not args.no_plan_kernels)
    gw = Gateway(router, host=args.host, port=args.port)
    await gw.start()
    ids = ", ".join(m.model_id for m in router.models())
    print(f"gateway listening on {gw.url} (models: {ids})", flush=True)

    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    await stop.wait()
    await gw.stop()
    print("gateway shut down cleanly", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", action="append", default=None,
                    help="model arch to serve; repeatable — each becomes "
                         "one routed model id (default: qwen3-0.6b)")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced per-arch configs (CPU CI size)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000,
                    help="0 picks an ephemeral port (printed when ready)")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--mesh", type=int, default=0,
                    help="shard each engine's KV pool over N devices "
                         "(0 = defer to REPRO_SERVE_MESH)")
    ap.add_argument("--no-plan-kernels", action="store_true",
                    help="skip the pipeline compile of the paged attention "
                         "shapes (faster boot; smoke/CI use)")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.mesh import ensure_fake_pod
    ensure_fake_pod(args.mesh)
    use_compile_cache()
    asyncio.run(serve(args))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
