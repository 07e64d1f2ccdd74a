"""Multi-device behaviours that need >1 device: run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count so the main test process
keeps its single-device view (the dry-run rule from the assignment).

Each subprocess pays a cold jax import + 8-device compile (~8 min apiece on
the CI runner), so the whole module is marked slow — the full `test` job
still runs it; the fast lane (-m "not slow") skips it."""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow

ROOT = Path(__file__).resolve().parents[1]


def _run(script: str, n_devices: int = 8) -> dict:
    code = ("import os\n"
            f"os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count={n_devices}'\n"
            + textwrap.dedent(script))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600,
                       env={"PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_elastic_remesh_shrinks_data_axis():
    out = _run("""
    import json, jax
    from repro.distributed.fault_tolerance import elastic_remesh
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    m2 = elastic_remesh(mesh, lost_hosts=1)
    print(json.dumps({"shape": dict(m2.shape), "n": int(m2.devices.size)}))
    """)
    assert out["shape"] == {"data": 3, "model": 2}
    assert out["n"] == 6


def test_sharded_train_step_runs_on_8_devices():
    """One REAL sharded train step (not just lowering) on a 4x2 mesh."""
    out = _run("""
    import json, jax, jax.numpy as jnp
    from repro.configs.base import get_config, reduced_config
    from repro.distributed import sharding as shd
    from repro.launch.steps import make_train_step
    from repro.models import build_model

    cfg = reduced_config(get_config("qwen3-0.6b"))
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    fns = build_model(cfg)
    step, opt = make_train_step(cfg, remat=False)
    with mesh:
        params = fns.init(jax.random.PRNGKey(0))
        pspecs = shd.param_specs(cfg, params, mesh)
        opt_state = opt.init(params)
        ospecs = shd.opt_state_specs(pspecs, jax.eval_shape(lambda: opt_state), mesh)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab)
        batch = {"tokens": toks, "labels": toks}
        bspecs = shd.batch_specs(cfg, batch, mesh)
        f = jax.jit(step,
                    in_shardings=(shd.to_named(pspecs, mesh),
                                  shd.to_named(ospecs, mesh),
                                  shd.to_named(bspecs, mesh)))
        params = jax.device_put(params, shd.to_named(pspecs, mesh))
        opt_state = jax.device_put(opt_state, shd.to_named(ospecs, mesh))
        batch = jax.device_put(batch, shd.to_named(bspecs, mesh))
        p2, o2, metrics = f(params, opt_state, batch)
        loss = float(metrics["loss"])
    print(json.dumps({"loss": loss, "finite": bool(loss == loss)}))
    """)
    assert out["finite"]
    assert 0 < out["loss"] < 100


def test_dryrun_cell_runner_small_mesh():
    """The dry-run analysis pipeline end-to-end on a synthetic 8-dev mesh."""
    out = _run("""
    import json, jax, time
    import repro.launch.mesh as mesh_mod
    # shrink the production mesh for the test host
    mesh_mod.make_production_mesh = lambda multi_pod=False: jax.make_mesh(
        (2, 2, 2) if multi_pod else (4, 2),
        ("pod", "data", "model") if multi_pod else ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * (3 if multi_pod else 2))
    import repro.launch.dryrun as dr
    from pathlib import Path
    import tempfile
    import repro.configs.base as cb
    import dataclasses
    # tiny shape so the compile is fast
    cb.SHAPES["tiny_train"] = cb.ShapeSpec("tiny_train", 64, 8, "train")
    import repro.configs  # register archs
    cfg = cb.get_config("qwen3-0.6b")
    cb._REGISTRY["tiny-arch"] = lambda: dataclasses.replace(
        cb.reduced_config(cfg), name="tiny-arch")
    with tempfile.TemporaryDirectory() as d:
        res = dr.run_cell("tiny-arch", "tiny_train", "pod2",
                          Path(d) / "out.json")
    print(json.dumps({"status": res["status"],
                      "devices": res["devices"],
                      "flops": res["hlo_flops_per_device"],
                      "bottleneck": res["roofline"]["bottleneck"]}))
    """)
    assert out["status"] == "ok"
    assert out["devices"] == 8
    assert out["flops"] > 0
