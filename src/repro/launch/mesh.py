"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


def make_local_mesh():
    """Degenerate 1x1 mesh over whatever devices exist (tests/examples)."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def ensure_fake_pod(n: int) -> None:
    """Ask XLA for an ``n``-device CPU fake pod by appending
    ``--xla_force_host_platform_device_count`` to XLA_FLAGS.

    Acts only when ``JAX_PLATFORMS`` is explicitly ``cpu``: a run meant for
    an accelerator must find its devices or fail, never quietly carry on
    over fake CPU devices.  Only effective if the backend has not
    initialized yet (XLA reads the flag at first device use) — call it
    before anything touches ``jax.devices()``.  No-op when ``n <= 1`` or
    when XLA_FLAGS already carries a forced count (an operator's explicit
    setting wins)."""
    import os

    if os.environ.get("JAX_PLATFORMS", "") != "cpu":
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if n > 1 and "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}").strip()


def make_serve_mesh(n_model=None):
    """Serving mesh: tensor-parallel only, ``(1, n)`` over ("data", "model").

    The serve engine shards its KV block pool on the kv-heads axis, which
    maps to "model"; the size-1 "data" axis exists so the cache PartitionSpec
    rules in ``repro.distributed.sharding`` resolve every axis name.  Uses
    the first ``n_model`` devices (default: all visible — on a CPU fake pod
    that is whatever ``--xla_force_host_platform_device_count`` forced)."""
    import numpy as np

    from jax.sharding import Mesh

    devices = jax.devices()
    n = n_model or len(devices)
    if n > len(devices):
        raise ValueError(f"serve mesh wants {n} devices, only "
                         f"{len(devices)} visible")
    return Mesh(np.array(devices[:n]).reshape(1, n), ("data", "model"))


def mesh_device_count(mesh) -> int:
    return int(mesh.devices.size)
