"""Fault tolerance + elasticity for the training runtime.

* ``FaultTolerantLoop`` — catches step failures, restores the latest atomic
  checkpoint, and replays from there (checkpoint/restart).
* ``StragglerDetector`` — EWMA of step durations; flags steps slower than
  ``threshold x`` the running median.  On repeated stragglers the loop calls
  the elastic hook.
* ``elastic_remesh`` — rebuilds a smaller mesh after losing hosts (shrink the
  data axis), letting the caller re-lower the step function: train state is
  resharded by jax.device_put onto the new mesh.  At 1000+ nodes this is the
  drain-and-resume path: the checkpoint is the source of truth, and because
  batches are keyed by (seed, step) the data pipeline replays exactly.
"""
from __future__ import annotations

import dataclasses
import statistics
import time
from typing import Callable, List, Optional

import jax
from jax.sharding import AxisType


@dataclasses.dataclass
class StragglerEvent:
    step: int
    duration: float
    median: float


class StragglerDetector:
    def __init__(self, threshold: float = 3.0, window: int = 50):
        self.threshold = threshold
        self.window = window
        self.durations: List[float] = []
        self.events: List[StragglerEvent] = []

    def record(self, step: int, duration: float) -> bool:
        self.durations.append(duration)
        if len(self.durations) > self.window:
            self.durations.pop(0)
        if len(self.durations) >= 5:
            med = statistics.median(self.durations)
            if duration > self.threshold * med:
                self.events.append(StragglerEvent(step, duration, med))
                return True
        return False


def elastic_remesh(current_mesh, lost_hosts: int = 1):
    """Build the largest valid mesh after losing `lost_hosts` along the data
    axis (model axis is preserved: weights shards must survive)."""
    import numpy as np
    shape = dict(current_mesh.shape)
    axes = tuple(shape.keys())
    data_ax = "data" if "data" in shape else axes[0]
    new_data = shape[data_ax] - lost_hosts
    while new_data > 0:
        try:
            sizes = tuple(new_data if a == data_ax else shape[a] for a in axes)
            n = int(np.prod(sizes))
            devices = jax.devices()[:n]
            if len(devices) < n:
                raise ValueError("not enough devices")
            return jax.make_mesh(sizes, axes,
                                 axis_types=(AxisType.Auto,) * len(axes),
                                 devices=devices)
        except ValueError:
            new_data -= 1
    raise RuntimeError("no viable mesh after failures")


class FaultTolerantLoop:
    """Wraps (step_fn, save_fn, restore_fn) with retry-from-checkpoint."""

    def __init__(self, step_fn: Callable, save_fn: Callable,
                 restore_fn: Callable, max_retries: int = 3,
                 straggler_threshold: float = 3.0,
                 on_straggler: Optional[Callable] = None):
        self.step_fn = step_fn
        self.save_fn = save_fn
        self.restore_fn = restore_fn
        self.max_retries = max_retries
        self.detector = StragglerDetector(straggler_threshold)
        self.on_straggler = on_straggler
        self.failures = 0
        self.restores = 0

    def run(self, state, start_step: int, n_steps: int,
            checkpoint_every: int = 50, batch_fn: Callable = None):
        step = start_step
        retries = 0
        while step < start_step + n_steps:
            batch = batch_fn(step) if batch_fn else None
            t0 = time.monotonic()
            try:
                state = self.step_fn(state, step, batch)
                retries = 0
            except Exception:
                self.failures += 1
                retries += 1
                if retries > self.max_retries:
                    raise
                restored = self.restore_fn(state)
                if restored is not None:
                    state, step = restored
                    self.restores += 1
                continue
            dt = time.monotonic() - t0
            if self.detector.record(step, dt) and self.on_straggler:
                self.on_straggler(step, dt)
            step += 1
            if step % checkpoint_every == 0:
                self.save_fn(state, step)
        return state, step
