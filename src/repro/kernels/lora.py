"""Segmented gather-BGMV LoRA Pallas kernels: every row of a batch applies
its *own* low-rank adapter in one call.

Multi-tenant decode batches mix requests served by different LoRA adapters
(and base-only rows).  The dense approach gathers each row's ``(A, B)`` pair
out of the adapter slab into per-row matrices and runs a batched matmul —
O(rows * d * r) HBM traffic for the gather alone, repeated every step.
Punica's insight (SGMV/BGMV) is that the gather belongs *inside* the kernel:
the grid walks batch rows, and each grid step DMAs exactly one adapter's
weight tile straight from the slab into VMEM, selected by a scalar-prefetched
per-row adapter index — the same trick ``paged_attention.py`` uses to walk
block tables.

Two kernels factor the delta ``y = (x @ A) @ B``:

* ``lora_shrink``: ``x (T, d)`` against slab ``A (S, d, R)`` with per-row
  slot indices ``idx (T,)`` -> ``h (T, R)`` in f32.  Rows with ``idx < 0``
  (no adapter) produce exact zeros.
* ``lora_expand``: ``h (T, R)`` against slab ``B (S, R, O)`` -> ``y (T, O)``
  in the requested dtype, tiled over the output features by ``block_out``
  (chosen by Auto Schedule, see ``repro.core.codegen.lora_tiles``).

Ragged ranks cost nothing: the slab pads every adapter to the shared rank
slot ``R`` with zeros, so a rank-8 adapter in a rank-16 slot contributes
zero through the padding — and a rank-0 adapter is all padding, making its
delta exactly zero (the token-identity contract for rank 0).

TPU tiling note: one grid step touches a ``(d, R)`` or ``(R, block_out)``
weight tile; R is sublane-padded (multiple of 8) by the AdapterStore, and
``block_out`` is lane-aligned by the plan, so Mosaic pads at most the tiny
rank axis.  CPU runs in interpret mode like every other kernel here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Auto Schedule's tile choice for the expand kernel, set at trace time by the
# serve engine (repro.core.codegen.lora_tiles -> set_lora_plan) exactly like
# attention.set_paged_plan routes pages_per_fetch.  Direct callers (tests,
# one-off scripts) get the default.
_LORA_PLAN = {"block_out": 256}


def set_lora_plan(block_out: int) -> None:
    _LORA_PLAN["block_out"] = max(1, int(block_out))


def lora_plan_block_out() -> int:
    return _LORA_PLAN["block_out"]


def _shrink_kernel(idx_ref, x_ref, a_ref, o_ref):
    t = pl.program_id(0)
    valid = idx_ref[t] >= 0
    h = jnp.dot(x_ref[0].astype(jnp.float32),
                a_ref[0].astype(jnp.float32),
                preferred_element_type=jnp.float32)
    o_ref[0] = jnp.where(valid, h, 0.0)


def lora_shrink_kernel(x: jax.Array, a_slab: jax.Array, idx: jax.Array,
                       interpret: bool = False) -> jax.Array:
    """x (T, d); a_slab (S, d, R); idx (T,) int32 slot per row, -1 = no
    adapter -> (T, R) f32.  Each grid step DMAs one row's adapter tile
    ``A[idx[t]]`` (rows with idx < 0 read slot 0 and mask to zero).

    Rows ride as ``(T, 1, d)`` so a one-row block's last two dims equal the
    array's, which Mosaic accepts at any T; a ``(1, d)`` block of ``(T, d)``
    breaks its (8, 128) tiling rule."""
    t, d = x.shape
    _, d2, r = a_slab.shape
    assert d == d2, f"x feature dim {d} != slab {d2}"
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,      # idx
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda t, idx: (t, 0, 0)),
            pl.BlockSpec((1, d, r),
                         lambda t, idx: (jnp.maximum(idx[t], 0), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, r), lambda t, idx: (t, 0, 0)),
    )
    h = pl.pallas_call(
        _shrink_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, 1, r), jnp.float32),
        interpret=interpret,
    )(idx.astype(jnp.int32), x.reshape(t, 1, d), a_slab)
    return h.reshape(t, r)


def _expand_kernel(idx_ref, h_ref, b_ref, o_ref):
    t = pl.program_id(0)
    valid = idx_ref[t] >= 0
    y = jnp.dot(h_ref[0], b_ref[0].astype(jnp.float32),
                preferred_element_type=jnp.float32)
    o_ref[0] = jnp.where(valid, y, 0.0).astype(o_ref.dtype)


def lora_expand_kernel(h: jax.Array, b_slab: jax.Array, idx: jax.Array,
                       out_dtype, block_out: int = 256,
                       interpret: bool = False) -> jax.Array:
    """h (T, R) f32; b_slab (S, R, O); idx (T,) int32 -> (T, O) out_dtype.
    The grid tiles the output features by ``block_out`` so one step's
    weight tile is ``(R, block_out)`` regardless of projection width.
    Rows ride as ``(T, 1, .)`` for the same tiling reason as the shrink."""
    t, r = h.shape
    _, r2, o = b_slab.shape
    assert r == r2, f"h rank {r} != slab {r2}"
    bo = max(1, min(block_out, o))
    pad = (-o) % bo
    if pad:
        b_slab = jnp.pad(b_slab, ((0, 0), (0, 0), (0, pad)))
    steps = (o + pad) // bo
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,      # idx
        grid=(t, steps),
        in_specs=[
            pl.BlockSpec((1, 1, r), lambda t, j, idx: (t, 0, 0)),
            pl.BlockSpec((1, r, bo),
                         lambda t, j, idx: (jnp.maximum(idx[t], 0), 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, bo), lambda t, j, idx: (t, 0, j)),
    )
    y = pl.pallas_call(
        _expand_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, 1, o + pad), out_dtype),
        interpret=interpret,
    )(idx.astype(jnp.int32), h.reshape(t, 1, r), b_slab).reshape(t, o + pad)
    return y[:, :o] if pad else y
