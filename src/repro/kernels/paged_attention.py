"""Paged flash-attention Pallas kernel: stream KV pages through the block
table instead of materializing the gathered span.

The dense fallback (``paged_gather`` in ``repro.models.attention``) copies the
*entire* ``pages[tables]`` span into a ``(B, M*bs, KV, hd)`` tensor on every
decode step — O(max_len) HBM traffic per token.  This kernel walks each
request's block table in SMEM (``PrefetchScalarGridSpec`` scalar prefetch, so
the table is resident before the first tile DMA is issued), streams K/V one
page at a time straight from the pool into VMEM, and folds each page into a
``flash_attention.py``-style online softmax (running row-max / row-sum /
accumulator living in VMEM scratch across grid steps).  Pages past a
request's length — including null-padded table entries, which sit at the tail
by construction — are skipped entirely via ``pl.when``, so per-token traffic
is O(resident pages), not O(table capacity).

Layout:  pages stay in their native pool layout ``(N, bs, KV, hd)``; a grid
step fetches the ``(bs, KV, hd)`` slab of one page (all KV heads of one
block, contiguous in HBM).  Queries arrive grouped by KV head as
``(B, KV, R, hd)`` where ``R = group * C`` rows share one KV head (``group``
= GQA ratio, ``C`` = query tokens: 1 for decode, the chunk length for chunked
prefill).  Per-row causal bounds ``q_pos`` unify both callers: decode rows
all carry ``seq_len - 1``; prefill rows carry their absolute position.

``pages_per_fetch`` (chosen by the Auto Schedule cost model, see
``repro.core.codegen.paged_pages_per_fetch``) issues that many independent
page DMAs per grid step — on TPU the pipelined fetches hide each other's
latency; the online softmax folds them sequentially either way.

TPU tiling note: the per-page tile is ``(bs, KV, hd)`` with ``hd`` typically
64–128; Mosaic pads sub-(8,128) tiles, which wastes some VMEM at small block
sizes but keeps the pool layout untouched (no transpose of the whole pool
per step — that would reintroduce the O(pool) traffic this kernel removes).

Mesh-sharded serving note: when the serve engine shards the KV pool on the
kv-heads axis (``repro.models.attention.set_serve_mesh``), this kernel is
invoked *inside* shard_map with the per-shard page slab ``(N, bs, KV/n,
hd)`` and the query heads grouped under those KV heads.  Nothing here
changes: the grid is already per KV head, so each shard simply runs a
narrower grid over its own heads — the head axis partitions the kernel
cleanly, which is exactly why the pool shards on it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(tables_ref, lens_ref, qpos_ref, q_ref, *refs,
                  scale: float, block_size: int, kv_heads: int,
                  pages_per_fetch: int, steps: int):
    """One grid step: fold ``pages_per_fetch`` pages of one batch row into
    the running softmax.  refs = P k_refs + P v_refs + o_ref + 3 scratch."""
    p_f = pages_per_fetch
    k_refs = refs[:p_f]
    v_refs = refs[p_f:2 * p_f]
    o_ref = refs[2 * p_f]
    m_ref, l_ref, acc_ref = refs[2 * p_f + 1:]
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = lens_ref[b]          # scalar read from SMEM
    qpos = qpos_ref[0]            # (R, 1) per-row causal bound, VMEM

    for p in range(p_f):
        page_no = j * p_f + p

        # page live iff its first slot is inside the row's KV span; null-padded
        # table entries sit past ceil(kv_len/bs) so this skips those too
        @pl.when(page_no * block_size < kv_len)
        def _fold(k_ref=k_refs[p], v_ref=v_refs[p], page_no=page_no):
            k = k_ref[0]          # (bs, KV, hd)
            v = v_ref[0]
            for h in range(kv_heads):
                q = q_ref[0, h]   # (R, hd)
                s = jnp.dot(q, k[:, h, :].T,
                            preferred_element_type=jnp.float32) * scale
                kpos = page_no * block_size + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 1)
                live = (kpos <= qpos) & (kpos < kv_len)
                s = jnp.where(live, s, NEG_INF)
                # rows fully masked in this page contribute at m == NEG_INF;
                # the first real score's alpha rescale annihilates them, and
                # every row with qpos >= 0 sees page 0 — so nothing survives
                m_prev = m_ref[h]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
                pexp = jnp.exp(s - m_new[:, None])
                alpha = jnp.exp(m_prev - m_new)
                l_ref[h] = l_ref[h] * alpha + jnp.sum(pexp, axis=1)
                acc_ref[h] = (acc_ref[h] * alpha[:, None]
                              + jnp.dot(pexp.astype(v.dtype), v[:, h, :],
                                        preferred_element_type=jnp.float32))
                m_ref[h] = m_new

    @pl.when(j == steps - 1)
    def _store():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


def paged_attention_kernel(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_tables: jax.Array,
                           q_pos: jax.Array, kv_lens: jax.Array,
                           pages_per_fetch: int = 1,
                           interpret: bool = False) -> jax.Array:
    """q (B,KV,R,hd); pages (N,bs,KV,hd); block_tables (B,M) int32;
    q_pos (B,R) int32 per-row causal bound (row attends to kpos <= q_pos);
    kv_lens (B,) int32 valid KV entries per row (must be >= 1)
    -> (B,KV,R,hd).

    Each row's softmax runs over positions {kpos : kpos <= q_pos[row] and
    kpos < kv_lens[batch]} of the table-ordered span.  The table is padded
    with null (0) entries past ceil(kv_lens/bs) — those pages are skipped.
    """
    b, kv_heads, r, hd = q.shape
    _, bs, kv2, hd2 = k_pages.shape
    assert (kv_heads, hd) == (kv2, hd2), "q / pages head layout mismatch"
    assert v_pages.shape == k_pages.shape
    m = block_tables.shape[1]
    p_f = max(1, min(pages_per_fetch, m))
    pad = (-m) % p_f
    if pad:
        # pad with null blocks: past every row's length, skipped by pl.when
        block_tables = jnp.pad(block_tables, ((0, 0), (0, pad)))
        m += pad
    steps = m // p_f
    scale = 1.0 / math.sqrt(hd)

    page_spec = [
        pl.BlockSpec((1, bs, kv_heads, hd),
                     lambda b, j, tables, lens, p=p: (tables[b, j * p_f + p],
                                                      0, 0, 0))
        for p in range(p_f)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,      # block_tables, kv_lens
        grid=(b, steps),
        in_specs=[
            # q_pos rides as (B, R, 1): a block's last two dims must equal
            # the array's (or tile by (8, 128)), and (R, 1) is both whole and
            # already column-shaped for the (R, bs) score mask
            pl.BlockSpec((1, r, 1), lambda b, j, tables, lens: (b, 0, 0)),
            pl.BlockSpec((1, kv_heads, r, hd),
                         lambda b, j, tables, lens: (b, 0, 0, 0)),
        ] + page_spec + page_spec,
        out_specs=pl.BlockSpec((1, kv_heads, r, hd),
                               lambda b, j, tables, lens: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kv_heads, r), jnp.float32),
            pltpu.VMEM((kv_heads, r), jnp.float32),
            pltpu.VMEM((kv_heads, r, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, block_size=bs,
                          kv_heads=kv_heads, pages_per_fetch=p_f,
                          steps=steps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv_heads, r, hd), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), kv_lens.astype(jnp.int32),
      q_pos.astype(jnp.int32).reshape(b, r, 1), q,
      *([k_pages] * p_f), *([v_pages] * p_f))
