"""GQA attention: chunked online-softmax (flash-style) prefill/train path and a
single-token decode path.  The chunked jnp implementation doubles as the
oracle for the Pallas flash kernel in ``repro.kernels.flash_attention``.

The implementation to use is selected per-call via ``impl=``:
  * "reference" — pure jnp (runs everywhere; what the dry-run lowers)
  * "pallas"    — ``repro.kernels.flash_attention`` (TPU target; interpret
                  mode on CPU in tests)
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models.layers import apply_rope, default_mrope_sections, rms_norm, truncated_normal

_DEFAULT_IMPL = "reference"
# q chunks of this size bound the live score tensor to (B,H,CHUNK,S_kv):
# the XLA-level analogue of flash attention's online softmax.
Q_CHUNK = 1024


def set_default_impl(impl: str) -> None:
    global _DEFAULT_IMPL
    assert impl in ("reference", "pallas")
    _DEFAULT_IMPL = impl


# Paged-attention tiling chosen by the compiler (repro.pipeline -> Auto
# Schedule -> KernelPlan): the serve engine calls set_paged_plan() with the
# pages-per-fetch its compiled KernelPlan implies before tracing its
# decode/prefill functions.  Module-level like _DEFAULT_IMPL: read at trace
# time, so each engine's jit closures bake in the plan active at build.
_PAGED_PLAN = {"pages_per_fetch": 1}


def set_paged_plan(pages_per_fetch: int) -> None:
    assert pages_per_fetch >= 1
    _PAGED_PLAN["pages_per_fetch"] = int(pages_per_fetch)


def paged_plan() -> dict:
    return dict(_PAGED_PLAN)


# Serve mesh for the paged-attention paths, set by the engine at trace time
# (same pattern as set_paged_plan): when a Mesh with a "model" axis is
# active, the paged scatter + attend runs under shard_map with pages and
# query heads split on that axis — each shard owns KV/n kv heads of every
# block and the H/n query heads grouped under them, so no cross-shard
# arithmetic happens and outputs are BITWISE identical to the single-device
# path (the per-shard outputs are all-gathered, never partial-summed).
_SERVE_MESH = {"mesh": None}


def set_serve_mesh(mesh) -> None:
    """Engine hook: the mesh whose "model" axis shards the KV block pool
    (None = single-device).  Read at trace time by the paged attention
    paths; each engine's jit wrappers set it before tracing, so concurrent
    sharded and unsharded engines bake in their own setting."""
    _SERVE_MESH["mesh"] = mesh


def serve_mesh():
    return _SERVE_MESH["mesh"]


def _serve_shard_mesh(kv_heads: int, q_heads: int):
    """The active serve mesh iff its "model" axis cleanly partitions both
    head counts (GQA groups stay intact per shard); None otherwise."""
    mesh = _SERVE_MESH["mesh"]
    if mesh is None or "model" not in mesh.shape:
        return None
    n = mesh.shape["model"]
    if kv_heads % n or q_heads % n:
        return None
    return mesh


def _paged_impl() -> str:
    """Resolve the paged-attention path: the REPRO_PAGED_ATTN knob, with
    "auto" meaning kernel on TPU and dense gather on CPU (where interpret-
    mode Pallas would be pure emulation)."""
    from repro.perf import perf
    mode = perf().paged_attn
    if mode == "auto":
        return "kernel" if jax.default_backend() != "cpu" else "gather"
    assert mode in ("kernel", "gather"), f"bad REPRO_PAGED_ATTN {mode!r}"
    return mode


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return k
    b, s, kv, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, n_rep, hd)).reshape(
        b, s, kv * n_rep, hd)


def _attend_block(q, k, v, mask_add, scale):
    """q (B,Hq,Sq,hd) k/v (B,Hq,Skv,hd) -> (B,Hq,Sq,hd); f32 softmax.

    Masking is ADDITIVE ((Sq,Skv) f32, broadcast into the softmax fusion):
    a boolean `where` select materializes a (B,H,Sq,Skv) pred tensor, which
    the §Perf loop measured as the dominant HBM-traffic term in train cells.
    """
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if mask_add is not None:
        scores = scores + mask_add
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


def _causal_mask_add(qpos, kpos):
    """(Sq,Skv) f32 additive mask: 0 where visible, -1e30 where masked."""
    return jnp.where(qpos[:, None] >= kpos[None, :], 0.0, -1e30
                     ).astype(jnp.float32)


def multi_head_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         causal: bool = True,
                         q_offset: int = 0,
                         chunk: int = Q_CHUNK,
                         impl: Optional[str] = None) -> jax.Array:
    """q (B,Sq,H,hd), k/v (B,Skv,KV,hd) -> (B,Sq,H,hd).

    Scans over q chunks so the score tensor never exceeds
    (B, H, chunk, Skv) — bounding live memory for 32k prefill.
    """
    impl = impl or _DEFAULT_IMPL
    from repro.perf import perf
    chunk = perf().attn_chunk if chunk == Q_CHUNK else chunk
    b, sq, h, hd = q.shape
    skv, kv = k.shape[1], k.shape[2]
    scale = 1.0 / math.sqrt(hd)
    if impl == "pallas":
        from repro.kernels import ops as kops
        return kops.flash_attention(q, k, v, causal=causal, q_offset=q_offset)

    kh = _repeat_kv(k, h // kv).transpose(0, 2, 1, 3)  # (B,H,Skv,hd)
    vh = _repeat_kv(v, h // kv).transpose(0, 2, 1, 3)
    qh = q.transpose(0, 2, 1, 3)                       # (B,H,Sq,hd)
    kpos = jnp.arange(skv)

    if sq <= 2 * chunk or sq % chunk != 0:
        qpos = q_offset + jnp.arange(sq)
        mask = _causal_mask_add(qpos, kpos)[None, None] if causal else None
        out = _attend_block(qh, kh, vh, mask, scale)
        return out.transpose(0, 2, 1, 3)

    n_chunks = sq // chunk
    qh = qh.reshape(b, h, n_chunks, chunk, hd).transpose(2, 0, 1, 3, 4)

    def step(_, args):
        i, qc = args
        qpos = q_offset + i * chunk + jnp.arange(chunk)
        mask = _causal_mask_add(qpos, kpos)[None, None] if causal else None
        return None, _attend_block(qc, kh, vh, mask, scale)

    _, out = jax.lax.scan(step, None, (jnp.arange(n_chunks), qh))
    out = out.transpose(1, 2, 0, 3, 4).reshape(b, h, sq, hd)
    return out.transpose(0, 2, 1, 3)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cur_len: jax.Array) -> jax.Array:
    """q (B,1,H,hd); caches (B,Smax,KV,hd); positions >= cur_len are masked.

    ``cur_len`` may be a scalar (all rows share one length — the dense slot
    engine's aligned decode) or a (B,) vector of per-request lengths (the
    paged engine's continuous batching, where every row is at its own
    position).
    """
    b, _, h, hd = q.shape
    kv = k_cache.shape[2]
    scale = 1.0 / math.sqrt(hd)
    kh = _repeat_kv(k_cache, h // kv)
    vh = _repeat_kv(v_cache, h // kv)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kh).astype(jnp.float32) * scale
    kpos = jnp.arange(k_cache.shape[1])
    lens = jnp.asarray(cur_len)
    if lens.ndim == 0:
        mask_add = jnp.where(kpos < lens, 0.0, -1e30
                             ).astype(jnp.float32)[None, None, None, :]
    else:
        mask_add = jnp.where(kpos[None, :] < lens[:, None], 0.0, -1e30
                             ).astype(jnp.float32)[:, None, None, :]
    scores = scores + mask_add
    probs = jax.nn.softmax(scores, axis=-1).astype(vh.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vh)
    return out


# ---------------------------------------------------------------------------
# Paged KV: block-pool scatter/gather attention
# ---------------------------------------------------------------------------

def paged_gather(pages: jax.Array, tables: jax.Array) -> jax.Array:
    """pages (N,bs,KV,hd), tables (B,M) int32 -> (B, M*bs, KV, hd).

    Linear position within the gathered view equals the token position for
    the owning request (blocks appear in table order), so causal/length masks
    apply directly to the gathered axis.  Null-padded table entries gather
    block 0 — masked out by the caller's length mask.
    """
    b, m = tables.shape
    _, bs, kv, hd = pages.shape
    return pages[tables].reshape(b, m * bs, kv, hd)


def paged_scatter_token(pages: jax.Array, tables: jax.Array,
                        positions: jax.Array, values: jax.Array) -> jax.Array:
    """Write one token's KV per batch row into the block pool.

    pages (N,bs,KV,hd); tables (B,M); positions (B,) token index for each
    row; values (B,KV,hd).  Rows whose table entry is the null block (dead
    batch rows) all collide on block 0 — harmless, block 0 is never read
    unmasked.
    """
    bs = pages.shape[1]
    m = tables.shape[1]
    idx = jnp.clip(positions // bs, 0, m - 1)
    blk = jnp.take_along_axis(tables, idx[:, None], axis=1)[:, 0]
    return pages.at[blk, positions % bs].set(values.astype(pages.dtype))


def _paged_decode_attend(q, k_pages, v_pages, block_tables, seq_lens):
    """Dispatch one decode token's attention over (possibly per-shard)
    pages: the Pallas streaming kernel or the dense-gather fallback.  Under
    shard_map both see only the local KV-head slice; the kernel's grid is
    per KV head, so it partitions over the head axis without changes."""
    if _paged_impl() == "kernel":
        from repro.kernels import ops as kops
        return kops.paged_attention(
            q, k_pages, v_pages, block_tables, seq_lens + 1,
            pages_per_fetch=_PAGED_PLAN["pages_per_fetch"])
    kg = paged_gather(k_pages, block_tables)
    vg = paged_gather(v_pages, block_tables)
    return decode_attention(q, kg, vg, seq_lens + 1)


def attention_decode_block_paged(cfg: ModelConfig, p, x: jax.Array,
                                 k_pages: jax.Array, v_pages: jax.Array,
                                 block_tables: jax.Array, seq_lens: jax.Array,
                                 lora: Optional[dict] = None):
    """One-token attention against a paged cache.

    x (B,1,d); pages (N,bs,KV,hd); block_tables (B,M); seq_lens (B,) — the
    number of KV entries already written for each row (the new token's KV is
    written at position seq_lens[b]).  Returns (out, k_pages, v_pages).

    When a serve mesh is active (``set_serve_mesh``), the scatter + attend
    runs under shard_map with pages, new-token KV, and query heads all split
    on the "model" axis: each shard writes and attends its own KV heads
    (q heads grouped under them, so GQA never crosses a shard), and the
    per-shard outputs are all-gathered — bitwise identical to single-device
    because no reduction ever spans shards.
    """
    positions = seq_lens[:, None].astype(jnp.int32)
    q, k, v = qkv_project(cfg, p, x, positions, lora=lora)
    mesh = _serve_shard_mesh(k_pages.shape[2], q.shape[2])
    if mesh is None:
        k_pages = paged_scatter_token(k_pages, block_tables, seq_lens, k[:, 0])
        v_pages = paged_scatter_token(v_pages, block_tables, seq_lens, v[:, 0])
        o = _paged_decode_attend(q, k_pages, v_pages, block_tables, seq_lens)
    else:
        from jax.sharding import PartitionSpec as P
        from repro.distributed.param_sharding import serve_tp_reduce_scatter
        hs = P(None, None, "model", None)    # heads/kv axis of q, k, v, pages
        # Under reduce-scatter TP the per-shard outputs STAY head-sharded:
        # the row-parallel wo consumes them locally and the layer's single
        # all-reduce happens on its partial sums instead of gathering o here.
        rs = serve_tp_reduce_scatter()

        def body(q_l, k_l, v_l, kp_l, vp_l, tables, lens):
            kp_l = paged_scatter_token(kp_l, tables, lens, k_l[:, 0])
            vp_l = paged_scatter_token(vp_l, tables, lens, v_l[:, 0])
            o_l = _paged_decode_attend(q_l, kp_l, vp_l, tables, lens)
            if not rs:
                o_l = jax.lax.all_gather(o_l, "model", axis=2, tiled=True)
            return o_l, kp_l, vp_l

        o, k_pages, v_pages = jax.shard_map(
            body, mesh=mesh,
            in_specs=(hs, hs, hs, hs, hs, P(None, None), P(None)),
            out_specs=(hs if rs else P(None, None, None, None), hs, hs),
            check_vma=False)(q, k, v, k_pages, v_pages, block_tables, seq_lens)
    b = x.shape[0]
    from repro.distributed.sharding import weight_use
    from repro.models import lora as lora_mod
    oh = o.reshape(b, 1, cfg.q_dim)
    out = lora_mod.add_delta(
        "o", jnp.einsum("bse,ed->bsd", oh,
                        weight_use(p["wo"], "heads", None)), oh, lora)
    return out, k_pages, v_pages


def attention_prefill_chunk_block(cfg: ModelConfig, p, x: jax.Array,
                                  k_pages: jax.Array, v_pages: jax.Array,
                                  block_table: jax.Array, chunk_pos: jax.Array,
                                  prompt_len: jax.Array,
                                  m_used: Optional[int] = None,
                                  lora: Optional[dict] = None):
    """One prompt chunk's attention against the paged cache (batch of 1).

    x (1,C,d); block_table (1,M); chunk_pos (C,) absolute token positions of
    the chunk (start..start+C-1); prompt_len () — positions >= prompt_len are
    padding (their KV goes to the null block, their outputs are discarded by
    the engine).  The chunk attends to every previously-written position plus
    itself, causally — this is what lets prefill proceed in small chunks
    interleaved with decode steps without ever stalling the decode batch.

    ``m_used`` (static) bounds the attended span to the table's first
    ``m_used`` blocks — the engine passes ceil((start+C)/bs), so a chunk
    never re-gathers (or re-streams) the full table capacity, only the
    blocks written so far.  Positions past the chunk are causally masked
    either way; this is purely a traffic/FLOP win.
    """
    q, k, v = qkv_project(cfg, p, x, chunk_pos[None, :], lora=lora)
    bs = k_pages.shape[1]
    if m_used is not None:
        block_table = block_table[:, :min(m_used, block_table.shape[1])]
    m = block_table.shape[1]
    valid = chunk_pos < prompt_len
    idx = jnp.clip(chunk_pos // bs, 0, m - 1)
    blk = jnp.where(valid, block_table[0, idx], 0)
    off = chunk_pos % bs
    c = x.shape[1]
    mesh = _serve_shard_mesh(k_pages.shape[2], q.shape[2])
    if mesh is None:
        k_pages = k_pages.at[blk, off].set(k[0].astype(k_pages.dtype))
        v_pages = v_pages.at[blk, off].set(v[0].astype(v_pages.dtype))
        o = _paged_prefill_attend(cfg, q, k_pages, v_pages, block_table,
                                  chunk_pos)
    else:
        # shard_map over the kv-heads axis, mirroring the decode path: each
        # shard scatters and attends its own KV-head slice of the chunk,
        # then the head-split outputs are all-gathered (no cross-shard sums)
        # — except under reduce-scatter TP, where they stay head-sharded for
        # the row-parallel wo (one all-reduce on its partial sums instead)
        from jax.sharding import PartitionSpec as P
        from repro.distributed.param_sharding import serve_tp_reduce_scatter
        hs = P(None, None, "model", None)
        rs = serve_tp_reduce_scatter()

        def body(q_l, k_l, v_l, kp_l, vp_l, table, blk_, off_, cpos):
            kp_l = kp_l.at[blk_, off_].set(k_l[0].astype(kp_l.dtype))
            vp_l = vp_l.at[blk_, off_].set(v_l[0].astype(vp_l.dtype))
            o_l = _paged_prefill_attend(cfg, q_l, kp_l, vp_l, table, cpos)
            if not rs:
                o_l = jax.lax.all_gather(o_l, "model", axis=2, tiled=True)
            return o_l, kp_l, vp_l

        o, k_pages, v_pages = jax.shard_map(
            body, mesh=mesh,
            in_specs=(hs, hs, hs, hs, hs, P(None, None), P(None), P(None),
                      P(None)),
            out_specs=(hs if rs else P(None, None, None, None), hs, hs),
            check_vma=False)(q, k, v, k_pages, v_pages, block_table, blk,
                             off, chunk_pos)
    from repro.distributed.sharding import weight_use
    from repro.models import lora as lora_mod
    oh = o.reshape(1, c, cfg.q_dim)
    out = lora_mod.add_delta(
        "o", jnp.einsum("bse,ed->bsd", oh,
                        weight_use(p["wo"], "heads", None)), oh, lora)
    return out, k_pages, v_pages


def _paged_prefill_attend(cfg: ModelConfig, q, k_pages, v_pages, block_table,
                          chunk_pos):
    """One prefill chunk's attention over (possibly per-shard) pages —
    kernel or gather dispatch, shared by the single-device and shard_map
    paths of ``attention_prefill_chunk_block``."""
    if _paged_impl() == "kernel":
        from repro.kernels import ops as kops
        kv_lens = (chunk_pos[-1] + 1)[None]            # span written so far
        return kops.paged_attention_chunk(
            q, k_pages, v_pages, block_table, chunk_pos, kv_lens,
            pages_per_fetch=_PAGED_PLAN["pages_per_fetch"])
    m, bs = block_table.shape[1], k_pages.shape[1]
    kg = paged_gather(k_pages, block_table)         # (1, m_used*bs, KV, hd)
    vg = paged_gather(v_pages, block_table)
    h_q = q.shape[2]
    kv = kg.shape[2]
    scale = 1.0 / math.sqrt(cfg.resolved_head_dim)
    kh = _repeat_kv(kg, h_q // kv).transpose(0, 2, 1, 3)      # (1,H,m*bs,hd)
    vh = _repeat_kv(vg, h_q // kv).transpose(0, 2, 1, 3)
    qh = q.transpose(0, 2, 1, 3)                              # (1,H,C,hd)
    kpos = jnp.arange(m * bs)
    mask_add = _causal_mask_add(chunk_pos, kpos)[None, None]
    return _attend_block(qh, kh, vh, mask_add, scale).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Full attention layer (projections + rope + qk-norm)
# ---------------------------------------------------------------------------

def init_attention(cfg: ModelConfig, rng, dtype):
    d, qd, kvd, hd = cfg.d_model, cfg.q_dim, cfg.kv_dim, cfg.resolved_head_dim
    r = jax.random.split(rng, 4)
    s = 1.0 / math.sqrt(d)
    p = {
        "wq": truncated_normal(r[0], (d, qd), s, dtype),
        "wk": truncated_normal(r[1], (d, kvd), s, dtype),
        "wv": truncated_normal(r[2], (d, kvd), s, dtype),
        "wo": truncated_normal(r[3], (qd, d), 1.0 / math.sqrt(qd), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def qkv_project(cfg: ModelConfig, p, x: jax.Array, positions: jax.Array,
                lora: Optional[dict] = None):
    """x (B,S,d) -> q (B,S,H,hd), k/v (B,S,KV,hd) with rope + qk-norm.

    ``lora`` (serve-only, see ``repro.models.lora``) adds each batch row's
    own adapter delta to the q/k/v projections before reshape/norm/rope;
    None (every non-serve caller) traces the exact pre-LoRA graph."""
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    from repro.distributed.sharding import weight_use
    from repro.models import lora as lora_mod
    q = lora_mod.add_delta("q", jnp.einsum(
        "bsd,de->bse", x, weight_use(p["wq"], None, "heads")), x, lora
        ).reshape(b, s, cfg.n_heads, hd)
    k = lora_mod.add_delta("k", jnp.einsum(
        "bsd,de->bse", x, weight_use(p["wk"], None, "kv")), x, lora
        ).reshape(b, s, cfg.n_kv_heads, hd)
    v = lora_mod.add_delta("v", jnp.einsum(
        "bsd,de->bse", x, weight_use(p["wv"], None, "kv")), x, lora
        ).reshape(b, s, cfg.n_kv_heads, hd)
    q = constrain(q, "batch", None, "heads", None)
    k = constrain(k, "batch", None, "kv", None)
    v = constrain(v, "batch", None, "kv", None)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.rope != "none":
        sections = default_mrope_sections(hd) if cfg.rope == "mrope" else None
        q = apply_rope(q, positions, cfg.rope_theta, sections)
        k = apply_rope(k, positions, cfg.rope_theta, sections)
    return q, k, v


def attention_block(cfg: ModelConfig, p, x: jax.Array, positions: jax.Array,
                    causal: bool = True, impl: Optional[str] = None) -> jax.Array:
    q, k, v = qkv_project(cfg, p, x, positions)
    o = multi_head_attention(q, k, v, causal=causal, impl=impl)
    o = constrain(o, "batch", None, "heads", None)
    b, s = x.shape[:2]
    from repro.distributed.sharding import weight_use
    return jnp.einsum("bse,ed->bsd", o.reshape(b, s, cfg.q_dim),
                      weight_use(p["wo"], "heads", None))


def attention_decode_block(cfg: ModelConfig, p, x: jax.Array,
                           k_cache: jax.Array, v_cache: jax.Array,
                           cur_len: jax.Array, positions: jax.Array):
    """One-token attention; returns (out, new_k_cache, new_v_cache)."""
    q, k, v = qkv_project(cfg, p, x, positions)
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k.astype(k_cache.dtype), cur_len, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v.astype(v_cache.dtype), cur_len, axis=1)
    o = decode_attention(q, k_cache, v_cache, cur_len + 1)
    b = x.shape[0]
    from repro.distributed.sharding import weight_use
    out = jnp.einsum("bse,ed->bsd", o.reshape(b, 1, cfg.q_dim),
                     weight_use(p["wo"], "heads", None))
    return out, k_cache, v_cache
