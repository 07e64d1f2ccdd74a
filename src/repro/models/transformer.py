"""Decoder-only transformer covering the dense, moe, and vlm families.

Layers are *scanned* (weights stacked on a leading axis) so the lowered HLO is
depth-independent — essential for compiling 80-layer models in the multi-pod
dry-run.  MoE-every-2 archs scan over "super-layers" of (dense layer, MoE
layer) so the scan body stays homogeneous.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models import attention as attn
from repro.models import lora as lora_mod
from repro.models import moe as moe_lib
from repro.models.layers import (
    apply_mlp, embed_tokens, init_embed, init_mlp, logits_from_hidden,
    rms_norm, softmax_cross_entropy,
)


def _layer_kind(cfg: ModelConfig, layer_idx_in_super: int) -> str:
    if cfg.moe is None:
        return "dense"
    if cfg.moe.every == 1:
        return "moe"
    # every=k: last layer of the super-layer is MoE, the rest dense
    return "moe" if layer_idx_in_super == cfg.moe.every - 1 else "dense"


def init_layer(cfg: ModelConfig, rng, kind: str, dtype):
    r = jax.random.split(rng, 3)
    p = {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "attn": attn.init_attention(cfg, r[0], dtype),
    }
    if kind == "moe":
        p["moe"] = moe_lib.init_moe(cfg, r[1], dtype)
    else:
        d_ff = cfg.moe.d_ff_dense if (cfg.moe and cfg.moe.d_ff_dense) else cfg.d_ff
        p["mlp"] = init_mlp(cfg, r[1], d_ff, dtype)
    return p


def init_lm(cfg: ModelConfig, rng) -> Dict:
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    every = cfg.moe.every if cfg.moe else 1
    n_super = cfg.n_layers // every
    r = jax.random.split(rng, 2 + n_super * every)

    def stack_layers(kind_idx):
        keys = [r[2 + i * every + kind_idx] for i in range(n_super)]
        kind = _layer_kind(cfg, kind_idx)
        return jax.tree.map(lambda *xs: jnp.stack(xs),
                            *[init_layer(cfg, k, kind, dtype) for k in keys])

    params = {
        "embed": init_embed(cfg, r[0], dtype),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
        "layers": tuple(stack_layers(j) for j in range(every)),
    }
    return params


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _ffn(cfg: ModelConfig, lp, h: jax.Array, decode: bool,
         lora: Optional[dict] = None) -> Tuple[jax.Array, jax.Array]:
    if "moe" in lp:
        # MoE experts are per-token routed; per-tenant deltas there would
        # need per-(token, expert) gathers — MoE archs get attention-only
        # LoRA (adapters.adapted_projections omits the MLP for them)
        if decode:
            from repro.perf import perf
            if perf().moe_decode == "dispatch":
                return moe_lib.apply_moe_decode_dispatch(cfg, lp["moe"], h), \
                    jnp.float32(0)
            return moe_lib.apply_moe_decode(cfg, lp["moe"], h), jnp.float32(0)
        return moe_lib.apply_moe(cfg, lp["moe"], h)
    return apply_mlp(cfg, lp["mlp"], h, lora=lora), jnp.float32(0)


def _layer_fwd(cfg: ModelConfig, lp, x: jax.Array, positions: jax.Array,
               impl: Optional[str]) -> Tuple[jax.Array, jax.Array]:
    from repro.perf import perf
    seq_axis = "seq_mp" if perf().seq_parallel else None
    h = x + attn.attention_block(cfg, lp["attn"], rms_norm(x, lp["ln1"], cfg.norm_eps),
                                 positions, causal=True, impl=impl)
    h = constrain(h, "batch", seq_axis, None)
    y, aux = _ffn(cfg, lp, rms_norm(h, lp["ln2"], cfg.norm_eps), decode=False)
    return constrain(h + y, "batch", seq_axis, None), aux


def forward_hidden(cfg: ModelConfig, params, embeds: jax.Array,
                   positions: jax.Array, remat: bool = False,
                   impl: Optional[str] = None) -> Tuple[jax.Array, jax.Array]:
    """embeds (B,S,d) -> (hidden (B,S,d), moe_aux scalar)."""
    sub_stacks = params["layers"]

    def body(x, lps):
        aux_total = jnp.float32(0)
        for lp in lps:
            x, aux = _layer_fwd(cfg, lp, x, positions, impl)
            aux_total = aux_total + aux
        return x, aux_total

    if remat:
        from repro.perf import remat_policy_fn
        body = jax.checkpoint(body, policy=remat_policy_fn())
    x, auxs = jax.lax.scan(body, embeds, sub_stacks)
    return rms_norm(x, params["final_norm"], cfg.norm_eps), jnp.sum(auxs)


def lm_loss(cfg: ModelConfig, params, batch: Dict, remat: bool = True) -> jax.Array:
    if "embeds" in batch:  # vlm stub frontend
        embeds, positions = batch["embeds"], batch["positions"]
    else:
        embeds = embed_tokens(params["embed"], batch["tokens"])
        b, s = batch["tokens"].shape
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    h, aux = forward_hidden(cfg, params, embeds, positions, remat=remat)
    logits = logits_from_hidden(cfg, params["embed"], h)
    return softmax_cross_entropy(logits, batch["labels"]) + 0.01 * aux


# ---------------------------------------------------------------------------
# Serving: prefill + decode with stacked KV caches
# ---------------------------------------------------------------------------

def _collect_kv_layer(cfg, lp, x, positions, impl):
    """Layer fwd that also returns this layer's (k, v) for the cache."""
    xn = rms_norm(x, lp["ln1"], cfg.norm_eps)
    q, k, v = attn.qkv_project(cfg, lp["attn"], xn, positions)
    o = attn.multi_head_attention(q, k, v, causal=True, impl=impl)
    b, s = x.shape[:2]
    from repro.distributed.sharding import weight_use
    h = x + jnp.einsum("bse,ed->bsd", o.reshape(b, s, cfg.q_dim),
                       weight_use(lp["attn"]["wo"], "heads", None))
    y, _ = _ffn(cfg, lp, rms_norm(h, lp["ln2"], cfg.norm_eps), decode=False)
    return constrain(h + y, "batch", None, None), (k, v)


def lm_prefill(cfg: ModelConfig, params, batch: Dict,
               impl: Optional[str] = None) -> Tuple[Dict, jax.Array]:
    """Returns (cache, last-position logits (B,V)). Cache capacity == S."""
    if "embeds" in batch:
        embeds, positions = batch["embeds"], batch["positions"]
    else:
        b, s = batch["tokens"].shape
        embeds = embed_tokens(params["embed"], batch["tokens"])
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))

    def body(x, lps):
        kvs = []
        for lp in lps:
            x, kv = _collect_kv_layer(cfg, lp, x, positions, impl)
            kvs.append(kv)
        return x, tuple(kvs)

    x, kvs = jax.lax.scan(body, embeds, params["layers"])
    h = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = logits_from_hidden(cfg, params["embed"], h[:, -1:, :])[:, 0, :]
    # Cache is stored SLOT-MAJOR: [slot0 layers..., slot1 layers...]; decode
    # slices it the same way, so ordering is consistent end-to-end.
    ks = jnp.concatenate([kv[0] for kv in kvs], axis=0) if len(kvs) > 1 else kvs[0][0]
    vs = jnp.concatenate([kv[1] for kv in kvs], axis=0) if len(kvs) > 1 else kvs[0][1]
    cache = {"k": ks, "v": vs}
    return cache, logits


def make_decode_cache(cfg: ModelConfig, batch_size: int, max_len: int, dtype):
    hd = cfg.resolved_head_dim
    shape = (cfg.n_layers, batch_size, max_len, cfg.n_kv_heads, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def make_paged_cache(cfg: ModelConfig, num_blocks: int, block_size: int, dtype):
    """Block-pool KV cache shared by all in-flight requests: live memory
    scales with tokens actually written, not max_batch x max_len.  Block 0 is
    the null block (see repro.serve.paged_cache)."""
    hd = cfg.resolved_head_dim
    shape = (cfg.n_layers, num_blocks, block_size, cfg.n_kv_heads, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def lm_decode_step(cfg: ModelConfig, params, cache: Dict, batch: Dict,
                   impl: Optional[str] = None) -> Tuple[Dict, jax.Array]:
    """One decode step.  batch: {"token" (B,1) | "embeds" (B,1,d), "cur_len" ()}.

    cache: {"k": (L,B,Smax,KV,hd), "v": ...}; the new token's K/V are written
    at cur_len; logits for the new token are returned.
    """
    cur_len = batch["cur_len"]
    if "embeds" in batch:
        x, positions = batch["embeds"], batch["positions"]
    else:
        x = embed_tokens(params["embed"], batch["token"])
        b = batch["token"].shape[0]
        positions = jnp.broadcast_to(cur_len[None, None], (b, 1)).astype(jnp.int32)

    def body(x, xs):
        lps, kcs, vcs = xs
        new_kc, new_vc = [], []
        for i, lp in enumerate(lps):
            kc, vc = kcs[i], vcs[i]
            xn = rms_norm(x, lp["ln1"], cfg.norm_eps)
            o, kc, vc = attn.attention_decode_block(cfg, lp["attn"], xn, kc, vc,
                                                    cur_len, positions)
            h = x + o
            y, _ = _ffn(cfg, lp, rms_norm(h, lp["ln2"], cfg.norm_eps), decode=True)
            x = h + y
            new_kc.append(kc)
            new_vc.append(vc)
        return x, (tuple(new_kc), tuple(new_vc))

    every, k_slots, v_slots = _slot_major_split(cfg, cache)
    x, (new_k, new_v) = jax.lax.scan(body, x, (params["layers"], k_slots, v_slots))
    cache = _slot_major_merge(new_k, new_v, every)
    h = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = logits_from_hidden(cfg, params["embed"], h)[:, 0, :]
    return cache, logits


def _slot_major_split(cfg: ModelConfig, cache: Dict):
    """Slice a cache's leading (L, ...) slabs into per-super-layer stacks —
    the slot-major convention lm_prefill established, shared by the dense and
    paged cache layouts."""
    every = cfg.moe.every if cfg.moe else 1
    n_super = cfg.n_layers // every
    k_slots = tuple(cache["k"][i * n_super:(i + 1) * n_super] for i in range(every))
    v_slots = tuple(cache["v"][i * n_super:(i + 1) * n_super] for i in range(every))
    return every, k_slots, v_slots


def _slot_major_merge(new_k, new_v, every: int) -> Dict:
    return {"k": jnp.concatenate(new_k, axis=0) if every > 1 else new_k[0],
            "v": jnp.concatenate(new_v, axis=0) if every > 1 else new_v[0]}


# ---------------------------------------------------------------------------
# Paged serving: block-table-aware chunked prefill + decode
# ---------------------------------------------------------------------------


# Block indices are TRACED scalars: baking them in as constants would
# recompile the scatter for every distinct (src, dst) pair — one jit per
# cache shape instead, shared across all blocks and all engines.  On a
# mesh-sharded slab the same jits compile a second, partitioned executable
# (jit caches per input sharding): the copy runs shard-local, the read
# gathers one block's head-slices to host, the write re-splits them —
# DeviceTier._pin re-asserts the slab sharding after each update.
_paged_copy_jit = jax.jit(
    lambda c, src, dst: {k: v.at[:, dst].set(v[:, src]) for k, v in c.items()})
_paged_read_jit = jax.jit(lambda c, idx: {k: v[:, idx] for k, v in c.items()})
_paged_write_jit = jax.jit(
    lambda c, idx, data: {k: v.at[:, idx].set(data[k].astype(v.dtype))
                          for k, v in c.items()})


def paged_block_copy(cache: Dict, src, dst) -> Dict:
    """Device-side copy of one KV block (all layers): the copy-on-write data
    plane for ``repro.serve.kv_store`` — a shared block is duplicated on
    device before a holder writes into it, so sharers never see each other's
    tokens."""
    return _paged_copy_jit(cache, jnp.int32(src), jnp.int32(dst))


def paged_block_read(cache: Dict, idx) -> Dict:
    """Block ``idx`` -> host numpy {(k|v): (L, bs, KV, hd)} — the device->host
    half of a swap_out (bf16 round-trips bit-exactly through ml_dtypes)."""
    import numpy as np
    return {k: np.asarray(v)
            for k, v in _paged_read_jit(cache, jnp.int32(idx)).items()}


def paged_block_write(cache: Dict, idx, data: Dict) -> Dict:
    """Host numpy block -> device block ``idx`` (the swap_in half)."""
    return _paged_write_jit(cache, jnp.int32(idx),
                            {k: jnp.asarray(v) for k, v in data.items()})


def lm_decode_step_paged(cfg: ModelConfig, params, cache: Dict, batch: Dict):
    """One decode step over a paged cache.

    batch: {"token" (B,1) int32, "block_tables" (B,M) int32,
    "seq_lens" (B,) int32}.  Every row sits at its own position — no shared
    ``cur_len`` — which is what makes continuous batching (rows at wildly
    different depths) exact instead of aligned-and-masked.

    The whole KV pool rides the layer scan's carry: each ``(L, N, bs, KV,
    hd)`` leaf is viewed as ``(L*N, bs, KV, hd)`` (merging the leading axes
    leaves the tiled minor axes alone, so the view is a bitcast), and layer
    ``l`` adds ``l*N`` to the block tables, so a table's null entry 0
    becomes layer l's own null block.  Each layer scatters its new K/V into
    the carried pool and reads it there: no layer's slab is sliced out and
    stacked back, and with the pool donated the program updates it in
    place.  The scan runs over super-layers; slot ``i`` of super-layer ``s``
    is layer ``i * n_super + s``, the slot-major order ``lm_prefill``
    stores.
    """
    seq_lens = batch["seq_lens"].astype(jnp.int32)
    tables = batch["block_tables"].astype(jnp.int32)
    x = embed_tokens(params["embed"], batch["token"])
    every = cfg.moe.every if cfg.moe else 1
    n_super = cfg.n_layers // every
    shape = cache["k"].shape
    n_blocks = shape[1]
    flat = (shape[0] * n_blocks,) + shape[2:]
    # multi-LoRA: per-row adapter slot ids + stacked slabs ride the batch
    # only when the engine has adapters loaded — absent, not even a zero-add
    # is traced (the adapter_id=None bitwise-identity contract)
    lora = batch.get("lora")
    lora_ids = None if lora is None else lora["ids"].astype(jnp.int32)

    def body(carry, xs):
        x, k_pool, v_pool = carry
        lps, s, lsl = xs if lora is not None else (*xs, None)
        for i, lp in enumerate(lps):
            ll = None if lsl is None else {"ids": lora_ids,
                                           "slabs": lsl[i]}
            xn = rms_norm(x, lp["ln1"], cfg.norm_eps)
            o, k_pool, v_pool = attn.attention_decode_block_paged(
                cfg, lp["attn"], xn, k_pool, v_pool,
                tables + (i * n_super + s) * n_blocks, seq_lens, lora=ll)
            h = x + o
            y, _ = _ffn(cfg, lp, rms_norm(h, lp["ln2"], cfg.norm_eps),
                        decode=True, lora=ll)
            x = h + y
        return (x, k_pool, v_pool), None

    xs = (params["layers"], jnp.arange(n_super, dtype=jnp.int32))
    if lora is not None:
        xs = xs + (lora_mod.split_layers(lora, every),)
    (x, k_pool, v_pool), _ = jax.lax.scan(
        body, (x, cache["k"].reshape(flat), cache["v"].reshape(flat)), xs)
    h = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = logits_from_hidden(cfg, params["embed"], h)[:, 0, :]
    return {"k": k_pool.reshape(shape), "v": v_pool.reshape(shape)}, logits


def lm_prefill_chunk(cfg: ModelConfig, params, cache: Dict, batch: Dict,
                     m_used: Optional[int] = None):
    """Process one prompt chunk for a single request into the paged cache.

    batch: {"tokens" (1,C) int32 (null-padded past the prompt),
    "block_table" (1,M) int32, "start" () int32 — absolute position of the
    chunk's first token, "prompt_len" () int32 — the chunk's write limit:
    positions >= it are padding whose KV goes to the null block (the engine
    passes the chunk's end, which on the final chunk is the true prompt
    length)}.  Returns (cache, logits (1,C,V)) — the engine reads the logit
    row of the prompt's last token from the final chunk.

    ``m_used`` (static int) restricts attention to the table's first blocks
    — the engine passes ceil((start+C)/block_size), so early chunks don't
    gather/stream the request's full table capacity.  One retrace per
    distinct value, bounded by max_blocks_per_seq.

    Note for MoE archs: expert capacity is computed per forward call, so a
    chunked prefill can route/drop tokens slightly differently than one full
    prefill of the same prompt.  Dense archs are bit-identical to lm_prefill.
    """
    start = batch["start"].astype(jnp.int32)
    prompt_len = batch["prompt_len"].astype(jnp.int32)
    table = batch["block_table"].astype(jnp.int32)
    c = batch["tokens"].shape[1]
    chunk_pos = start + jnp.arange(c, dtype=jnp.int32)
    x = embed_tokens(params["embed"], batch["tokens"])
    every, k_slots, v_slots = _slot_major_split(cfg, cache)
    # prefill runs one request per call: "lora" carries a single-element ids
    # row broadcast over the chunk (see lm_decode_step_paged for the shape)
    lora = batch.get("lora")
    lora_ids = None if lora is None else lora["ids"].astype(jnp.int32)
    lora_slots = lora_mod.split_layers(lora, every)

    def body(x, xs):
        lps, kcs, vcs, lsl = xs if lora is not None else (*xs, None)
        new_kc, new_vc = [], []
        for i, lp in enumerate(lps):
            kc, vc = kcs[i], vcs[i]
            ll = None if lsl is None else {"ids": lora_ids,
                                           "slabs": lsl[i]}
            xn = rms_norm(x, lp["ln1"], cfg.norm_eps)
            o, kc, vc = attn.attention_prefill_chunk_block(
                cfg, lp["attn"], xn, kc, vc, table, chunk_pos, prompt_len,
                m_used=m_used, lora=ll)
            h = x + o
            y, _ = _ffn(cfg, lp, rms_norm(h, lp["ln2"], cfg.norm_eps),
                        decode=False, lora=ll)
            x = h + y
            new_kc.append(kc)
            new_vc.append(vc)
        return x, (tuple(new_kc), tuple(new_vc))

    xs = (params["layers"], k_slots, v_slots)
    if lora is not None:
        xs = xs + (lora_slots,)
    x, (new_k, new_v) = jax.lax.scan(body, x, xs)
    h = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = logits_from_hidden(cfg, params["embed"], h)
    return _slot_major_merge(new_k, new_v, every), logits
