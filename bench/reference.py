"""The reference module of dense SwiGLU RoPE decoders (Qwen3, Phi-3): the
benchmark's weights, its plain float32 reference of the served model,
independent of the program's model code, and the model's FLOP and byte
counts.  A configuration names it under ``reference``; the harness and the
metric readers take every family-specific fact from it:

    program_config(conf, base)      the program's ModelConfig, or ValueError
                                    for a family this module does not cover
    weight_layout, make_weights     the weights, from the seed
    logits_at(..., quant=None)      the reference's logits; "fp8" the control
    decode_step_flops, prefill_chunk_flops,
    paged_decode_attention_flops, paged_decode_attention_bytes

``make_weights`` draws a configuration's weights from the seed on the
device, in one jitted call, in bfloat16 (the type they are served in),
arranged as the serving engine takes them.  The reference reads the same
arrays by name and computes a Qwen3 / Phi-3 style decoder in float32 with
every matmul at ``Precision.HIGHEST``:

    x = embed[tokens]
    per layer:  h = x + Wo . attn(rope(norm_q(Wq . rms(x))), rope(norm_k(Wk . rms(x))), Wv . rms(x))
                x = h + Wdown . (silu(Wgate . rms(h)) * (Wup . rms(h)))
    logits = rms(x) . Wout        (Wout = embed^T when tied)

with grouped-query attention, causal masking and rotate-half RoPE.  The
q/k norms (Qwen3's per-head RMSNorm) are on when the config says
``qk_norm``.  It runs one sequence at a time, one layer per call, so it
fits beside the weights on one chip.

``quant="fp8"`` is the control: every linear layer's inputs are rounded
to float8 e4m3 (a scale per token row of the activations and per output
column of the weights), accumulated in float32; attention stays float32.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Iterable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops
from bench.flops import Dims, dims

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def jax_key(seed: int):
    """A PRNG key from a seed of up to 64 bits: JAX's own seeding keeps
    only the low 32 bits, so two large seeds could share a key."""
    s = int(seed) % 2**64
    key = jax.random.key(np.uint32(s & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(s >> 32))


def program_config(conf: Dict, base):
    """The registry's ModelConfig ``base`` with the file's numbers laid
    onto it; a family this reference does not compute is refused."""
    if base.family != "dense" or base.act != "swiglu" or base.rope != "rope":
        raise ValueError(f"the dense reference covers dense SwiGLU RoPE "
                         f"decoders, not {base.family}/{base.act}/{base.rope}")
    m = dims(conf)
    return dataclasses.replace(
        base, n_layers=m.layers, d_model=m.d, n_heads=m.heads,
        n_kv_heads=m.kv_heads, d_ff=m.ff, vocab=m.vocab, head_dim=m.head_dim,
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        qk_norm=bool(conf.get("qk_norm", False)), dtype=conf["torch_dtype"])


def weight_layout(conf: Dict) -> Dict:
    """{path: (shape, kind)} of every weight, in the engine's layout:
    layers stacked on a leading axis; kind "normal" or "ones"."""
    m = dims(conf)
    q, kv = m.heads * m.head_dim, m.kv_heads * m.head_dim
    L = m.layers
    out = {
        ("embed", "embed"): ((m.vocab, m.d), "normal"),
        ("final_norm",): ((m.d,), "ones"),
        ("layers", "ln1"): ((L, m.d), "ones"),
        ("layers", "ln2"): ((L, m.d), "ones"),
        ("layers", "attn", "wq"): ((L, m.d, q), "normal"),
        ("layers", "attn", "wk"): ((L, m.d, kv), "normal"),
        ("layers", "attn", "wv"): ((L, m.d, kv), "normal"),
        ("layers", "attn", "wo"): ((L, q, m.d), "normal"),
        ("layers", "mlp", "wi_gate"): ((L, m.d, m.ff), "normal"),
        ("layers", "mlp", "wi_up"): ((L, m.d, m.ff), "normal"),
        ("layers", "mlp", "wo"): ((L, m.ff, m.d), "normal"),
    }
    if conf.get("qk_norm"):
        out[("layers", "attn", "q_norm")] = ((L, m.head_dim), "ones")
        out[("layers", "attn", "k_norm")] = ((L, m.head_dim), "ones")
    if not conf["tie_word_embeddings"]:
        out[("embed", "unembed")] = ((m.d, m.vocab), "normal")
    return out


def _nest(flat: Dict) -> Dict:
    tree: Dict = {}
    for path, leaf in flat.items():
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = leaf
    # the engine scans over a tuple of layer stacks (one stack: no
    # interleaved layer kinds in these models)
    tree["layers"] = (tree["layers"],)
    return tree


def make_weights(conf: Dict, seed: int):
    """All weights from ``seed``, made on the device in one jitted call.
    Matrices are N(0, initializer_range^2) as the published configs'
    initializer_range states; norm scales are 1.  Stacked layers are drawn
    one layer at a time so float32 temporaries stay one layer large."""
    layout = weight_layout(conf)
    std = float(conf["initializer_range"])
    dtype = jnp.dtype(conf["torch_dtype"])

    def normal(key, shape):
        return (std * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)

    def build(key):
        flat = {}
        for i, (path, (shape, kind)) in enumerate(sorted(layout.items())):
            if kind == "ones":
                flat[path] = jnp.ones(shape, dtype)
            elif path[0] == "layers":
                keys = jax.random.split(jax.random.fold_in(key, i), shape[0])
                flat[path] = jax.lax.map(lambda k: normal(k, shape[1:]), keys)
            else:
                flat[path] = normal(jax.random.fold_in(key, i), shape)
        return _nest(flat)

    return jax.jit(build)(jax_key(seed))


# ---------------------------------------------------------------------------
# Reference forward
# ---------------------------------------------------------------------------

def _fp8_round(x, axis: int):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def _linear(x, w, quant: Optional[str]):
    """x (..., k) f32 times w (k, n) in its stored type, in f32."""
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _fp8_round(x, -1), _fp8_round(w, 0)
    return jnp.einsum("...k,kn->...n", x, w, precision=HIGHEST)


def _rms(x, w, eps: float):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, positions, theta: float):
    """Rotate-half RoPE; x (S, heads, hd), positions (S,)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.lru_cache(maxsize=None)
def _layer_fn(m: Dims, eps: float, theta: float, qk_norm: bool,
              quant: Optional[str]):
    hd, group = m.head_dim, m.heads // m.kv_heads

    def layer(x, stack, i):
        """x (S, d) f32 through layer ``i`` of the stacked weights."""
        w = jax.tree.map(lambda a: a[i], stack)
        s = x.shape[0]
        pos = jnp.arange(s)
        h = _rms(x, w["ln1"], eps)
        q = _linear(h, w["attn"]["wq"], quant).reshape(s, m.heads, hd)
        k = _linear(h, w["attn"]["wk"], quant).reshape(s, m.kv_heads, hd)
        v = _linear(h, w["attn"]["wv"], quant).reshape(s, m.kv_heads, hd)
        if qk_norm:
            q = _rms(q, w["attn"]["q_norm"], eps)
            k = _rms(k, w["attn"]["k_norm"], eps)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, k,
                            precision=HIGHEST) / math.sqrt(hd)
        causal = pos[:, None] >= pos[None, :]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)
        x = x + _linear(o.reshape(s, m.heads * hd), w["attn"]["wo"], quant)
        h = _rms(x, w["ln2"], eps)
        g = _linear(h, w["mlp"]["wi_gate"], quant)
        u = _linear(h, w["mlp"]["wi_up"], quant)
        return x + _linear(jax.nn.silu(g) * u, w["mlp"]["wo"], quant)

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float, tied: bool, quant: Optional[str]):
    def head(x, rows, final_norm, out_w):
        """Logits (R, V) f32 of hidden rows ``rows`` of x (S, d)."""
        h = _rms(x[rows], final_norm, eps)
        w = out_w.T if tied else out_w
        return _linear(h, w, quant)

    return jax.jit(head)


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(jnp.float32)


def logits_at(conf: Dict, params, tokens: Sequence[int], rows: Sequence[int],
              pad_to: int, quant: Optional[str] = None):
    """Logits (len(rows), V) f32 at positions ``rows`` of one sequence.
    The sequence is padded to ``pad_to`` so every call shares one program;
    padding sits after every row read and the mask is causal."""
    m = dims(conf)
    eps, theta = float(conf["rms_norm_eps"]), float(conf["rope_theta"])
    tied = bool(conf["tie_word_embeddings"])
    toks = np.zeros(pad_to, np.int32)
    toks[:len(tokens)] = tokens
    x = _embed(params["embed"]["embed"], jnp.asarray(toks))
    stack = params["layers"][0]
    layer = _layer_fn(m, eps, theta, bool(conf.get("qk_norm")), quant)
    for i in range(m.layers):
        x = layer(x, stack, np.int32(i))
    out_w = params["embed"]["embed"] if tied else params["embed"]["unembed"]
    r = np.zeros(pad_to, np.int32)          # fixed row count: one program
    r[:len(rows)] = rows
    logits = _head_fn(eps, tied, quant)(x, jnp.asarray(r),
                                        params["final_norm"], out_w)
    return logits[:len(rows)]


# ---------------------------------------------------------------------------
# FLOPs and bytes (bench/flops.py), as the metric readers ask for them
# ---------------------------------------------------------------------------

def decode_step_flops(conf: Dict, spans: Iterable[int]) -> int:
    return flops.decode_step_flops(dims(conf), spans)


def prefill_chunk_flops(conf: Dict, start: int, end: int, last: bool) -> int:
    return flops.prefill_chunk_flops(dims(conf), start, end, last)


def paged_decode_attention_flops(conf: Dict, spans: Iterable[int]) -> int:
    return flops.paged_decode_attention_flops(dims(conf), spans)


def paged_decode_attention_bytes(conf: Dict, spans: Iterable[int]) -> int:
    return flops.paged_decode_attention_bytes(dims(conf), spans)
