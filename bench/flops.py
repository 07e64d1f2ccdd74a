"""Operations and bytes the served model needs, computed from shapes.

All counts are of the work the algorithm needs, not of what a program
happens to do: a prefill chunk's padding rows, logits no one reads and the
null pages a kernel fetches are not counted.  A multiply-add is 2 FLOPs.
Softmax, norms, rope and activations are left out (under 1% of a step's
FLOPs at these widths); so is the embedding lookup, which is a gather.
"""
from __future__ import annotations

from typing import Dict, Iterable, NamedTuple

BF16 = 2   # bytes


class Dims(NamedTuple):
    d: int
    ff: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int


def dims(conf: Dict) -> Dims:
    """Widths from a configuration file (Hugging Face key names)."""
    d, h = int(conf["hidden_size"]), int(conf["num_attention_heads"])
    return Dims(d=d, ff=int(conf["intermediate_size"]),
                layers=int(conf["num_hidden_layers"]), heads=h,
                kv_heads=int(conf["num_key_value_heads"]),
                head_dim=int(conf.get("head_dim") or d // h),
                vocab=int(conf["vocab_size"]))


def layer_matmul_params(m: Dims) -> int:
    """Weights one token multiplies through in one layer: q, k, v, o
    projections and the gated MLP."""
    q, kv = m.heads * m.head_dim, m.kv_heads * m.head_dim
    return m.d * q + 2 * m.d * kv + q * m.d + 3 * m.d * m.ff


def attention_flops(m: Dims, span: int) -> int:
    """One query token, all layers, against ``span`` cached positions:
    q.k and p.v, 2 FLOPs a multiply-add each."""
    return m.layers * 4 * m.heads * m.head_dim * span


def decode_step_flops(m: Dims, spans: Iterable[int]) -> int:
    """One batched decode step; ``spans`` holds each live row's attended
    positions (its written KV plus the new token)."""
    per_token = 2 * (m.layers * layer_matmul_params(m) + m.d * m.vocab)
    return sum(per_token + attention_flops(m, s) for s in spans)


def prefill_chunk_flops(m: Dims, start: int, end: int, last: bool) -> int:
    """Prompt positions ``start..end-1`` of one request; ``last`` when the
    chunk holds the prompt's final token, whose logits are needed."""
    n = end - start
    out = 2 * m.layers * layer_matmul_params(m) * n
    out += sum(attention_flops(m, p + 1) for p in range(start, end))
    return out + (2 * m.d * m.vocab if last else 0)


def paged_decode_attention_bytes(m: Dims, spans: Iterable[int]) -> int:
    """HBM bytes decode attention needs in one step, all layers: each live
    row's K and V over its span, plus its q in and its output out."""
    kv_token = 2 * m.kv_heads * m.head_dim * BF16
    q_and_o = 2 * m.heads * m.head_dim * BF16
    return m.layers * sum(s * kv_token + q_and_o for s in spans)


def paged_decode_attention_flops(m: Dims, spans: Iterable[int]) -> int:
    return sum(attention_flops(m, s) for s in spans)


def least_seconds(flops: float, nbytes: float, peaks: Dict) -> tuple:
    """(least time on the chip, the bound that sets it)."""
    t_flops = flops / float(peaks["bf16_flops_per_s"])
    t_bytes = nbytes / float(peaks["hbm_bytes_per_s"])
    return (t_bytes, "memory") if t_bytes >= t_flops else (t_flops, "compute")
