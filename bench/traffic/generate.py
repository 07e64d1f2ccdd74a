"""The general traffic generator: a mix file's parameters and a seed ->
the requests of one run.

Every seed gets the same work: lengths and gaps are the quantiles of
their distributions at the stratified points (i + 0.5) / N, in an order
drawn once from ``ORDER_SEED``, so the sizes and arrivals are fixed by the
mix and the window length.  The seed draws the prompts' token ids (and,
elsewhere, the weights).  Two seeds therefore offer the same load in the
same order, which keeps the queueing a run sees, and so its latencies,
from changing with the seed; the same longest prompt also means the same
programs to warm up.  Every request decodes greedily, so the reference
can check each served token.

Mix file keys (any other is refused):

  arrival        {"kind": "poisson", "rate_per_s": r}: open loop, N =
                 round(r * seconds) requests due at the cumulated gaps
  prompt_tokens  {"dist": "lognormal", "median", "sigma", "min", "max"}
  output_tokens  or {"dist": "uniform", "min", "max"} (inclusive)
  about          one line on who sends such traffic
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Dict, List

import numpy as np

WARMUP_TOKENS = 4
ORDER_SEED = 0
MIX_KEYS = {"about", "arrival", "prompt_tokens", "output_tokens"}


@dataclasses.dataclass(frozen=True)
class Ask:
    """One greedy request as the client sends it."""
    index: int
    prompt: List[int]
    max_tokens: int
    due_s: float = 0.0          # seconds after the window's start


@dataclasses.dataclass(frozen=True)
class Schedule:
    asks: List[Ask]             # in due order
    warmup: List[Ask] = dataclasses.field(default_factory=list)


def _points(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: Dict, n: int) -> np.ndarray:
    """The ``n`` stratified quantiles of a length distribution, as ints."""
    p = _points(n)
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in p])
        v = np.rint(float(dist["median"]) * np.exp(float(dist["sigma"]) * z))
    elif dist["dist"] == "uniform":
        v = np.floor(lo + p * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(v, lo, hi).astype(np.int64)


def poisson_gaps(rate: float, n: int) -> np.ndarray:
    """Stratified quantiles of the exponential inter-arrival gap."""
    return -np.log1p(-_points(n)) / rate


def request_count(mix: Dict, seconds: float) -> int:
    extra = set(mix) - MIX_KEYS
    if extra:
        raise ValueError(f"mix keys the generator does not read: "
                         f"{sorted(extra)}")
    arr = mix["arrival"]
    if arr["kind"] != "poisson":
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    return max(1, int(round(float(arr["rate_per_s"]) * seconds)))


def make_schedule(mix: Dict, seed: int, seconds: float,
                  vocab: int) -> Schedule:
    n = request_count(mix, seconds)
    order = np.random.default_rng(ORDER_SEED)
    plens = lengths(mix["prompt_tokens"], n)[order.permutation(n)]
    olens = lengths(mix["output_tokens"], n)[order.permutation(n)]
    gaps = poisson_gaps(float(mix["arrival"]["rate_per_s"]), n)
    due = np.cumsum(gaps[order.permutation(n)])
    rng = np.random.default_rng(seed)
    asks = [Ask(index=i,
                prompt=rng.integers(1, vocab, size=int(plens[i])).tolist(),
                max_tokens=int(olens[i]), due_s=float(due[i]))
            for i in range(n)]
    return Schedule(asks=asks, warmup=warmup_asks(mix, seed, vocab, n))


def warmup_asks(mix: Dict, seed: int, vocab: int, n: int) -> List[Ask]:
    """Requests that run every program the mix will: the longest prompt
    walks every prefill span the mix's prompts visit, one a token shorter
    ends in a part-filled block (whose first decode write copies it, the
    prefix registry holding the block too), and all of them decode
    together."""
    rng = np.random.default_rng([seed, 1])
    plens = lengths(mix["prompt_tokens"], n)
    top = int(plens.max())
    sizes = sorted({top, max(top - 1, 1), int(plens.min()),
                    int(np.median(plens))}, reverse=True)
    return [Ask(index=-1 - i, prompt=rng.integers(1, vocab, size=p).tolist(),
                max_tokens=WARMUP_TOKENS)
            for i, p in enumerate(sizes)]


def longest_request(mix: Dict) -> int:
    """Upper bound on prompt + output tokens of any request of the mix."""
    return int(mix["prompt_tokens"]["max"]) + int(
        mix["output_tokens"]["max"])
