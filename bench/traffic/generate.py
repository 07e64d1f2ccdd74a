"""The general traffic generator: a mix file's parameters and a seed ->
the requests of one run.

Every seed gets the same work: lengths and gaps are the quantiles of
their distributions at the stratified points (i + 0.5) / N, in an order
drawn once from ``ORDER_SEED``, so the sizes and arrivals are fixed by the
mix and the window length; so is which requests decode greedily.  The seed
draws the prompts' token ids and each sampled request's sampling seed
(and, elsewhere, the weights).  Two seeds therefore offer the same load in
the same order, which keeps the queueing a run sees, and so its
latencies, from changing with the seed; the same longest prompt also
means the same programs to warm up.

Mix file keys (any other is refused):

  arrival        {"kind": "poisson", "rate_per_s": r}: open loop, N =
                 round(r * seconds) requests due at the cumulated gaps;
                 or {"kind": "closed", "clients": c, "requests": n}:
                 c clients, each sending the next of the n requests in
                 order once its previous answer has ended
  prompt_tokens  {"dist": "lognormal", "median", "sigma", "min", "max"}
  output_tokens  or {"dist": "uniform", "min", "max"} (inclusive)
  sampling       optional {"temperature": t, "top_k": k, "greedy_share": g}:
                 round(g * N) requests decode greedily, the others sample
                 at temperature t from the k likeliest tokens; without it
                 every request is greedy
  about          one line on who sends such traffic
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Dict, List

import numpy as np

WARMUP_TOKENS = 4
ORDER_SEED = 0
RAMP_S = 10.0       # closed loop: clients start this long before the window
MIX_KEYS = {"about", "arrival", "prompt_tokens", "output_tokens", "sampling"}


@dataclasses.dataclass(frozen=True)
class Ask:
    """One request as the client sends it; greedy unless ``temperature``
    is above 0."""
    index: int
    prompt: List[int]
    max_tokens: int
    due_s: float = 0.0          # open loop: seconds after the window's start
    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0

    @property
    def rank_allowed(self) -> int:
        """How far down the reference's ranking a served token may lie:
        1 for greedy, ``top_k`` for a sampled request."""
        return self.top_k if self.temperature > 0 else 1


@dataclasses.dataclass(frozen=True)
class Schedule:
    asks: List[Ask]             # in due (open loop) or sending order
    warmup: List[Ask] = dataclasses.field(default_factory=list)
    clients: int = 0            # closed loop: clients; 0 for an open loop
    ramp_s: float = 0.0         # from the clients' start to the window's


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
    if arr["kind"] == "poisson":
        return max(1, int(round(float(arr["rate_per_s"]) * seconds)))
    if arr["kind"] == "closed":
        if int(arr.get("clients", 0)) < 1 or int(arr.get("requests", 0)) < 1:
            raise ValueError("a closed loop needs clients and requests >= 1")
        return int(arr["requests"])
    raise ValueError(f"unknown arrival kind {arr['kind']!r}")


def _greedy(mix: Dict, n: int, order) -> np.ndarray:
    """Which requests decode greedily: all without ``sampling``, else the
    first round(g * n) of an order drawn from ``order``."""
    s = mix.get("sampling")
    if s is None:
        return np.ones(n, bool)
    if set(s) != {"temperature", "top_k", "greedy_share"}:
        raise ValueError("sampling needs temperature, top_k and "
                         "greedy_share, and nothing else")
    if not (float(s["temperature"]) > 0 and int(s["top_k"]) >= 1
            and 0.0 <= float(s["greedy_share"]) <= 1.0):
        # top_k bounds every sampled token, which is what the check reads
        raise ValueError("sampling needs temperature > 0, top_k >= 1 and "
                         "greedy_share in [0, 1]")
    greedy = np.zeros(n, bool)
    greedy[order.permutation(n)[:int(round(float(s["greedy_share"]) * n))]] \
        = True
    return greedy


def sampling_seed(seed: int, index: int) -> int:
    """A request's sampling seed: a hash of the run's seed and its index."""
    return int(np.random.SeedSequence([seed, index]).generate_state(
        1, np.uint64)[0] >> 1)


def make_schedule(mix: Dict, seed: int, seconds: float,
                  vocab: int) -> Schedule:
    n = request_count(mix, seconds)
    closed = mix["arrival"]["kind"] == "closed"
    order = np.random.default_rng(ORDER_SEED)
    plens = lengths(mix["prompt_tokens"], n)[order.permutation(n)]
    olens = lengths(mix["output_tokens"], n)[order.permutation(n)]
    if closed:
        due = np.zeros(n)
    else:
        gaps = poisson_gaps(float(mix["arrival"]["rate_per_s"]), n)
        due = np.cumsum(gaps[order.permutation(n)])
    greedy = _greedy(mix, n, order)
    s = mix.get("sampling")
    rng = np.random.default_rng(seed)
    asks = []
    for i in range(n):
        sampled = {} if greedy[i] else {
            "temperature": float(s["temperature"]), "top_k": int(s["top_k"]),
            "seed": sampling_seed(seed, i)}
        asks.append(Ask(
            index=i, prompt=rng.integers(1, vocab, size=int(plens[i])).tolist(),
            max_tokens=int(olens[i]), due_s=float(due[i]), **sampled))
    return Schedule(asks=asks, warmup=warmup_asks(mix, seed, vocab, n),
                    clients=int(mix["arrival"]["clients"]) if closed else 0,
                    ramp_s=RAMP_S if closed else 0.0)


def warmup_asks(mix: Dict, seed: int, vocab: int, n: int) -> List[Ask]:
    """Requests that run every program the mix will: the longest prompt
    walks every prefill span the mix's prompts visit, one a token shorter
    ends in a part-filled block (whose first decode write copies it, the
    prefix registry holding the block too), and all of them decode
    together.  Sampling runs on the host, so greedy warm-up covers it."""
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
