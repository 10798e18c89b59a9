"""The one general generator: a mix's data file plus a seed gives the work.

Every seed draws the same multiset of sizes and gaps (the quantiles of the
mix's distributions, stratified) in another order, so two seeds offer the
same load and differ only in which request meets which. Where the mix names
an ``order_block`` of K, the order is stratified too: every K consecutive
requests hold one gap, one prompt length and one budget from each of the K
quantile bands, so no seed puts all the long answers at the window's end.
Where it names a ``schedule_seed``, that order is the mix's own and the same
for every run, and ``--seed`` draws the token ids (and the weights): which
size arrives when decides the queueing, so it is part of the traffic, not of
the noise. Token ids are uniform from the seed.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


def quantiles(spec: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` stratified draws ((i + 0.5) / n quantiles) of ``spec``."""
    u = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "constant":
        x = np.full(n, float(spec["value"]))
    elif dist == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"])
    elif dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif dist == "exponential":
        x = -np.log1p(-u) * spec.get("mean", 1.0)
    elif dist == "gamma":  # mean 1 by default, coefficient of variation cv
        shape = 1.0 / spec["cv"] ** 2
        rng = np.random.default_rng(12345)  # quantiles by a fixed sample
        sample = np.sort(rng.gamma(shape, 1.0 / shape, 200_001))
        x = sample[(u * 200_000).astype(int)] * spec.get("mean", 1.0)
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    if "min" in spec or "max" in spec:
        x = np.clip(x, spec.get("min", -math.inf), spec.get("max", math.inf))
    return x


def ordered(values: np.ndarray, rng, block: int = 1) -> np.ndarray:
    """``values`` in an order drawn from ``rng``: a plain permutation, or
    with ``block`` K > 1 one value of each of K quantile bands, shuffled,
    in every K consecutive places."""
    if block <= 1:
        return rng.permutation(values)
    bands = [rng.permutation(b) for b in np.array_split(np.sort(values),
                                                        block)]
    out = []
    for j in range(len(bands[0])):  # array_split: the first are longest
        out.extend(rng.permutation([b[j] for b in bands if j < len(b)]))
    return np.asarray(out)


def _ints(spec, n, rng, block) -> List[int]:
    return [int(round(v)) for v in ordered(quantiles(spec, n), rng, block)]


def serve_requests(mix: Dict[str, Any], seed: int, n: int,
                   vocab: int) -> List[Dict[str, Any]]:
    """``n`` requests of the mix: prompt token ids and an output budget."""
    rng = np.random.default_rng([int(mix.get("schedule_seed", seed)), 1])
    block = int(mix.get("order_block", 1))
    prompts = _ints(mix["prompt_tokens"], n, rng, block)
    budgets = _ints(mix["output_tokens"], n, rng, block)
    ids = np.random.default_rng([seed, 5])
    return [{"tokens": ids.integers(2, vocab, p).tolist(),
             "max_new_tokens": b} for p, b in zip(prompts, budgets)]


def open_schedule(mix: Dict[str, Any], seed: int, seconds: float,
                  vocab: int) -> List[Dict[str, Any]]:
    """Open loop: requests with the instant (seconds from the window's
    start) each is due; inter-arrival gaps have mean 1 / rate_per_s."""
    rate = float(mix["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng([int(mix.get("schedule_seed", seed)), 0])
    spec = dict(mix["arrivals"], mean=1.0 / rate)
    gaps = ordered(quantiles(spec, n), rng, int(mix.get("order_block", 1)))
    due = np.cumsum(gaps) - gaps[0] * 0.5
    reqs = serve_requests(mix, seed, n, vocab)
    return [dict(r, due=float(t)) for r, t in zip(reqs, due) if t < seconds]


def closed_clients(mix: Dict[str, Any], seed: int, per_client: int,
                   vocab: int) -> List[List[Dict[str, Any]]]:
    """Closed loop: for each client, the requests it sends one after the
    other."""
    c = int(mix["clients"])
    reqs = serve_requests(mix, seed, c * per_client, vocab)
    return [reqs[i::c] for i in range(c)]


def train_batch(mix: Dict[str, Any], seed: int, step: int,
                vocab: int) -> Dict[str, np.ndarray]:
    """The batch of ``step``: [batch, seq] token ids and the same shifted
    by one, every row different, made on the host."""
    rng = np.random.default_rng([seed, 2, step])
    t = rng.integers(0, vocab, (mix["batch"], mix["seq"] + 1), dtype=np.int32)
    return {"tokens": t[:, :-1], "targets": t[:, 1:]}
