"""The general traffic generator: one data file of parameters per mix.

Every seed draws the same *set* of sizes and inter-arrival gaps.  Sizes
and gaps come in blocks of ``block`` stratified quantiles of their
distribution; the seed draws the request contents (pixels, token ids)
and, unless the mix fixes the order, shuffles each block.  So two seeds
offer the same work at the same rate.

A traffic file holds:

* ``loop``: ``"closed"`` (``clients`` callers, each waiting for its reply;
  an LM mix's first requests are admitted and prefilled in set-up) or
  ``"open"`` (``rate_per_s`` Poisson arrivals, ``warm_in_s`` of the
  same load before the window);
* for model-serving mixes, ``prompt_len`` and ``output_len``:
  ``{"median", "sigma", "min", "max"}`` of a clipped lognormal;
* ``block``: how many requests share one set of quantiles (64 unless
  set);
* ``order``: ``"shuffled"`` (the default: the seed permutes each block)
  or ``"fixed"``: every block in one low-discrepancy order (the ranks of
  a van der Corput sequence; base 2 for prompts, 3 for answers, 5 for
  gaps), so that each stretch of requests spreads over the whole
  distribution and a run that sees a few dozen requests sees the same
  work under every seed;
* ``trace_s``: the traced end of the window of a ``--trace 1`` run.
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

BLOCK = 64


def _quantiles(k: int) -> np.ndarray:
    return (np.arange(k) + 0.5) / k


def fixed_order(k: int, base: int) -> np.ndarray:
    """Quantile index of each of k positions: the rank of the position's
    base-``base`` van der Corput number, so every prefix is spread."""
    def radical_inverse(i: int) -> float:
        f, r = 1.0, 0.0
        while i:
            f /= base
            r += f * (i % base)
            i //= base
        return r

    return np.argsort(np.argsort([radical_inverse(i) for i in range(k)]))


def _blocks(base: np.ndarray, n: int, rng: np.random.Generator,
            order_base: int | None) -> np.ndarray:
    k = -(-n // len(base))
    if order_base is None:
        out = [rng.permutation(base) for _ in range(k)]
    else:
        out = [base[fixed_order(len(base), order_base)]] * k
    return np.concatenate(out)[:n]


def exp_gaps(rate: float, n: int, rng: np.random.Generator,
             block: int = BLOCK, order_base: int | None = None) -> np.ndarray:
    """n inter-arrival gaps of a Poisson process at ``rate``: blocks of
    stratified exponential quantiles, each shuffled or in a fixed order."""
    base = -np.log1p(-_quantiles(block)) / rate
    return _blocks(base, n, rng, order_base)


def lognormal_lengths(dist: dict, n: int, rng: np.random.Generator,
                      block: int = BLOCK,
                      order_base: int | None = None) -> np.ndarray:
    """n integer lengths from a clipped lognormal with the given median:
    blocks of stratified quantiles, each shuffled or in a fixed order."""
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf(float(q)) for q in _quantiles(block)])
    base = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    base = np.clip(np.rint(base), dist["min"], dist["max"]).astype(np.int64)
    return _blocks(base, n, rng, order_base)


def _order_base(traffic: dict, base: int) -> int | None:
    order = traffic.get("order", "shuffled")
    if order not in ("shuffled", "fixed"):
        raise ValueError(f"unknown traffic order {order!r}")
    return base if order == "fixed" else None


@dataclasses.dataclass
class Arrivals:
    """Scheduled arrival offsets (s, from the schedule's start) and the
    lateness of each actual submit, recorded by the driver."""

    offsets: np.ndarray
    late: list = dataclasses.field(default_factory=list)

    def report(self) -> dict:
        if not self.late:
            return {}
        late = np.asarray(self.late) * 1e3
        return dict(late_p50_ms=float(np.percentile(late, 50)),
                    late_p99_ms=float(np.percentile(late, 99)),
                    late_max_ms=float(late.max()), submitted=len(late))


def open_schedule(traffic: dict, seconds: float,
                  rng: np.random.Generator) -> Arrivals:
    """Arrivals covering the warm-in and the window, with one block of
    slack past the end."""
    rate = float(traffic["rate_per_s"])
    block = int(traffic.get("block", BLOCK))
    span = float(traffic.get("warm_in_s", 0.0)) + seconds
    n = int(math.ceil(rate * span)) + block
    return Arrivals(np.cumsum(exp_gaps(rate, n, rng, block,
                                       _order_base(traffic, 5))))


def lm_requests(traffic: dict, n: int, vocab: int,
                rng: np.random.Generator) -> list:
    """n ``(prompt token ids, output length)`` requests."""
    block = int(traffic.get("block", BLOCK))
    p_len = lognormal_lengths(traffic["prompt_len"], n, rng, block,
                              _order_base(traffic, 2))
    o_len = lognormal_lengths(traffic["output_len"], n, rng, block,
                              _order_base(traffic, 3))
    return [(rng.integers(0, vocab, size=int(p), dtype=np.int32), int(o))
            for p, o in zip(p_len, o_len)]


def images(n: int, hw: int, rng: np.random.Generator) -> np.ndarray:
    """n distinct float32 images in [0, 1), (n, hw, hw, 3)."""
    return rng.random((n, hw, hw, 3), dtype=np.float32)
