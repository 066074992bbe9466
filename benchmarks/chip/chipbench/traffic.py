"""Open-loop traffic from a mix's parameters and a seed.

The inter-arrival gaps are the quantiles of an exponential distribution
(a Poisson process at the mix's rate), the output lengths the quantiles
of the mix's length distribution, rounded to whole tokens. Their order is
fixed: drawn once from ``ORDER_SEED``, the same for every run. Near the knee the order alone moves the latency's median by a
quarter (a queue at 0.8 of capacity is that sensitive), so a seed that
chose the order would change the work; the run's seed draws the prompt
tokens, and elsewhere the weights and the check's sample.

The order is shuffled within strata: the requests fall into consecutive
blocks of the mix's ``block`` requests, and each block gets one value
from every stratum of the sorted gaps and of the sorted lengths, so every
stretch of ``block`` requests offers the same load. Pure numpy: the load
generator's process imports this and never JAX.
"""

from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

# Sub-streams of one seed, so that prompts, weights and the check's
# sample never share random numbers; the mix's order has one of its own.
STREAM_TRAFFIC, STREAM_WEIGHTS, STREAM_SAMPLE, STREAM_ORDER = 0, 1, 2, 3
ORDER_SEED = 1


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([abs(int(seed)), stream]))


def seed32(seed: int, stream: int) -> int:
    """A 32-bit seed for JAX's PRNG, derived from any whole number."""
    return int(np.random.SeedSequence([abs(int(seed)), stream]).generate_state(1)[0])


@dataclass(frozen=True)
class Request:
    index: int
    due_s: float  # seconds after the window opens
    prompt: np.ndarray  # (prompt_tokens,) int32
    max_new_tokens: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def output_lengths(spec: dict, n: int) -> np.ndarray:
    """The mix's n output lengths in ascending order."""
    q = _quantiles(n)
    if spec["dist"] == "fixed":
        raw = np.full(n, float(spec["value"]))
    elif spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in q])
        raw = spec["median"] * np.exp(spec["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.round(raw), spec["min"], spec["max"]).astype(np.int64)


def stratified_order(values: np.ndarray, block: int, rng: np.random.Generator) -> np.ndarray:
    """``values`` (ascending) in blocks of ``block``: each block takes one
    value, drawn from rng, of each stratum of ``len(values) // block``
    consecutive values; each block, and the remainder, in an order drawn
    from rng."""
    k = len(values) // block
    strata = np.stack([rng.permutation(row) for row in values[: k * block].reshape(block, k)])
    rows = [rng.permutation(col) for col in strata.T] + [rng.permutation(values[k * block:])]
    return np.concatenate(rows)


def schedule(traffic: dict, seed: int, seconds: float, vocab: int) -> list[Request]:
    """The requests due in a window of ``seconds``, in due order."""
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    order = rng_for(ORDER_SEED, STREAM_ORDER)
    gaps = -np.log1p(-_quantiles(n)) / rate
    gaps *= seconds / gaps.sum()  # n arrivals in [0, seconds): exactly the rate
    block = int(traffic["block"])
    gaps = stratified_order(gaps, block, order)
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    lengths = stratified_order(output_lengths(traffic["output_tokens"], n), block, order)
    plen = int(traffic["prompt_tokens"])
    prompts = rng_for(seed, STREAM_TRAFFIC).integers(0, vocab, (n, plen), dtype=np.int32)
    return [Request(i, float(due[i]), prompts[i], int(lengths[i])) for i in range(n)]
