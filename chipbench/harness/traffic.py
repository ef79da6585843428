"""The one general generator: every traffic mix is a data file of
parameters that this module turns into work, from the seed alone.

Every seed gets the same multiset of sizes and arrival gaps (the
quantiles of the mix's distributions) in another order, so runs on two
seeds do the same amount of work and differ only in how it is laid out.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for one named stream of one seed. ``seed`` is any
    whole number (the driver's are above 2**31)."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def percentile(values, p: float) -> float:
    """The ``p``-th percentile by linear interpolation between the two
    nearest ranks (numpy's default rule), on plain floats."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of nothing")
    rank = (len(xs) - 1) * p / 100.0
    lo = int(math.floor(rank))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def lognormal_quantiles(n: int, dist: dict) -> np.ndarray:
    """``n`` whole lengths: the (i + 1/2)/n quantiles of a log-normal with
    the file's ``median`` and ``sigma``, clipped to ``min`` and ``max``."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def request_sizes(mix: dict, n: int, seed: int):
    """``n`` (prompt length, output length) pairs: both quantile sets,
    each shuffled by the seed, the output cut so that the pair fits
    ``max_total``."""
    prompts = lognormal_quantiles(n, mix["prompt_len"])
    outputs = lognormal_quantiles(n, mix["output_len"])
    rng(seed, 1).shuffle(prompts)
    rng(seed, 2).shuffle(outputs)
    outputs = np.minimum(outputs, mix["max_total"] - prompts)
    if outputs.min() < 1:
        raise ValueError("a prompt leaves no room for an output")
    return [(int(p), int(o)) for p, o in zip(prompts, outputs)]


def prompt_tokens(vocab_size: int, length: int, seed: int, index: int):
    """Request ``index``'s prompt: uniform token ids, no shared prefix."""
    return rng(seed, 1000 + index).integers(
        0, vocab_size, size=length).astype(np.int32)


def poisson_schedule(rate_rps: float, duration_s: float, seed: int):
    """Due times (seconds from the start of sending) of a Poisson stream
    of ``floor(rate * duration)`` requests: the gaps are the quantiles of
    the exponential distribution, shuffled by the seed, so every seed
    sends as many requests over as long, bunched differently."""
    n = int(math.floor(rate_rps * duration_s))
    if n < 1:
        raise ValueError("the schedule holds no request")
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate_rps
    rng(seed, 3).shuffle(gaps)
    return np.cumsum(gaps) - gaps[0]


def unigram_corpus(job: dict, vocab_size: int, rows: int, seed: int):
    """``[rows, seq_len]`` token ids from a skewed unigram distribution
    (p ~ 1 / rank ** skew), each row over its own shuffling of the
    vocabulary: a few optimizer steps already move the loss well below
    ln(vocab), and the rows differ in what they hold and in their loss,
    so that a part of the batch left out moves the batch's mean."""
    p = 1.0 / np.arange(1, vocab_size + 1) ** job["unigram_skew"]
    gen = rng(seed, 4)
    ranks = gen.choice(vocab_size, size=(rows, job["seq_len"]), p=p / p.sum())
    return np.stack([gen.permutation(vocab_size)[r] for r in ranks]
                    ).astype(np.int32)
