"""One general generator of open-loop traffic from a mix's parameters.

A mix (``benchmarks/traffic/<name>.json``, ``kind: open_loop``) gives a rate
and the distributions of prompt and output length. The schedule is built in
blocks of ``BLOCK`` requests: inside a block the gaps are the block's
quantiles of the exponential distribution (scaled to the rate exactly) and
the lengths the quantiles of their distributions, each shuffled by the run's
seed. So every seed offers the same set of sizes and arrivals in another
order, and the work offered in a window does not depend on luck.
"""
import math
from statistics import NormalDist

import numpy as np

BLOCK = 64


def _quantiles(n):
    return (np.arange(n) + 0.5) / n


def length_quantiles(dist, n):
    """``n`` lengths: the quantiles of a clipped log-normal."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(int)


def gap_quantiles(rate, n):
    """``n`` exponential gaps whose mean is exactly 1 / rate."""
    g = -np.log1p(-_quantiles(n))
    return g / g.mean() / rate


def schedule(mix, seconds, seed, stream=0):
    """[(due_s, prompt_len, output_len)] of every request due before
    ``seconds``. ``stream`` separates warm-up traffic from the window's."""
    rng = np.random.default_rng([int(seed), int(stream)])
    out, t = [], 0.0
    while t < seconds:
        gaps = rng.permutation(gap_quantiles(mix["rate_per_s"], BLOCK))
        plen = rng.permutation(length_quantiles(mix["prompt_len"], BLOCK))
        olen = rng.permutation(length_quantiles(mix["output_len"], BLOCK))
        for g, p, o in zip(gaps, plen, olen):
            t += float(g)
            if t >= seconds:
                break
            out.append((t, int(p), int(o)))
    return out


def prompt_ids(seed, stream, index, length, vocab_size):
    """Token ids of request ``index`` of ``stream``, from the run's seed."""
    rng = np.random.default_rng([int(seed), int(stream), int(index)])
    return rng.integers(0, vocab_size, (length,)).astype(np.int32)


def nearest_rank(values, q):
    """The q-th percentile by nearest rank (no interpolation): the smallest
    value with at least q% of the sample at or below it."""
    xs = sorted(values)
    return xs[max(0, math.ceil(q / 100.0 * len(xs)) - 1)]
