"""Seeded draws shared by the traffic kinds.

Lengths and gaps are STRATIFIED: a run of n requests holds the n mid-quantiles
of the distribution, in an order drawn from the seed. Every seed then offers the
same multiset of lengths and gaps (the same amount of work in the same time) and
only their order differs, which is what keeps runs of one cell within a few
percent of each other; the marginal distribution is the one named.
"""

import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def _norm_ppf(u):
    return np.array([_NORMAL.inv_cdf(float(x)) for x in u])


def mid_quantiles(n, rng):
    """The n mid-quantiles (i + 1/2) / n in an order drawn from ``rng``."""
    return (rng.permutation(n) + 0.5) / n


def lengths(spec, n, rng):
    """n integer lengths from a length spec:
    ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}`` or
    ``{"dist": "uniform", "min": a, "max": b}`` (both ends included)."""
    u = mid_quantiles(n, rng)
    if spec["dist"] == "lognormal":
        x = spec["median"] * np.exp(spec["sigma"] * _norm_ppf(u))
    elif spec["dist"] == "uniform":
        x = spec["min"] + u * (spec["max"] + 1 - spec["min"]) - 0.5
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(rate, n, rng):
    """n gaps of a Poisson process of ``rate`` per second."""
    return -np.log1p(-mid_quantiles(n, rng)) / rate


def tokens(rng, vocab_size, n):
    return rng.integers(0, vocab_size, n, dtype=np.int64).astype(np.int32)
