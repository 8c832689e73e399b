"""Lengths and gaps for the traffic mixes.

Every seed gets the SAME multiset of sizes and gaps, in another order: the
values are the evenly spaced quantiles of the distribution (a stratified
sample), and the seed only shuffles them. A run's work then does not depend
on the luck of the draw, so runs with different seeds can be compared.
"""

import math
import statistics

_NORMAL = statistics.NormalDist()


def quantile(spec, u):
    """The ``u``-quantile (0 < u < 1) of the distribution ``spec``."""
    kind = spec["dist"]
    if kind == "lognormal":
        x = math.exp(math.log(spec["median"])
                     + spec["sigma"] * _NORMAL.inv_cdf(u))
    elif kind == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"])
    elif kind == "exponential":        # mean 1; the caller scales
        return -math.log1p(-u)
    elif kind == "fixed":
        return spec["value"]
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return min(spec["max"], max(spec["min"], x))


def stratified(spec, n, rng, integer=True):
    """``n`` values: the quantiles (i + 0.5) / n, shuffled by ``rng``."""
    vals = [quantile(spec, (i + 0.5) / n) for i in range(n)]
    if integer:
        vals = [int(round(v)) for v in vals]
    rng.shuffle(vals)
    return vals


def tokens(rng, n, vocab):
    """``n`` uniform random token ids (0 and 1 are left to pad and eos)."""
    return rng.choices(range(2, vocab), k=n)
