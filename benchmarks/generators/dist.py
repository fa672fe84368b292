"""Length distributions as data: ``{"dist": ..., ...}`` -> n values.

Values are the n evenly spaced quantiles of the distribution, so every
seed gets the same multiset of sizes and only their order changes: the
seed then permutes the work and does not change its amount.
"""
import math
import statistics

_NORMAL = statistics.NormalDist()


def _quantile(spec, u):
    kind = spec["dist"]
    if kind == "lognormal":
        x = math.exp(math.log(spec["median"])
                     + spec["sigma"] * _NORMAL.inv_cdf(u))
    elif kind == "uniform":
        x = spec["min"] + (spec["max"] - spec["min"]) * u
    elif kind == "exponential":
        x = -math.log1p(-u) * spec["mean"]
    else:
        raise ValueError(f"unknown dist {kind!r}")
    return x


def stratified(spec, n):
    """n values at the quantiles (i + 0.5) / n, clipped to
    [min, max] where the spec gives them."""
    out = []
    for i in range(n):
        x = _quantile(spec, (i + 0.5) / n)
        if "min" in spec:
            x = max(spec["min"], x)
        if "max" in spec:
            x = min(spec["max"], x)
        out.append(x)
    return out


def stratified_ints(spec, n):
    return [int(round(x)) for x in stratified(spec, n)]
