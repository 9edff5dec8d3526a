"""The arithmetic of the metrics.  Quantiles are numpy's linear ones over
all samples; a geometric mean is over the per-type medians (TPC-H clause
5.4.1's shape), so a gain on one type shows and none hides in a pool."""

import math

import numpy as np


def median(xs):
    return float(np.median(np.asarray(xs, dtype=np.float64)))


def quantile(xs, q):
    return float(np.quantile(np.asarray(xs, dtype=np.float64), q))


def geomean(xs):
    return float(math.exp(sum(math.log(x) for x in xs) / len(xs)))


def reduce(xs, how):
    if how == "median":
        return median(xs)
    if how == "p95":
        return quantile(xs, 0.95)
    if how == "max":
        return float(np.max(np.asarray(xs, dtype=np.float64)))
    raise ValueError(f"unknown reduction {how!r}")
