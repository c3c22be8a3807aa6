"""Percentiles and the distance between two outputs, one definition
for the whole benchmark."""
import math

import numpy as np


def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation between
    order statistics (numpy's default). Empty input is an error: a
    tail of nothing is not 0."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values):
    return percentile(values, 50)


def row_errors(got, want):
    """How far ``got`` is from ``want``, row by row (a row is the last
    axis: the vocabulary of one position): the RMS of the difference
    over the row, as a share of the standard deviation of ``want`` over
    all its elements. 0 is agreement; an unrelated output of the same
    spread reads about 1.4."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape:
        raise ValueError(f"shapes differ: {got.shape} and {want.shape}")
    diff = np.sqrt(np.mean(np.square(got - want), axis=-1))
    return (diff / float(want.std())).reshape(-1)
