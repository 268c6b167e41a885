"""Order statistics used by the benchmark: percentiles of a sample and
the quartile spread that bounds are set from."""
from __future__ import annotations

import statistics
from typing import Sequence

import numpy as np


def percentile(samples: Sequence[float], q: float) -> float:
    """The q-th percentile with linear interpolation (numpy's default);
    NaN for an empty sample, so no one mistakes "no data" for zero."""
    if len(samples) == 0:
        return float("nan")
    return float(np.percentile(np.asarray(samples, np.float64), q))


def quartile_spread(values: Sequence[float]) -> float:
    """Distance between the first and the third quartile as a share of
    the median, with Python's ``statistics.quantiles(values, n=4)``."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / abs(q2)
