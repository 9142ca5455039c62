"""Lognormal: ``median`` and ``sigma`` (of the log)."""
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def quantile(u, spec):
    z = np.array([_NORMAL.inv_cdf(float(x)) for x in u])
    return spec["median"] * np.exp(spec["sigma"] * z)
