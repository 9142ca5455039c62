"""Uniform over the whole numbers ``min`` to ``max``."""
import numpy as np


def quantile(u, spec):
    lo, hi = spec["min"], spec["max"]
    return np.floor(lo + u * (hi - lo + 1))
