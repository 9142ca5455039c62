"""Exponential of mean 1: the gaps of Poisson arrivals (a generator
scales them to its rate)."""
import numpy as np


def quantile(u, spec):
    return -np.log1p(-u)
