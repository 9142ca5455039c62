"""What the traffic generators share.  A mix (``bench/traffic/<mix>.json``)
names its generator (``bench/generators/<name>.py``) and, for each length
or gap it draws, a distribution (``bench/distributions/<name>.py``) with
its parameters.

Every seed gets the same multiset of sizes and gaps, in another order:
a distribution is sampled at its quantiles (i + 1/2) / n, and the seed
chooses the order and the prompt tokens, so runs with different seeds do
the same amount of work and differ in how it collides.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

import registry


@dataclass
class Req:
    due_s: float              # seconds after the window opens (open loop)
    prompt: np.ndarray
    max_new_tokens: int
    client: int = -1          # closed loop: the client that sends it


def rng_for(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng([salt, seed & 0xFFFFFFFF, seed >> 32])


def quantiles(spec: dict, n: int, root=registry.ROOT) -> np.ndarray:
    """``n`` values of the distribution ``spec`` names, at its quantiles
    (i + 1/2) / n, clipped to ``min``/``max`` where the spec gives them."""
    u = (np.arange(n) + 0.5) / n
    v = np.asarray(registry.module("distributions", spec["dist"], root)
                   .quantile(u, spec), np.float64)
    return np.clip(v, spec.get("min", -np.inf), spec.get("max", np.inf))


def lengths(spec: dict, n: int, root=registry.ROOT) -> np.ndarray:
    return np.round(quantiles(spec, n, root)).astype(np.int64)


def prompts(rng, lens, vocab: int) -> list:
    return [rng.integers(1, vocab, size=int(n)).astype(np.int32) for n in lens]
