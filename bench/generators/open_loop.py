"""Open loop: independent users, each request due at its own time.

Mix keys: ``arrivals`` (a distribution of the gaps, scaled so that the
``rate x seconds`` requests fill the window), ``prompt_len`` and
``output_len`` (distributions of lengths).  The rate is the cell's
(``bench/rates/<cell>.json``).
"""
from __future__ import annotations

import numpy as np

import traffic_gen as tg

LOOP = "open"


def make(mix: dict, rate: float, seconds: float, vocab: int, seed: int,
         root) -> list:
    """The window's requests, in the order they are due."""
    n = max(1, int(round(rate * seconds)))
    rng = tg.rng_for(seed, 1)
    gaps = tg.quantiles(mix["arrivals"], n, root)
    gaps = rng.permutation(gaps * (seconds / gaps.sum()))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    plens = rng.permutation(tg.lengths(mix["prompt_len"], n, root))
    olens = rng.permutation(tg.lengths(mix["output_len"], n, root))
    prompts = tg.prompts(rng, plens, vocab)
    return [tg.Req(float(d), p, int(o)) for d, p, o in zip(due, prompts, olens)]


def max_tokens(mix: dict) -> int:
    """The longest prompt plus output the mix can send."""
    return mix["prompt_len"]["max"] + mix["output_len"]["max"]
