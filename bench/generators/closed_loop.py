"""Closed loop: ``clients`` callers, each sending its next request when
its last one finishes.

Mix keys: ``clients``, ``requests_per_client``, ``prompt_len``,
``output_len`` and ``first_wave_output_len`` (distributions of lengths;
the first wave's outputs are staggered so that completions spread out).

Which lengths each client sends, in which order, is the mix's and the
same for every seed; the seed chooses the prompt tokens and which client
(slot) runs which sequence.  When the seed also shuffled the lengths
among the clients, the requests that a window cuts through changed with
it, and seeds read output rates 2.6% apart where one seed repeated
within 0.4% (one TPU v5e, 51 s windows).
"""
from __future__ import annotations

import traffic_gen as tg

LOOP = "closed"


def make(mix: dict, rate, seconds: float, vocab: int, seed: int,
         root) -> list:
    """Per client, its requests in the order it sends them: the first
    wave, then ``requests_per_client`` more."""
    c, k = mix["clients"], mix["requests_per_client"]
    layout = tg.rng_for(0, 2)          # the mix's arrangement, not the seed's
    first_p = layout.permutation(tg.lengths(mix["prompt_len"], c, root))
    first_o = layout.permutation(tg.lengths(mix["first_wave_output_len"], c, root))
    plens = layout.permutation(tg.lengths(mix["prompt_len"], c * k, root))
    olens = layout.permutation(tg.lengths(mix["output_len"], c * k, root))
    rng = tg.rng_for(seed, 2)
    first = tg.prompts(rng, first_p, vocab)
    rest = tg.prompts(rng, plens, vocab)
    queues = []
    for n, i in enumerate(rng.permutation(c)):
        q = [tg.Req(0.0, first[i], int(first_o[i]), n)]
        q += [tg.Req(0.0, rest[j], int(olens[j]), n) for j in range(i, c * k, c)]
        queues.append(q)
    return queues


def max_tokens(mix: dict) -> int:
    """The longest prompt plus output the mix can send."""
    out = max(mix["output_len"]["max"], mix["first_wave_output_len"]["max"])
    return mix["prompt_len"]["max"] + out
