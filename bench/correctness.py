"""What decides ``correct``: the served tokens of a sample of the
window's finished requests against the configuration's plain float32
reference (``bench/reference/<name>.py``, named in its file).

For each sampled request the reference runs once over its prompt and its
served tokens.  At every position that served a token, the gap is how
far the served token's reference logit lies below the reference's best
logit there; the widest gap over the sample is compared with the cell's
limit (``bench/checks/<cell>.json``).  Greedy decoding in the served
precision picks a token whose gap is at most the rounding of the logits;
a wrong kernel, a wrong mask or a lower precision picks worse ones.
Served ids outside the vocabulary and requests that stopped short are
counted apart, each with the limit 0.

The control (``control=True``, never in a benchmark run) is the
reference in fp8 put in the program's place: at the same positions of the
same sample, the token it puts first is judged by the same numbers and
limits, and has to come out not correct.
"""
from __future__ import annotations

import json
import pathlib

import numpy as np

from traffic_gen import rng_for

CHECK_POSITIONS = 512     # compared positions per request, padded


def load_limits(cell: str, root: pathlib.Path) -> dict:
    return json.loads((root / "bench" / "checks" / f"{cell}.json").read_text())


def sample(finished: dict, target_tokens: int, seed: int) -> list:
    """rids of finished requests: the one with the most served tokens,
    then others drawn from the seed until ``target_tokens`` are in."""
    if not finished:
        return []
    rids = sorted(finished)
    longest = max(rids, key=lambda r: (len(finished[r]), -r))
    rest = [r for r in rids if r != longest]
    order = rng_for(seed, 3).permutation(len(rest))
    out, n = [longest], len(finished[longest])
    for i in order:
        if n >= target_tokens:
            break
        out.append(rest[i])
        n += len(finished[rest[i]])
    return out


def gaps_of(ref, c: dict, params, prompt, served, max_len: int,
            control: bool = False) -> dict:
    """Per-position gaps of one request (numpy, unpadded)."""
    import jax.numpy as jnp
    n = len(served)
    if n > CHECK_POSITIONS or len(prompt) + n > max_len:
        raise ValueError(f"request of {len(prompt)}+{n} tokens exceeds the "
                         "reference's padding")
    toks = np.zeros(max_len, np.int32)
    toks[:len(prompt)] = prompt
    toks[len(prompt):len(prompt) + n] = served
    pos = np.zeros(CHECK_POSITIONS, np.int32)
    pos[:n] = len(prompt) - 1 + np.arange(n)
    srv = np.zeros(CHECK_POSITIONS, np.int32)
    srv[:n] = np.clip(served, 0, c["vocab_size"] - 1)
    out = ref.logit_gaps(c, params, jnp.asarray(toks), jnp.asarray(pos),
                               jnp.asarray(srv), control=control)
    return {k: np.asarray(v)[:n] for k, v in out.items()}


def check(ref, c: dict, params, prompts: dict, finished: dict,
          want_len: dict, limits: dict, seed: int, target_tokens: int,
          control: bool = False):
    """The compared numbers of one run, each beside its limit, and with
    ``control`` the same numbers for the control's tokens (else None)."""
    vocab = c["vocab_size"]
    max_len = c["engine"]["max_len"]
    bad_ids = sum(int(((np.asarray(t) < 0) | (np.asarray(t) >= vocab)).sum())
                  for t in finished.values())
    short = sum(1 for r, t in finished.items() if len(t) != want_len[r])
    rids = sample(finished, target_tokens, seed)
    worst, ctl, n_tok = 0.0, 0.0, 0
    for r in rids:
        g = gaps_of(ref, c, params, prompts[r], np.asarray(finished[r]),
                    max_len, control=control)
        worst = max(worst, float(g["served_gap"].max()))
        if control:
            ctl = max(ctl, float(g["control_gap"].max()))
        n_tok += len(g["served_gap"])
    limit = limits["max_logit_gap"]["limit"]
    out = {
        "max_logit_gap": {"value": worst, "limit": limit},
        "ids_outside_vocab": {"value": bad_ids, "limit": 0},
        "requests_cut_short": {"value": short, "limit": 0},
        "tokens_compared": {"value": n_tok, "limit": target_tokens},
        "requests_compared": {"value": len(rids), "limit": 1},
    }
    if not control:
        return out, None
    # the control reads every position the program served, so its counts
    # are the program's; its tokens are argmax over the vocabulary
    return out, dict(out, max_logit_gap={"value": ctl, "limit": limit},
                     ids_outside_vocab={"value": 0, "limit": 0})


def passed(checks: dict) -> bool:
    """Every compared number within its limit (the counts of what was
    compared must reach theirs)."""
    c = checks
    return (c["max_logit_gap"]["value"] <= c["max_logit_gap"]["limit"]
            and c["ids_outside_vocab"]["value"] == 0
            and c["requests_cut_short"]["value"] == 0
            and c["tokens_compared"]["value"] >= c["tokens_compared"]["limit"]
            and c["requests_compared"]["value"] >= 1)
