"""Plain reference forward pass of a dense decoder with grouped-query
attention, RoPE and SwiGLU (Granite, Phi-3 and Llama-style configs), in
float32 ``jax.numpy`` with no cache, no kernel and no batching.

It follows the published equations of the configuration file, including
Granite's four scalar multipliers where the file states them.  It reads
the benchmark's weights in the layout of the served parameter tree
(``embed``/``unembed`` tables, ``final_norm``, and a ``stack`` whose
leaves carry one row per layer) and imports nothing of the program.

``dots="fp8"`` is the control: the same forward with every matrix
product taken on float8 (e4m3) operands, each scaled per tensor by its
largest magnitude, accumulating in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F8_MAX = 448.0   # largest finite float8_e4m3fn


def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, a, b, dots):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if dots == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale.astype(jnp.float32)


def _rope(x, theta):
    """Rotate-half RoPE on (S, heads, head_dim) at positions 0..S-1."""
    S, _, h = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, h, 2, dtype=jnp.float32) / h)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : h // 2], x[..., h // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(c, dots, x, lp):
    S = x.shape[0]
    nq, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or c["hidden_size"] // nq
    eps, res = c["rms_norm_eps"], c.get("residual_multiplier", 1.0)
    a = lp["attn"]
    h = _rms(x, lp["ln1"]["scale"], eps)
    q = _rope(_mm("sd,de->se", h, a["wq"]["w"], dots).reshape(S, nq, hd),
              c["rope_theta"])
    k = _rope(_mm("sd,de->se", h, a["wk"]["w"], dots).reshape(S, nkv, hd),
              c["rope_theta"])
    v = _mm("sd,de->se", h, a["wv"]["w"], dots).reshape(S, nkv, hd)
    # query head i reads key/value head i // (nq // nkv)
    k = jnp.repeat(k, nq // nkv, axis=1)
    v = jnp.repeat(v, nq // nkv, axis=1)
    scale = c.get("attention_multiplier", hd ** -0.5)
    s = _mm("qhd,khd->hqk", q, k, dots) * scale
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = _mm("hqk,khd->qhd", p, v, dots).reshape(S, nq * hd)
    x = x + res * _mm("se,ed->sd", o, a["wo"]["w"], dots)
    h = _rms(x, lp["ln2"]["scale"], eps)
    m = lp["mlp"]
    g = jax.nn.silu(_mm("sd,df->sf", h, m["gate"]["w"], dots))
    u = _mm("sd,df->sf", h, m["up"]["w"], dots)
    x = x + res * _mm("sf,fd->sd", g * u, m["down"]["w"], dots)
    return x, None


def _forward(c, dots, params, tokens):
    vocab = c["vocab_size"]
    table = params["embed"]["table"]
    x = table[tokens].astype(jnp.float32) * c.get("embedding_multiplier", 1.0)
    x, _ = jax.lax.scan(functools.partial(_layer, c, dots), x, params["stack"])
    x = _rms(x, params["final_norm"]["scale"], c["rms_norm_eps"])
    out = params["embed" if c["tie_word_embeddings"] else "unembed"]["table"]
    logits = _mm("sd,vd->sv", x, out[:vocab], dots)
    return logits / c.get("logits_scaling", 1.0)


_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
         "hidden_size", "rms_norm_eps", "residual_multiplier", "rope_theta",
         "attention_multiplier", "embedding_multiplier", "vocab_size",
         "tie_word_embeddings", "logits_scaling")


@functools.lru_cache(maxsize=None)
def _compiled(frozen, dots):
    c = dict(frozen)

    def gaps(params, tokens, positions, served):
        """Per compared position: how far the served token's reference
        logit lies below the reference's best, and the same for the
        token the control puts first (with ``dots="fp8"``)."""
        ref = _forward(c, "f32", params, tokens)[positions]
        best = ref.max(-1)
        got = jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
        out = {"served_gap": best - got}
        if dots == "fp8":
            ctl = _forward(c, "fp8", params, tokens)[positions]
            pick = jnp.argmax(ctl, -1)
            out["control_gap"] = best - jnp.take_along_axis(
                ref, pick[:, None], -1)[:, 0]
        return out

    return jax.jit(gaps)


def logit_gaps(c: dict, params, tokens, positions, served,
               control: bool = False) -> dict:
    """Gaps at ``positions`` of the sequence ``tokens`` (one request's
    prompt and served tokens, padded), where ``served[i]`` is the token
    served from position ``positions[i]``."""
    frozen = tuple((k, c[k]) for k in _KEYS if k in c)
    fn = _compiled(frozen, "fp8" if control else "f32")
    with jax.default_matmul_precision("highest"):
        return fn(params, tokens, positions, served)
