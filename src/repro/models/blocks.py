"""Per-family layer blocks and scan-over-layers stack runners.

Stacks are represented as *stacked parameter pytrees* (every leaf carries a
leading ``n_steps`` dim) and executed with ``lax.scan`` so compile time is
O(1) in depth.  Heterogeneous architectures scan over their homogeneous
period: Jamba scans 8-layer periods (1 attn : 7 mamba, MoE on odd layers),
the VLM scans 5-layer periods (4 self-attn + 1 gated cross-attn layer).

Each block body has three modes — train / prefill / decode — selected
statically; a stacked attention KV cache rides the scan's carry, other
caches ride along as scan xs/ys.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention, layers, mamba2, moe as moe_lib
from repro.models.layers import dtype_of
from repro.parallel.axes import constrain

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# sub-layer helpers
# ---------------------------------------------------------------------------
def _mlp_or_moe(p: Params, x: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    """Returns (out, aux_loss)."""
    if "moe" in p:
        B, S, d = x.shape
        y, aux = moe_lib.moe_apply(p["moe"], x, cfg)
        return y, aux
    return layers.mlp(x, p["mlp"]), jnp.zeros((), jnp.float32)


def _init_ffn(key, cfg, use_moe: bool) -> Params:
    dtype = dtype_of(cfg.param_dtype)
    if use_moe:
        return {"moe": moe_lib.init_moe(key, cfg)}
    return {"mlp": layers.init_mlp(key, cfg.d_model, cfg.d_ff, dtype,
                                   cfg.mlp_type)}


def _ffn_specs(cfg, use_moe: bool) -> Params:
    if use_moe:
        return {"moe": moe_lib.moe_specs(cfg)}
    return {"mlp": layers.mlp_specs(cfg.mlp_type)}


# ---------------------------------------------------------------------------
# attention decoder layer (dense / moe families)
# ---------------------------------------------------------------------------
def init_attn_layer(key, cfg, use_moe: bool, cross: bool = False) -> Params:
    k1, k2 = jax.random.split(key)
    dtype = dtype_of(cfg.param_dtype)
    p = {
        "ln1": layers.init_rmsnorm(cfg.d_model, dtype),
        "attn": attention.init_attention(k1, cfg, cross=cross),
        "ln2": layers.init_rmsnorm(cfg.d_model, dtype),
    }
    p.update(_init_ffn(k2, cfg, use_moe))
    return p


def attn_layer_specs(cfg, use_moe: bool, cross: bool = False) -> Params:
    p = {
        "ln1": layers.rmsnorm_specs(),
        "attn": attention.attention_specs(cfg, cross=cross),
        "ln2": layers.rmsnorm_specs(),
    }
    p.update(_ffn_specs(cfg, use_moe))
    return p


def _name_block_out(t):
    """Tag post-collective block outputs for the ``save_blocks`` remat
    policy: saving these tensors lets the backward replay skip the
    tensor-parallel all-reduces (a Megatron-style selective-recompute
    optimization; quantified in EXPERIMENTS.md §Perf)."""
    from jax.ad_checkpoint import checkpoint_name
    return checkpoint_name(t, "block_out")


def attn_layer(p, x, cfg, *, mode, positions, cache=None, causal=True,
               block_causal=True, n_valid=None):
    """One pre-norm decoder layer.  Returns (x, new_cache, aux).

    ``n_valid`` only applies to decode mode — see attention.attn_decode."""
    h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
    if mode == "train":
        a = attention.attn_train(p["attn"], h, cfg, positions=positions,
                                 causal=causal, block_causal=block_causal)
        new_cache = None
    elif mode == "prefill":
        a, new_cache = attention.attn_prefill(
            p["attn"], h, cfg, positions=positions, cache=cache,
            block_causal=block_causal)
    else:
        a, new_cache = attention.attn_decode(
            p["attn"], h, cfg, positions=positions, cache=cache,
            n_valid=n_valid)
    x = x + _name_block_out(a)
    h = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
    f, aux = _mlp_or_moe(p, h, cfg)
    return x + _name_block_out(f), new_cache, aux


# ---------------------------------------------------------------------------
# mamba layer (ssm / hybrid families)
# ---------------------------------------------------------------------------
def init_mamba_layer(key, cfg, use_moe: bool = False,
                     with_ffn: bool = True) -> Params:
    k1, k2 = jax.random.split(key)
    dtype = dtype_of(cfg.param_dtype)
    p = {
        "ln1": layers.init_rmsnorm(cfg.d_model, dtype),
        "mamba": mamba2.init_mamba(k1, cfg),
    }
    if with_ffn and (cfg.d_ff > 0 or use_moe):
        p["ln2"] = layers.init_rmsnorm(cfg.d_model, dtype)
        p.update(_init_ffn(k2, cfg, use_moe))
    return p


def mamba_layer_specs(cfg, use_moe: bool = False, with_ffn: bool = True) -> Params:
    p = {"ln1": layers.rmsnorm_specs(), "mamba": mamba2.mamba_specs(cfg)}
    if with_ffn and (cfg.d_ff > 0 or use_moe):
        p["ln2"] = layers.rmsnorm_specs()
        p.update(_ffn_specs(cfg, use_moe))
    return p


def mamba_layer(p, x, cfg, *, mode, state=None, n_valid=None):
    """``n_valid`` only applies to decode mode — the per-row ragged mask of
    mamba2.mamba_forward's masked recurrence."""
    h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
    y, new_state = mamba2.mamba_forward(
        p["mamba"], h, cfg, state=state if mode == "decode" else None,
        mode=mode, n_valid=n_valid if mode == "decode" else None)
    x = x + _name_block_out(y)
    aux = jnp.zeros((), jnp.float32)
    if "ln2" in p:
        h = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
        f, aux = _mlp_or_moe(p, h, cfg)
        x = x + _name_block_out(f)
    return x, new_state, aux


# ---------------------------------------------------------------------------
# cross-attention layer (vlm / whisper decoder)
# ---------------------------------------------------------------------------
def init_cross_layer(key, cfg, use_moe: bool = False) -> Params:
    k1, k2 = jax.random.split(key)
    dtype = dtype_of(cfg.param_dtype)
    p = {
        "lnx": layers.init_rmsnorm(cfg.d_model, dtype),
        "xattn": attention.init_attention(k1, cfg, cross=True),
        "ln2": layers.init_rmsnorm(cfg.d_model, dtype),
    }
    p.update(_init_ffn(k2, cfg, use_moe))
    return p


def cross_layer_specs(cfg, use_moe: bool = False) -> Params:
    p = {
        "lnx": layers.rmsnorm_specs(),
        "xattn": attention.attention_specs(cfg, cross=True),
        "ln2": layers.rmsnorm_specs(),
    }
    p.update(_ffn_specs(cfg, use_moe))
    return p


def cross_layer(p, x, cfg, *, ctx=None, cached_kv=None):
    """Gated cross-attn + FFN (Llama-3.2-Vision style).  Returns
    (x, new_cross_kv, aux)."""
    h = layers.rms_norm(x, p["lnx"], cfg.norm_eps)
    a, kv = attention.cross_attn(p["xattn"], h, cfg, ctx=ctx,
                                 cached_kv=cached_kv)
    x = x + a
    h = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
    f, aux = _mlp_or_moe(p, h, cfg)
    return x + f, kv, aux


# ---------------------------------------------------------------------------
# stack runner
# ---------------------------------------------------------------------------
def run_stack(
    x: jax.Array,
    stacked_params: Params,
    step_fn: Callable,                 # (x, p_slice, cache_slice) -> (x, new_cache_slice, aux)
    stacked_cache: Optional[Any] = None,
    n_steps: int = 0,
    remat: str = "none",
) -> Tuple[jax.Array, Optional[Any], jax.Array]:
    """Scan ``step_fn`` over stacked layer params (+ optional stacked cache).

    A stacked attention KV cache (``attention.is_kv_cache``: the dense and
    moe layout) rides the scan's carry: each step gets a layer view of the
    whole stack (``attention._cache_stack``), writes its tokens into it in
    place and attends there, so no layer's K/V is sliced out and stacked
    back.  Only the per-layer positions go through xs/ys.  Any other cache
    is scanned as xs/ys, one slice per step."""

    if attention.is_kv_cache(stacked_cache):
        def kv_body(carry, inp):
            xc, aux, k, v = carry
            p, pos, layer = inp
            xn, c, a = step_fn(xc, p, {"k": k, "v": v, "pos": pos,
                                       "layer": layer})
            return (xn, aux + a, c["k"], c["v"]), c["pos"]

        n_layers = stacked_cache["k"].shape[0]
        (x, aux, k, v), pos = jax.lax.scan(
            kv_body, (x, jnp.zeros((), jnp.float32), stacked_cache["k"],
                      stacked_cache["v"]),
            (stacked_params, stacked_cache["pos"],
             jnp.arange(n_layers, dtype=jnp.int32)))
        return x, {"k": k, "v": v, "pos": pos}, aux

    from jax.ad_checkpoint import checkpoint_name

    def body(carry, inp):
        xc, aux = carry
        # pin the saved residual to exactly this bf16 tensor: without the
        # explicit name, partial-eval may elect an fp32 *convert* of x as
        # the per-layer residual (2x activation-checkpoint memory).
        xc = checkpoint_name(xc, "layer_input")
        p, c = inp
        xn, c_new, a = step_fn(xc, p, c)
        return (xn, aux + a), c_new

    if remat == "full":
        body = jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_only_these_names(
                "layer_input"),
            prevent_cse=False)
    elif remat == "save_blocks":
        # full remat + keep post-collective block outputs: the backward
        # replay recomputes matmuls but NOT the TP all-reduces
        body = jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_only_these_names(
                "layer_input", "block_out"),
            prevent_cse=False)
    elif remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots,
            prevent_cse=False)

    has_cache = stacked_cache is not None
    xs = (stacked_params, stacked_cache if has_cache
          else jnp.zeros((n_steps,), jnp.int8))
    (x, aux), new_cache = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs)
    return x, (new_cache if has_cache else None), aux


def stack_init(key, n: int, init_fn: Callable) -> Params:
    """vmap an init over n layer keys -> stacked param tree."""
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


def stack_specs(spec_tree) -> Params:
    """Prefix every leaf spec with the (unsharded) layers dim."""
    return jax.tree.map(
        lambda s: (None,) + tuple(s),
        spec_tree, is_leaf=lambda s: isinstance(s, tuple))
