"""GQA attention: chunked online-softmax reference ("flash in jnp", memory-
flat in KV length), prefill/decode against a KV cache, cross-attention.

Two implementations are selectable per config (DESIGN.md §2 — the paper's
compiler-autovec vs hand-intrinsics axis):
  * ``reference`` — pure jnp chunked attention (lax.scan over KV blocks with
    an online softmax).  This path is what the multi-pod dry-run compiles.
  * ``pallas``    — repro.kernels.flash_attention (TPU target; validated in
    interpret mode; selected when cfg.attention_impl == "pallas").

The reference path has a ``block_causal`` switch: False computes every KV
chunk and masks (the paper's "masked predication" idiom — ~2x wasted work on
causal shapes); True skips chunks entirely above the diagonal (the "vsetvl
exact-length" idiom).  Fig-3 / §Perf quantify the gap.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.models.layers import Params, dense, dense_specs, init_dense, rms_norm_nd
from repro.parallel.axes import constrain

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# paged flash-decode context
# ---------------------------------------------------------------------------
# Trace-time plumbing for the fused paged-attention decode path
# (kernels/paged_attention).  The serving engine enters `paged_decode`
# inside its traced decode/prefill closures; `attn_decode` (and the
# embedding lookup in model.forward) then pick gather-free
# implementations without threading new arguments through every layer —
# same idiom as `repro.parallel.axes.sharding_ctx`.
@dataclasses.dataclass
class PagedDecodeState:
    """page_idx: (B, pages_per_seq) int32 device array (slot-major page
    ids into the pool view of the cache) or ``None`` for the row-local
    identity map (the engine's prefill rows).  ``impl=None`` auto-picks
    pallas on TPU / the xla identity-layout path elsewhere."""
    page_idx: Optional[jax.Array]
    page_size: int
    block_pages: int = 1
    impl: Optional[str] = None


_PAGED_STACK: List[PagedDecodeState] = []


@contextlib.contextmanager
def paged_decode(state: PagedDecodeState):
    _PAGED_STACK.append(state)
    try:
        yield state
    finally:
        _PAGED_STACK.pop()


def paged_state() -> Optional[PagedDecodeState]:
    return _PAGED_STACK[-1] if _PAGED_STACK else None


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------
def init_attention(key, cfg, cross: bool = False) -> Params:
    d, h = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 5)
    dtype = layers.dtype_of(cfg.param_dtype)
    p = {
        "wq": init_dense(ks[0], d, nq * h, dtype),
        "wk": init_dense(ks[1], d, nkv * h, dtype),
        "wv": init_dense(ks[2], d, nkv * h, dtype),
        "wo": init_dense(ks[3], nq * h, d, dtype, scale=(nq * h) ** -0.5),
    }
    if cfg.qk_norm:
        p["q_norm"] = {"scale": jnp.ones((h,), dtype)}
        p["k_norm"] = {"scale": jnp.ones((h,), dtype)}
    if cross:
        # gated cross-attention (Llama-3.2-Vision style zero-init gate)
        p["gate_attn"] = jnp.zeros((), dtype)
    return p


def attention_specs(cfg, cross: bool = False) -> Params:
    p = {
        "wq": dense_specs("embed", "heads"),
        "wk": dense_specs("embed", "kv_heads"),
        "wv": dense_specs("embed", "kv_heads"),
        "wo": dense_specs("heads", "embed"),
    }
    if cfg.qk_norm:
        p["q_norm"] = {"scale": (None,)}
        p["k_norm"] = {"scale": (None,)}
    if cross:
        p["gate_attn"] = ()
    return p


# ---------------------------------------------------------------------------
# projections
# ---------------------------------------------------------------------------
def _project_q(params, x, cfg):
    B, S, _ = x.shape
    h, nq = cfg.resolved_head_dim, cfg.n_heads
    q = dense(x, params["wq"]).reshape(B, S, nq, h)
    if cfg.qk_norm:
        q = rms_norm_nd(q, params["q_norm"]["scale"], cfg.norm_eps)
    return q


def _project_kv(params, x, cfg):
    B, S, _ = x.shape
    h, nkv = cfg.resolved_head_dim, cfg.n_kv_heads
    k = dense(x, params["wk"]).reshape(B, S, nkv, h)
    v = dense(x, params["wv"]).reshape(B, S, nkv, h)
    if cfg.qk_norm:
        k = rms_norm_nd(k, params["k_norm"]["scale"], cfg.norm_eps)
    return k, v


def _out_proj(params, out, cfg):
    B, S = out.shape[:2]
    out = constrain(out, "batch", None, "heads", None)
    y = dense(out.reshape(B, S, -1), params["wo"])
    if "gate_attn" in params:
        y = jnp.tanh(params["gate_attn"].astype(y.dtype)) * y
    return y


# ---------------------------------------------------------------------------
# core chunked attention (online softmax over KV blocks)
# ---------------------------------------------------------------------------
def _chunk_attend(q, k_c, v_c, m, l, acc, *, scale, softcap, mask):
    """One online-softmax step.  q:(B,N,Sq,H)  k_c/v_c:(B,N,Ck,H)
    mask:(B,1,Sq,Ck) boolean (True = attend)."""
    s = jnp.einsum("bnqh,bnkh->bnqk", q, k_c, preferred_element_type=jnp.float32)
    s = s * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))          # (B,N,Sq)
    corr = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l * corr + jnp.sum(p, axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum(
        "bnqk,bnkh->bnqh", p.astype(v_c.dtype), v_c,
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _expand_kv(q, k, v):
    """Broadcast KV heads to query heads; transpose to (B,N,S,H)."""
    G = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    return (q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3))


def _chunk_mask(B, Sq, kv_chunk, c_idx, causal, skv_real):
    """Batch/head-free (1,1,Sq,Ck) mask — keeping it rank-broadcastable
    stops XLA from hoisting a stacked (nc,B,N,Sq,Ck) mask out of the scan."""
    q_pos = jnp.arange(Sq)[:, None]                        # (Sq,1)
    kv_pos = c_idx * kv_chunk + jnp.arange(kv_chunk)[None, :]  # (1,Ck)
    mask = kv_pos < skv_real
    if causal:
        mask = mask & (kv_pos <= q_pos)
    else:
        mask = jnp.broadcast_to(mask, (Sq, kv_chunk))
    return mask[None, None]                                # (1,1,Sq,Ck)


def _flash_fwd_impl(qT, kcs, vcs, causal, softcap, block_causal, skv_real,
                    kv_chunk):
    """qT: (B,N,Sq,H) fp32; kcs/vcs: (nc,B,N,Ck,H).  Returns out, m, l.

    The chunk index rides in the scan *carry* (not xs): index-derived masks
    must stay loop-variant, otherwise XLA loop-invariant code motion hoists
    them out of the scan as an (nc, B, N, Sq, Ck) stacked buffer — the exact
    O(S^2) materialization flash attention exists to avoid.
    """
    B, N, Sq, H = qT.shape
    n_chunks = kcs.shape[0]

    def body(carry, inp):
        m, l, acc, c_idx = carry
        k_c, v_c = inp
        mask = _chunk_mask(B, Sq, kv_chunk, c_idx, causal, skv_real)

        def attend_fn(args):
            mm, ll, aa = args
            return _chunk_attend(qT, k_c, v_c, mm, ll, aa,
                                 scale=H ** -0.5, softcap=softcap, mask=mask)

        if causal and block_causal:
            # skip chunks entirely above the diagonal ("vsetvl" idiom)
            any_valid = (Sq - 1) >= c_idx * kv_chunk
            m, l, acc = jax.lax.cond(any_valid, attend_fn, lambda a: a,
                                     (m, l, acc))
        else:
            m, l, acc = attend_fn((m, l, acc))
        return (m, l, acc, c_idx + 1), None

    m0 = jnp.full((B, N, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, N, Sq), jnp.float32)
    acc0 = jnp.zeros((B, N, Sq, H), jnp.float32)
    # taint the counter with runtime data: a statically-known counter lets
    # scan partial-eval precompute every chunk mask into a stacked
    # (nc,B,N,Sq,Ck) residual — O(S^2) memory this path exists to avoid.
    c0 = (qT[0, 0, 0, 0] * 0.0).astype(jnp.int32)
    (m, l, acc, _), _ = jax.lax.scan(
        body, (m0, l0, acc0, c0), (kcs, vcs))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out, m, l


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(qT, kcs, vcs, causal, softcap, block_causal, skv_real, kv_chunk):
    out, _, _ = _flash_fwd_impl(qT, kcs, vcs, causal, softcap, block_causal,
                                skv_real, kv_chunk)
    return out


def _flash_fwd(qT, kcs, vcs, causal, softcap, block_causal, skv_real,
               kv_chunk):
    out, m, l = _flash_fwd_impl(qT, kcs, vcs, causal, softcap, block_causal,
                                skv_real, kv_chunk)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return out, (qT, kcs, vcs, out, lse)


def _flash_bwd(causal, softcap, block_causal, skv_real, kv_chunk, res, dout):
    """Flash backward: recompute per-chunk probabilities from (q, k, v, lse)
    instead of storing them — this is what keeps train-step memory flat in
    sequence length (saved residuals: out + lse only).
    """
    qT, kcs, vcs, out, lse = res
    B, N, Sq, H = qT.shape
    scale = H ** -0.5
    n_chunks = kcs.shape[0]
    # D_i = rowsum(dout * out)
    D = jnp.sum(dout * out, axis=-1)                      # (B,N,Sq)

    def body(carry, inp):
        dq_acc, c_idx = carry
        k_c, v_c = inp
        mask = _chunk_mask(B, Sq, kv_chunk, c_idx, causal, skv_real)

        def grads(dq_acc):
            s = jnp.einsum("bnqh,bnkh->bnqk", qT, k_c,
                           preferred_element_type=jnp.float32) * scale
            if softcap:
                sc = softcap * jnp.tanh(s / softcap)
                dsc_ds = 1.0 - jnp.square(sc / softcap)
            else:
                sc = s
                dsc_ds = None
            sc = jnp.where(mask, sc, NEG_INF)
            p = jnp.exp(sc - lse[..., None])              # (B,N,Sq,Ck)
            dv = jnp.einsum("bnqk,bnqh->bnkh", p, dout,
                            preferred_element_type=jnp.float32)
            dp = jnp.einsum("bnqh,bnkh->bnqk", dout, v_c,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - D[..., None])
            if dsc_ds is not None:
                ds = ds * dsc_ds
            ds = jnp.where(mask, ds, 0.0)
            dq = jnp.einsum("bnqk,bnkh->bnqh", ds, k_c,
                            preferred_element_type=jnp.float32) * scale
            dk = jnp.einsum("bnqk,bnqh->bnkh", ds, qT,
                            preferred_element_type=jnp.float32) * scale
            return dq_acc + dq, dk, dv

        if causal and block_causal:
            any_valid = (Sq - 1) >= c_idx * kv_chunk
            dq_acc, dk, dv = jax.lax.cond(
                any_valid, grads,
                lambda a: (a, jnp.zeros_like(k_c, jnp.float32),
                           jnp.zeros_like(v_c, jnp.float32)),
                dq_acc)
        else:
            dq_acc, dk, dv = grads(dq_acc)
        return (dq_acc, c_idx + 1), (dk, dv)

    dq0 = jnp.zeros_like(qT, jnp.float32)
    c0 = (dout[0, 0, 0, 0] * 0.0).astype(jnp.int32)   # taint: see fwd
    (dq, _), (dks, dvs) = jax.lax.scan(
        body, (dq0, c0), (kcs, vcs))
    return dq, dks, dvs


_flash.defvjp(_flash_fwd, _flash_bwd)


def chunked_attention(
    q: jax.Array,            # (B, Sq, NQ, H)
    k: jax.Array,            # (B, Skv, NKV, H)
    v: jax.Array,            # (B, Skv, NKV, H)
    *,
    causal: bool,
    softcap: float = 0.0,
    kv_chunk: int = 1024,
    block_causal: bool = True,
) -> jax.Array:
    B, Sq, NQ, H = q.shape
    Skv = k.shape[1]
    kv_chunk = min(kv_chunk, Skv)
    n_chunks = -(-Skv // kv_chunk)
    pad = n_chunks * kv_chunk - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qT, kT, vT = _expand_kv(q, k, v)
    qT = qT.astype(jnp.float32)
    kcs = kT.reshape(B, NQ, n_chunks, kv_chunk, H).transpose(2, 0, 1, 3, 4)
    vcs = vT.reshape(B, NQ, n_chunks, kv_chunk, H).transpose(2, 0, 1, 3, 4)
    out = _flash(qT, kcs, vcs, causal, softcap, block_causal, Skv, kv_chunk)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)      # (B,Sq,NQ,H)


def chunked_attention_autodiff(q, k, v, *, causal, softcap=0.0,
                               kv_chunk=1024, block_causal=True):
    """The naive version: plain autodiff through the online-softmax scan.
    Kept as the Fig-5 "compiler autovec" comparison point — its backward
    stores every per-chunk probability block (O(S^2) residuals)."""
    B, Sq, NQ, H = q.shape
    Skv = k.shape[1]
    kv_chunk = min(kv_chunk, Skv)
    n_chunks = -(-Skv // kv_chunk)
    pad = n_chunks * kv_chunk - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qT, kT, vT = _expand_kv(q, k, v)
    qT = qT.astype(jnp.float32)
    kcs = kT.reshape(B, NQ, n_chunks, kv_chunk, H).transpose(2, 0, 1, 3, 4)
    vcs = vT.reshape(B, NQ, n_chunks, kv_chunk, H).transpose(2, 0, 1, 3, 4)
    out, _, _ = _flash_fwd_impl(qT, kcs, vcs, causal, softcap, block_causal,
                                Skv, kv_chunk)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def _full_attention_with_cache(q, k, v, *, positions, kv_valid_len, softcap):
    """Decode-path attention: small Sq against the whole cache.
    q: (B,Sq,NQ,H); k/v: (B,Skv,NKV,H) (the cache)."""
    B, Sq, NQ, H = q.shape
    Skv, NKV = k.shape[1], k.shape[2]
    G = NQ // NKV
    scale = H ** -0.5
    k = jnp.repeat(k, G, axis=2).transpose(0, 2, 1, 3)    # (B,NQ,Skv,H)
    v = jnp.repeat(v, G, axis=2).transpose(0, 2, 1, 3)
    qT = q.transpose(0, 2, 1, 3).astype(jnp.float32)
    s = jnp.einsum("bnqh,bnkh->bnqk", qT, k, preferred_element_type=jnp.float32)
    s = s * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    kv_pos = jnp.arange(Skv)[None, None, None, :]
    mask = kv_pos <= positions[:, None, :, None]
    mask &= kv_pos < kv_valid_len[:, None, None, None]
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bnqk,bnkh->bnqh", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


# ---------------------------------------------------------------------------
# layer entry points
# ---------------------------------------------------------------------------
def _constrain_qkv(q, k, v):
    q = constrain(q, "batch", None, "heads", None)
    k = constrain(k, "batch", None, "kv_heads", None)
    v = constrain(v, "batch", None, "kv_heads", None)
    return q, k, v


def attn_train(params, x, cfg, *, positions, causal=True, kv_chunk=1024,
               block_causal=True):
    q = _project_q(params, x, cfg)
    k, v = _project_kv(params, x, cfg)
    if cfg.rope_theta > 0:
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, positions, cfg.rope_theta)
    q, k, v = _constrain_qkv(q, k, v)
    if cfg.attention_impl == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops
        out = fa_ops.flash_attention(q, k, v, causal=causal,
                                     softcap=cfg.attn_logit_softcap)
    else:
        out = chunked_attention(q, k, v, causal=causal,
                                softcap=cfg.attn_logit_softcap,
                                kv_chunk=kv_chunk, block_causal=block_causal)
    return _out_proj(params, out, cfg)


def attn_prefill(params, x, cfg, *, positions, cache, kv_chunk=1024,
                 block_causal=True):
    """Prefill: causal attention over the prompt AND populate the cache."""
    B, S, _ = x.shape
    q = _project_q(params, x, cfg)
    k, v = _project_kv(params, x, cfg)
    if cfg.rope_theta > 0:
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, positions, cfg.rope_theta)
    q, k, v = _constrain_qkv(q, k, v)
    out = chunked_attention(q, k, v, causal=True,
                            softcap=cfg.attn_logit_softcap,
                            kv_chunk=kv_chunk, block_causal=block_causal)
    k_all, v_all, layer = _cache_stack(cache)
    start = (layer, 0, 0, 0, 0)
    kc = jax.lax.dynamic_update_slice(k_all, k[None].astype(k_all.dtype), start)
    vc = jax.lax.dynamic_update_slice(v_all, v[None].astype(v_all.dtype), start)
    return _out_proj(params, out, cfg), _with_stack(cache, kc, vc,
                                                    cache["pos"] + S)


def _cache_stack(cache):
    """``(k, v, layer)``: a cache's K/V as a layer stack, and the layer
    this call reads and writes.

    Inside the layer scan a dense/moe cache arrives as a *layer view* of
    the stack the scan carries (``blocks.run_stack``): ``k``/``v`` hold
    every layer, (L, B, S_cache, NKV, H), and ``layer`` is this one's
    index.  Any other cache holds one layer's (B, S_cache, NKV, H) and
    is a stack of one."""
    if "layer" in cache:
        return cache["k"], cache["v"], cache["layer"]
    return cache["k"][None], cache["v"][None], 0


def _with_stack(cache, k, v, pos):
    """The updated cache in ``cache``'s own form (see ``_cache_stack``)."""
    if "layer" in cache:
        return dict(cache, k=k, v=v, pos=pos)
    return {"k": k[0], "v": v[0], "pos": pos}


def _write_tokens(stack, new, layer, idx):
    """Scatter ``new`` (B, S, NKV, H) into ``stack`` (L, B, S_cache, NKV,
    H) at ``[layer, b, idx[b, s]]``: one scatter over the whole stack,
    in place when the stack is donated or carried.  Indices past the
    cache (``idx == S_cache``) are dropped."""
    B, S = idx.shape
    where = jnp.stack([
        jnp.broadcast_to(jnp.asarray(layer, jnp.int32), (B, S)),
        jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[:, None], (B, S)),
        idx.astype(jnp.int32)], axis=-1)                      # (B, S, 3)
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(2, 3), inserted_window_dims=(0, 1, 2),
        scatter_dims_to_operand_dims=(0, 1, 2))
    return jax.lax.scatter(stack, where, new.astype(stack.dtype), dnums,
                           mode=jax.lax.GatherScatterMode.FILL_OR_DROP)


def attn_decode(params, x, cfg, *, positions, cache, n_valid=None):
    """Decode: write current token K/V at cache position, attend over cache.

    ``n_valid`` (B,) int32 — optional per-row count of valid tokens in the
    (B, S) step, for the serving engine's mixed chunked-prefill + decode
    batches: rows carry between 0 (idle slot) and S (full prefill chunk)
    real tokens, right-padded.  Cache writes for padding columns are
    dropped (their scatter index is forced out of bounds), the attention
    valid-length mask closes over ``pos + n_valid``, and the cache position
    advances by ``n_valid`` instead of S.  ``None`` keeps the classic
    all-rows-full behavior.

    ``cache`` is one layer's cache or, inside the dense/moe layer scan, a
    layer view of the stacked cache (``_cache_stack``): the step's K/V go
    into the stack with one scatter and the attention reads them there,
    so no layer's K/V is sliced out of the stack or written back.

    When the active sharding rules map the cache length ("kv_seq") to a
    mesh axis, the sequence-parallel flash-decoding path runs instead:
    each shard attends over its cache slice and the partial online-softmax
    states combine with one tiny pmax/psum — the cache is never gathered.
    It takes the layer's slice of a stack and writes it back.
    """
    from repro.parallel.axes import rule_axes

    B, S, _ = x.shape
    q = _project_q(params, x, cfg)
    k, v = _project_kv(params, x, cfg)
    if cfg.rope_theta > 0:
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, positions, cfg.rope_theta)
    k_all, v_all, layer = _cache_stack(cache)
    pos = cache["pos"]                                    # (B,)
    kv_axes = rule_axes("kv_seq")
    if kv_axes:
        out, lc = _attn_decode_spkv(
            params, q, k, v, cfg, positions=positions,
            cache={"k": jax.lax.dynamic_index_in_dim(k_all, layer, 0, False),
                   "v": jax.lax.dynamic_index_in_dim(v_all, layer, 0, False),
                   "pos": pos},
            axis=kv_axes[0], n_valid=n_valid)
        kc = jax.lax.dynamic_update_index_in_dim(k_all, lc["k"], layer, 0)
        vc = jax.lax.dynamic_update_index_in_dim(v_all, lc["v"], layer, 0)
        return out, _with_stack(cache, kc, vc, lc["pos"])
    q, k, v = _constrain_qkv(q, k, v)
    S_cache = k_all.shape[2]
    idx = pos[:, None] + jnp.arange(S)[None]              # (B,S)
    step = jnp.full((B,), S, jnp.int32) if n_valid is None else n_valid
    if n_valid is not None:
        # padding columns scatter out of bounds -> dropped
        idx = jnp.where(jnp.arange(S)[None] < n_valid[:, None], idx, S_cache)
    kc = _write_tokens(k_all, k, layer, idx)
    vc = _write_tokens(v_all, v, layer, idx)
    new_cache = _with_stack(cache, kc, vc, pos + step)
    ps = paged_state()
    pageable = (ps is not None and S_cache % ps.page_size == 0
                and (ps.page_idx is None or ps.page_idx.shape
                     == (B, S_cache // ps.page_size)))
    if pageable:
        out = _paged_attention_with_cache(
            q, kc, vc, ps, layer=layer, positions=positions,
            kv_valid_len=pos + step, softcap=cfg.attn_logit_softcap)
    else:
        out = _full_attention_with_cache(
            q, jax.lax.dynamic_index_in_dim(kc, layer, 0, False),
            jax.lax.dynamic_index_in_dim(vc, layer, 0, False),
            positions=positions, kv_valid_len=pos + step,
            softcap=cfg.attn_logit_softcap)
    return _out_proj(params, out, cfg), new_cache


def _paged_attention_with_cache(q, k, v, ps, *, layer, positions,
                                kv_valid_len, softcap):
    """Fused paged decode over layer ``layer`` of the stacked cache: the
    stack (L, B, S_cache, NKV, H) is *viewed* as a page pool
    (L*B*pages, page_size, NKV, H) — a reshape, not a gather or a copy —
    and kernels/paged_attention streams the layer's pages by page id,
    ``page_idx + layer*B*pages``, with the ragged mask folded in.
    Clears the trace-lint ``hot-gather`` finding the dense
    ``_full_attention_with_cache`` path triggers.  The XLA twin
    (``impl="xla"``) is specialised to one layer's identity-laid pool,
    so it reads the layer out of the stack.

    Under a sharding context the kernel runs inside ``shard_map`` (Mosaic
    kernels cannot be partitioned automatically): slots split over the
    cache's "batch" axes and heads over its "kv_heads" axis; the layer
    axis stays whole.  The page map splits with the slots and each shard
    rebases its rows' global page ids onto its own pool view: the shard
    holding slot rows ``[i*B, (i+1)*B)`` holds each layer's pages
    ``[i*B*pps, (i+1)*B*pps)``.  A row's pages must live on its own
    shard, as ``PagedKVCache(n_shards)`` budgets pages per slot block."""
    from repro.kernels.paged_attention import ops as pa_ops
    from repro.parallel import axes as paxes

    layer = jnp.asarray(layer, jnp.int32)

    def attend(q, k, v, positions, kv_valid_len, layer, page_idx):
        L, B, S_cache, NKV, H = k.shape
        pps = S_cache // ps.page_size
        if page_idx is None:
            # row-local identity map (engine prefill rows run batch=1)
            page_idx = jnp.arange(B * pps, dtype=jnp.int32).reshape(B, pps)
        if pa_ops.resolve_impl(ps.impl) == "xla":
            k = jax.lax.dynamic_index_in_dim(k, layer, 0)
            v = jax.lax.dynamic_index_in_dim(v, layer, 0)
            L, layer = 1, 0
        k_pages = k.reshape(L * B * pps, ps.page_size, NKV, H)
        v_pages = v.reshape(L * B * pps, ps.page_size, NKV, H)
        return pa_ops.paged_attention(
            q, k_pages, v_pages, page_idx + layer * (B * pps), positions,
            kv_valid_len, page_size=ps.page_size, softcap=softcap,
            block_pages=ps.block_pages, impl=ps.impl)

    if not paxes.active():
        return attend(q, k, v, positions, kv_valid_len, layer, ps.page_idx)

    from jax.sharding import PartitionSpec as P
    from repro.core.compat import shard_map

    spec = paxes.resolve_spec((None, "batch", None, "kv_heads", None),
                              k.shape, record=False)
    spec = tuple(spec) + (None,) * (5 - len(spec))
    bax, hax = spec[1], spec[3]
    # query heads are KV-head-major (head n*G + g), so splitting them over
    # the KV-head axis keeps every group on its KV head's shard
    heads = P(bax, None, hax, None)
    stack = P(None, bax, None, hax, None)
    args = (q, k, v, positions, kv_valid_len, layer)
    in_specs = (heads, stack, stack, P(bax, None), P(bax), P())
    if ps.page_idx is not None:
        args += (ps.page_idx,)
        in_specs += (P(bax, None),)

    def local(q, k, v, positions, kv_valid_len, layer, page_idx=None):
        if page_idx is not None and bax is not None:
            B, S_cache = k.shape[1:3]
            page_idx = page_idx - (jax.lax.axis_index(bax)
                                   * (B * (S_cache // ps.page_size)))
        return attend(q, k, v, positions, kv_valid_len, layer, page_idx)

    return shard_map(local, mesh=paxes.current_mesh(), in_specs=in_specs,
                     out_specs=heads, check=False)(*args)


def _attn_decode_spkv(params, q, k, v, cfg, *, positions, cache, axis,
                      n_valid=None):
    """Sequence-parallel decode: cache length sharded over ``axis``.

    Per shard: scatter the new K/V into the locally-owned slice (index
    ``mode=drop`` keeps the write on the owning shard only), compute the
    partial online-softmax over the local cache slice, then combine the
    (m, l, acc) triple across shards — O(B*NQ*H) bytes instead of
    all-gathering the O(B*S*NKV*H) cache.

    ``n_valid`` (B,) follows the same ragged-write contract as the
    unsharded decode (serving engine mixed steps): cache scatters for
    columns past a row's count are dropped, the valid-length mask closes
    over ``pos + n_valid``, and the position advances by ``n_valid``.
    Rows with ``n_valid == 0`` see an all-masked score matrix — NEG_INF
    is a finite constant, so their (discarded) outputs stay NaN-free.
    """
    from jax.sharding import PartitionSpec as P
    from repro.core.compat import shard_map
    from repro.parallel.axes import current_mesh, resolve_spec

    mesh = current_mesh()
    softcap = cfg.attn_logit_softcap
    batch_spec = resolve_spec(("batch",), (q.shape[0],))  # e.g. ('data',)
    bax = batch_spec[0] if len(batch_spec) else None

    qs = P(bax, None, None, None)
    kv_new = P(bax, None, None, None)
    cache_s = P(bax, axis, None, None)
    pos_s = P(bax)
    step = (jnp.full((q.shape[0],), q.shape[1], jnp.int32)
            if n_valid is None else n_valid)
    # trace-time constant: when the paged-decode context is active the
    # per-shard partial comes from the grouped kernel helper instead of
    # the repeat-einsum below (no K/V head materialization per shard)
    ps = paged_state()

    def body(q, k_new, v_new, kc, vc, pos, positions, step):
        i = jax.lax.axis_index(axis)
        S_shard = kc.shape[1]
        offset = i * S_shard
        # local scatter (out-of-shard and past-n_valid indices drop)
        idx = pos[:, None] + jnp.arange(q.shape[1])[None] - offset
        idx = jnp.where(jnp.arange(q.shape[1])[None] < step[:, None],
                        idx, S_shard)
        kc = jax.vmap(lambda c, u, ii: c.at[ii].set(u, mode="drop"))(
            kc, k_new.astype(kc.dtype), idx)
        vc = jax.vmap(lambda c, u, ii: c.at[ii].set(u, mode="drop"))(
            vc, v_new.astype(vc.dtype), idx)
        # partial attention over the local slice
        B, Sq, NQ, H = q.shape
        NKV = kc.shape[2]
        G = NQ // NKV
        if ps is not None:
            # grouped flash-decode partials from the paged kernel family
            # — the cross-shard combine below folds over them directly
            from repro.kernels.paged_attention import ops as pa_ops
            m_loc, l_loc, acc_loc = pa_ops.decode_partials(
                q, kc, vc, positions, pos + step,
                kv_offset=jnp.asarray(offset, jnp.int32), softcap=softcap)
        else:
            ke = jnp.repeat(kc, G, axis=2).transpose(0, 2, 1, 3)
            ve = jnp.repeat(vc, G, axis=2).transpose(0, 2, 1, 3)
            qT = q.transpose(0, 2, 1, 3).astype(jnp.float32)
            s = jnp.einsum("bnqh,bnkh->bnqk", qT, ke,
                           preferred_element_type=jnp.float32) * (H ** -0.5)
            if softcap:
                s = softcap * jnp.tanh(s / softcap)
            kv_pos = offset + jnp.arange(S_shard)[None, None, None, :]
            mask = kv_pos <= positions[:, None, :, None]
            mask &= kv_pos < (pos + step)[:, None, None, None]
            s = jnp.where(mask, s, NEG_INF)
            m_loc = jnp.max(s, axis=-1)                   # (B,NQ,Sq)
            p = jnp.exp(s - m_loc[..., None])
            l_loc = jnp.sum(p, axis=-1)
            acc_loc = jnp.einsum("bnqk,bnkh->bnqh", p.astype(ve.dtype), ve,
                                 preferred_element_type=jnp.float32)
        # flash-decoding combine across shards (tiny)
        m_glob = jax.lax.pmax(m_loc, axis)
        corr = jnp.exp(m_loc - m_glob)
        l_glob = jax.lax.psum(l_loc * corr, axis)
        acc_glob = jax.lax.psum(acc_loc * corr[..., None], axis)
        out = acc_glob / jnp.maximum(l_glob, 1e-30)[..., None]
        return out.transpose(0, 2, 1, 3).astype(q.dtype), kc, vc

    out, kc, vc = shard_map(
        body, mesh=mesh,
        in_specs=(qs, kv_new, kv_new, cache_s, cache_s, pos_s, pos_s, pos_s),
        out_specs=(qs, cache_s, cache_s),
        check=False,
    )(q, k, v, cache["k"], cache["v"], cache["pos"], positions, step)
    new_cache = {"k": kc, "v": vc, "pos": cache["pos"] + step}
    return _out_proj(params, out, cfg), new_cache


def project_cross_kv(params, ctx, cfg):
    """K/V projection of a static cross-attention context (B, T, d).

    This is the read-only half of the DecodeState protocol for cross-
    attention families: the serving engine projects a request's context
    (image embeddings / encoder output) once at admission and installs
    the result into the slot's cache row; decode steps then attend over
    it without ever rewriting it."""
    return _project_kv(params, ctx, cfg)


def cross_attn(params, x, cfg, *, ctx=None, cached_kv=None, kv_chunk=1024):
    """Cross-attention to a static context (image patches / encoder output).

    Pass ``ctx`` (B, T, d) to compute K/V (prefill/train) — returned for
    caching — or ``cached_kv=(k, v)`` during decode.
    """
    q = _project_q(params, x, cfg)
    if ctx is not None:
        k, v = project_cross_kv(params, ctx, cfg)
    else:
        k, v = cached_kv
    q = constrain(q, "batch", None, "heads", None)
    out = chunked_attention(q, k, v, causal=False,
                            softcap=cfg.attn_logit_softcap, kv_chunk=kv_chunk)
    y = _out_proj(params, out, cfg)
    return (y, (k, v)) if ctx is not None else (y, None)


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------
def init_cache(cfg, batch: int, max_len: int, dtype) -> Dict[str, jax.Array]:
    h, nkv = cfg.resolved_head_dim, cfg.n_kv_heads
    return {
        "k": jnp.zeros((batch, max_len, nkv, h), dtype),
        "v": jnp.zeros((batch, max_len, nkv, h), dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def is_kv_cache(cache) -> bool:
    """True for an attention KV cache, one layer's or a stack of them:
    exactly the leaves ``init_cache`` makes."""
    return isinstance(cache, dict) and set(cache) == {"k", "v", "pos"}


def cache_specs(cfg) -> Dict[str, Any]:
    return {
        "k": ("batch", "kv_seq", "kv_heads", None),
        "v": ("batch", "kv_seq", "kv_heads", None),
        "pos": ("batch",),
    }
