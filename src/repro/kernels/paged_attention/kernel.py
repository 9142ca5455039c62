"""Fused paged flash-decode (TPU Pallas): page-table walk + online softmax.

The kernel consumes the serving layout directly: K/V live as a flat page
*pool* ``(P, page_size, NKV, H)`` and each decode row owns a list of page
ids ``page_idx[b, :]`` (the ``PagedKVCache`` page-index array).  The page
walk happens in the BlockSpec index_map — scalar-prefetched ``page_idx``
picks which pool block the next grid step streams into VMEM, so gathered
K/V rows are never materialized in HBM (the trace-lint ``hot-gather``
pattern this family exists to clear).

GQA head repeat is free: queries arrive grouped as ``(B, NKV, G*Sq, H)``
(a pure reshape in ops.py — no ``_expand``-style K/V copy) and each
KV head's G*Sq query rows attend to that head's K/V only.

Grid is (B, kv_blocks) with the kv dim minor (sequential).  One grid
step streams a ``(block_pages, page, NKV, H)`` K/V block — every KV head
at once, because Mosaic tiles a block's last two dims by (8, 128) or by
the whole array dim, and one head of NKV is neither — and a static loop
over the heads slices each out in VMEM.  The online-softmax state
(m, l, acc) lives per head in VMEM scratch across page tiles.  The
ragged ``n_valid`` serving contract folds into both the block skip
(``vsetvl`` idiom: tiles past ``kv_valid`` are never computed) and the
in-tile mask.

The kernel returns *partials* (acc, m, l) rather than normalized outputs
so one kernel serves both the single-device path (ops.py normalizes) and
the SP-KV cross-shard flash-decoding combine (pmax/psum over partials in
models/attention).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import LANE

NEG_INF = -1e30


def _paged_kernel(idx_ref, pos_ref, val_ref,          # scalar-prefetch
                  q_ref, k_ref, v_ref,                # VMEM inputs
                  acc_out, m_out, l_out,              # outputs
                  m_ref, l_ref, acc_ref, *,           # VMEM scratch
                  sq, block_kv, n_blocks, scale, softcap):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    valid = val_ref[b]
    pos0 = pos_ref[b]
    # ragged block skip: tiles at or past kv_valid are never computed.
    # Causality is implied — every query column c sits at position
    # pos0 + c <= valid - 1, so no tile beyond the valid band is needed.
    visit = j * block_kv < valid

    @pl.when(visit)
    def _attend():
        n_kv, rows = q_ref.shape[1], q_ref.shape[2]         # NKV, G * Sq
        # row r of a grouped q block is query column r % Sq (ops.py
        # lays groups out as g*Sq + c); the engine contract makes query
        # positions contiguous, so column c sits at absolute pos0 + c.
        # The mask is the same for every KV head.
        q_col = jax.lax.rem(
            jax.lax.broadcasted_iota(jnp.int32, (rows, block_kv), 0), sq)
        kv_pos = j * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_kv), 1)
        mask = (kv_pos <= pos0 + q_col) & (kv_pos < valid)
        for n in range(n_kv):
            q = q_ref[0, n].astype(jnp.float32)             # (G*Sq, H)
            k = k_ref[:, :, n, :].astype(jnp.float32).reshape(block_kv, -1)
            v = v_ref[:, :, n, :].astype(jnp.float32).reshape(block_kv, -1)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (G*Sq, bkv)
            if softcap:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(mask, s, NEG_INF)

            m_prev = m_ref[n, :, :1]                        # (rows, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = l_ref[n, :, :1] * corr + jnp.sum(p, -1, keepdims=True)
            acc_ref[n] = acc_ref[n] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[n] = jnp.broadcast_to(m_new, (rows, LANE))
            l_ref[n] = jnp.broadcast_to(l_new, (rows, LANE))

    @pl.when(j == n_blocks - 1)
    def _store():
        acc_out[0] = acc_ref[...]
        m_out[0] = m_ref[...]
        l_out[0] = l_ref[...]


def vmem_bytes(*, block_pages, page_size, n_kv_heads, head_dim, rows,
               dtype_bytes):
    """VMEM the kernel holds per grid step: every blocked operand twice
    (the pipeline double-buffers it) plus the fp32 scratch.  ``rows`` is
    G * Sq, the grouped query rows per KV head."""
    kv = 2 * block_pages * page_size * n_kv_heads * head_dim * dtype_bytes
    q = n_kv_heads * rows * head_dim * dtype_bytes
    out = n_kv_heads * rows * (head_dim + 2 * LANE) * 4       # acc, m, l
    return 2 * (kv + q + out) + out


def paged_flash_decode(qg, k_pages, v_pages, page_idx, pos0, kv_valid, *,
                       sq, softcap=0.0, block_pages=1, interpret=True):
    """qg: (B, NKV, G*Sq, H) grouped queries; k/v_pages: (P, page, NKV, H)
    pool; page_idx: (B, pages_per_seq) int32; pos0/kv_valid: (B,) int32.

    Returns fp32 partials ``(acc, m, l)`` shaped (B, NKV, G*Sq, H) /
    (B, NKV, G*Sq) / (B, NKV, G*Sq); normalize as ``acc / max(l, eps)``.

    ``block_pages > 1`` streams several pages per grid step; the
    index_map addresses pool blocks of that size, which requires each
    aligned ``block_pages`` chunk of a row's page list to be contiguous
    in the pool (the engine's identity layout trivially is).
    ``block_pages=1`` is fully general — any page permutation.
    """
    B, NKV, GS, H = qg.shape
    page = k_pages.shape[1]
    pps = page_idx.shape[1]
    bp = block_pages
    if pps % bp:
        raise ValueError(f"block_pages={bp} must divide pages_per_seq={pps}")
    n_blocks = pps // bp
    block_kv = bp * page
    kern = functools.partial(
        _paged_kernel, sq=sq, block_kv=block_kv, n_blocks=n_blocks,
        scale=H ** -0.5, softcap=softcap)

    def row_block(b, j, idx, pos, val):
        return b, 0, 0, 0

    def page_block(b, j, idx, pos, val):
        # the page walk: scalar-prefetched page_idx steers which pool
        # block (of bp pages) this grid step streams into VMEM
        return idx[b, j * bp] // bp, 0, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, n_blocks),
        in_specs=[
            pl.BlockSpec((1, NKV, GS, H), row_block),
            pl.BlockSpec((bp, page, NKV, H), page_block),
            pl.BlockSpec((bp, page, NKV, H), page_block),
        ],
        out_specs=[
            pl.BlockSpec((1, NKV, GS, H), row_block),
            pl.BlockSpec((1, NKV, GS, LANE), row_block),
            pl.BlockSpec((1, NKV, GS, LANE), row_block),
        ],
        scratch_shapes=[
            pltpu.VMEM((NKV, GS, LANE), jnp.float32),    # m
            pltpu.VMEM((NKV, GS, LANE), jnp.float32),    # l
            pltpu.VMEM((NKV, GS, H), jnp.float32),       # acc
        ],
    )
    acc, m, l = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, NKV, GS, H), jnp.float32),
            jax.ShapeDtypeStruct((B, NKV, GS, LANE), jnp.float32),
            jax.ShapeDtypeStruct((B, NKV, GS, LANE), jnp.float32),
        ],
        interpret=interpret,
        name="paged_attention",
    )(page_idx.astype(jnp.int32), pos0.astype(jnp.int32),
      kv_valid.astype(jnp.int32), qg, k_pages, v_pages)
    return acc, m[..., 0], l[..., 0]
