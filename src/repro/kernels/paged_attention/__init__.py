"""Paged flash-decode attention: the page-table walk fused into the kernel.

Motivating finding: the trace linter's ``hot-gather`` rule
(``repro.analysis.trace``) fired on every ``ContinuousBatchingEngine``
``decode_step`` program because the decode path materialized gathered
K/V rows at the XLA level — exactly the gather/strided access pattern
the source paper shows cost models misprice.  This family clears it: the
kernel streams K/V pages straight out of the ``PagedKVCache`` pool using
the slot page-index array (walked in scalar-prefetch BlockSpec
index_maps on the Pallas path, reshaped as a zero-gather identity view
on the XLA path), with the ``n_valid`` ragged contract folded into the
tile mask and GQA head-repeat done by query grouping instead of K/V
materialization.

- ``ref.py`` — dense fp32 gather-then-softmax oracle.
- ``kernel.py`` — the Pallas flash-decode kernel (partials out, for the
  SP-KV combine).
- ``ops.py`` — jit'd dispatch (pallas/xla), SP-KV ``decode_partials``,
  ``combine_partials``.

``block_pages`` (pages per tile) is autotuned per
(head_dim, n_kv_heads, page_size, dtype, impl, device kind) over the
tiles whose VMEM footprint fits (``kernel.vmem_bytes``) via
``core.autotune.tune_paged_attention``, with an on-disk cache at
``benchmarks/results/autotune_cache.json``.
"""
from repro.kernels.paged_attention import ref
from repro.kernels.paged_attention.ops import (combine_partials,
                                               decode_partials,
                                               paged_attention)

__all__ = ["paged_attention", "decode_partials", "combine_partials", "ref"]
