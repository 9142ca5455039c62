"""Operations and bytes that the served tokens need, from the model's
shapes and each step's real context lengths.

The counts are what the algorithm needs, whatever implements it: an
embedding lookup is a gather (no operations), attention is causal (a
query at position p attends to p + 1 keys), and the paged kernel's bytes
are the K/V up to each row's valid length plus its q and out, not the
pages it happens to stream.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Shapes:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    dtype_bytes: int = 2

    @classmethod
    def from_config(cls, c: dict) -> "Shapes":
        nh = c["num_attention_heads"]
        return cls(n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   n_heads=nh, n_kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim") or c["hidden_size"] // nh,
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"])

    @property
    def matmul_params_per_layer(self) -> int:
        d, h = self.d_model, self.head_dim
        attn = d * (self.n_heads + 2 * self.n_kv_heads) * h + self.n_heads * h * d
        return attn + 3 * d * self.d_ff


def attention_flops(s: Shapes, n_keys: int) -> float:
    """QK^T and PV of one query over ``n_keys`` keys, all heads, one layer."""
    return 4.0 * s.n_heads * s.head_dim * n_keys


def chunk_flops(s: Shapes, start: int, n: int, samples: bool) -> float:
    """Operations of ``n`` consecutive tokens starting at ``start``.  A
    chunk needs the unembedding of its last token only, and only when it
    samples one (a prefill chunk that does not end the prompt does not)."""
    if n <= 0:
        return 0.0
    keys = n * start + n * (n + 1) // 2
    per_layer = 2.0 * s.matmul_params_per_layer * n + attention_flops(s, keys)
    return s.n_layers * per_layer + (2.0 * s.d_model * s.vocab if samples else 0.0)


def paged_attn_call(s: Shapes, start: int, n: int) -> tuple:
    """(operations, bytes) the paged kernel needs for one row of ``n``
    queries at positions ``start`` .. ``start + n - 1`` in one layer:
    K and V up to the row's valid length ``start + n``, q and out."""
    valid = start + n
    keys = n * start + n * (n + 1) // 2
    flops = attention_flops(s, keys)
    kv_bytes = 2 * valid * s.n_kv_heads * s.head_dim * s.dtype_bytes
    qo_bytes = 2 * n * s.n_heads * s.head_dim * s.dtype_bytes
    return flops, float(kv_bytes + qo_bytes)
