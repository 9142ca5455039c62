"""Shared Pallas kernel utilities.

``interpret_default()`` — kernels target TPU (Mosaic) but validate on CPU in
interpret mode; every ops.py wrapper takes ``interpret=None`` meaning "auto".

``block_multiplier`` is the LMUL analogue (DESIGN.md §2): base tiles are
hardware-aligned (8 sublanes x 128 lanes; 128x128 for MXU operands) and the
multiplier groups {1,2,4,8} of them into one logical tile — more elements per
grid step (better pipelining/MXU occupancy) against VMEM pressure, exactly
RVV's register-grouping trade-off one level up the memory hierarchy.
"""
from __future__ import annotations

import jax

LANE = 128        # TPU vector lane width (last-dim alignment)
SUBLANE = 8       # f32 sublane count (second-minor alignment)
MXU = 128         # systolic array dim
# Mosaic's default scoped-VMEM limit per kernel on v4 / v5e (v6e: 32 MiB);
# a kernel whose blocks need more is refused at compile time
SCOPED_VMEM_BYTES = 16 * 2 ** 20

VALID_MULTIPLIERS = (1, 2, 4, 8)


def interpret_default(interpret=None) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def check_multiplier(m: int) -> int:
    if m not in VALID_MULTIPLIERS:
        raise ValueError(f"block multiplier must be one of {VALID_MULTIPLIERS}")
    return m


def cdiv(a: int, b: int) -> int:
    return -(-a // b)
