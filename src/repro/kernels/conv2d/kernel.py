"""Direct fused conv2d (NHWC, stride 1, SAME) — the CNN proxy-app hot spot
(paper: AlexNet / YOLOv3 convolution layers).

Rather than im2col-materialize (the memory-hungry GPU route), the kernel
keeps an output row-block in VMEM and accumulates kh*kw shifted matmuls
(each (bh*W, Cin) x (Cin, Cout) on the MXU) over a haloed input block —
the TPU-native implicit-GEMM formulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv


def _conv_kernel(x_ref, w_ref, o_ref, *, kh, kw, bh, W, cin, cout):
    x = x_ref[0]                             # (bh + kh - 1, W + kw - 1, cin)
    acc = jnp.zeros((bh * W, cout), jnp.float32)
    for dy in range(kh):
        for dx in range(kw):
            patch = x[dy:dy + bh, dx:dx + W, :].reshape(bh * W, cin)
            acc += jax.lax.dot_general(
                patch.astype(jnp.float32),
                w_ref[dy, dx].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    o_ref[0] = acc.reshape(bh, W, cout).astype(o_ref.dtype)


def conv2d_same(x, w, *, block_h=8, interpret=True):
    """x: (N, H, W, Cin); w: (kh, kw, Cin, Cout); stride 1, SAME padding."""
    N, H, W, Cin = x.shape
    kh, kw, _, Cout = w.shape
    ph, pw = kh // 2, kw // 2
    xp = jnp.pad(x, ((0, 0), (ph, kh - 1 - ph), (pw, kw - 1 - pw), (0, 0)))
    bh = min(block_h, H)
    assert H % bh == 0, "conv2d_same: H must be a multiple of block_h"
    grid = (N, cdiv(H, bh))
    kern = functools.partial(_conv_kernel, kh=kh, kw=kw, bh=bh, W=W,
                             cin=Cin, cout=Cout)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            # haloed input block: bh + kh - 1 rows starting at element i*bh
            # (an element-indexed row dim -> overlapping halo reads)
            pl.BlockSpec((1, pl.Element(bh + kh - 1), W + kw - 1, Cin),
                         lambda n, i: (n, i * bh, 0, 0)),
            pl.BlockSpec((kh, kw, Cin, Cout), lambda n, i: (0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bh, W, Cout), lambda n, i: (n, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, H, W, Cout), x.dtype),
        interpret=interpret,
    )(xp, w)
