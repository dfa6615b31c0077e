"""Pallas TPU kernel for the per-block fraction (n:m) blocking sparsifier.

Computes the keep-mask of per-m-block top-n selection along the last axis —
the first pass of the paper's two-pass blocking sparsifier (Table 1), and the
hot path of weight re-sparsification after optimizer updates (paper §5.2
notes conversion performance is critical during training).

Rank-based selection: element i of a block is kept iff
``#{j : |x_j| > |x_i|  or  (|x_j| == |x_i| and j < i)} < n`` — an O(m^2)
comparison network that avoids sorting and reproduces jax.lax.top_k's
lowest-index tie-breaking exactly (so the Pallas kernel and the jnp oracle
agree bit-for-bit).  The blocks stay on the lane axis: element i meets its
block partner at distance d through a lane rotation of the tile by d (the
TPU cannot split the lane axis into [nb, m]), and a partner counts only
when ``i % m + d`` stays inside the block.  Tiles are a multiple of both m
and the 128-lane width, so no block straddles a tile edge.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["nm_mask_pallas"]


def _kernel(x_ref, o_ref, *, n, m):
    a = jnp.abs(x_ref[...].astype(jnp.float32))
    tr, tk = a.shape
    pos = jax.lax.broadcasted_iota(jnp.int32, (tr, tk), 1) % m
    rank = jnp.zeros((tr, tk), jnp.int32)
    for d in range(1 - m, m):
        if d == 0:
            continue
        partner = pltpu.roll(a, (-d) % tk, 1)  # partner[i] = a[i + d]
        beats = (partner > a) if d > 0 else (partner >= a)
        inside = (pos + d >= 0) & (pos + d < m)
        rank = rank + jnp.where(beats & inside, 1, 0)
    o_ref[...] = jnp.where(rank < n, 1.0, 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "m", "tr", "tk", "interpret"))
def nm_mask_pallas(x: jnp.ndarray, n: int, m: int, *, tr: int = 256,
                   tk: int = 512, interpret: bool = True) -> jnp.ndarray:
    """Keep-mask (float32 0/1) of per-m-block top-n along the last axis.

    x: [R, K]; K is zero-padded to a multiple of the tile width (a multiple
    of lcm(m, 128)) internally.  Zero-padding is safe: padded blocks lie
    past K and are cropped from the output.
    """
    assert x.ndim == 2
    R, K = x.shape
    unit = math.lcm(m, 128)
    tk = unit * max(1, tk // unit)
    x_p = jnp.pad(x, (((0, (-R) % tr), (0, (-K) % tk))))
    Rp, Kp = x_p.shape

    out = pl.pallas_call(
        functools.partial(_kernel, n=n, m=m),
        grid=(Rp // tr, Kp // tk),
        in_specs=[pl.BlockSpec((tr, tk), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((tr, tk), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Rp, Kp), jnp.float32),
        interpret=interpret,
    )(x_p)
    return out[:R, :K]
