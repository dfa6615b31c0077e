"""Pallas TPU kernel for decode-shaped n:m:g sparse-dense matmul.

Computes ``C[R, M] = A @ B`` where A is the canonical [R, K(sparse)] view of
a :class:`GroupedNMTensor` and B is a *narrow* dense right operand
[K, M <= ~16] — the shape a serving decode step produces (B = the batch of
per-slot activations, transposed).

On the TPU this is the kernel of :mod:`repro.kernels.nmg_spmm` with the
decode regime's settings:

* the activations ride on the sublane axis, so a decode batch pads to one
  8-row (f32) or 16-row (bf16) tile instead of a 128-lane one, and the
  whole ``[M, K]`` slab stays resident while the compressed weights stream
  through — the weight bytes, read compressed, are what a decode step
  pays for;
* f32 accumulation with a dtype-preserving epilogue: the last step casts
  once into the caller-requested dtype.  The serving path asks for the
  activation dtype, so no f32 copy of the output is written.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.layouts import GroupedNMTensor
from repro.kernels.nmg_spmm import nmg_rows

__all__ = ["nmg_gemv_pallas"]


@functools.partial(
    jax.jit, static_argnames=("out_dtype", "tm", "interpret", "target_depth")
)
def nmg_gemv_pallas(a: GroupedNMTensor, b: jnp.ndarray, *,
                    out_dtype=None, tm: int = 128, interpret: bool = True,
                    target_depth: int = 128) -> jnp.ndarray:
    """C = A_canonical @ B via the decode kernel.  Returns [R, M] in
    ``out_dtype`` (default: f32, matching the SpMM contract).  ``tm`` caps
    the activation rows per grid step."""
    out_dtype = jnp.float32 if out_dtype is None else out_dtype
    return nmg_rows(a, b.T, out_dtype=out_dtype, tm=tm,
                    target_depth=target_depth, stream=True,
                    interpret=interpret).T
