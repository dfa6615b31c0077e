"""Decode megakernels: fused QKV and fused gated-FFN Pallas launches.

The decode kernel (:mod:`repro.kernels.nmg_gemv`) launches once *per
projection*.  The paper's argument (and the Hoefler et al. survey's) is
that grouped n:m only pays when the per-call overheads are amortized
across the whole operator — so the decode step wants one launch per fused
operator, not one per weight.

Two fusions, both exploiting n:m:g storage invariants, both launches of
the shared kernel in :mod:`repro.kernels.nmg_spmm`:

* **QKV** (:func:`nmg_qkv_pallas`): ``wq``/``wk``/``wv`` share the
  contraction axis (d_model) and, when sparsified together, the
  (n, m, g, gr) format.  Their compressed storage concatenates along the
  canonical output-row axis — ``val`` on rows, the gather plan on fiber
  groups, legal because conversion pads every operand's rows to a ``gr``
  multiple — so **one** launch computes all three projections.  Every
  fiber group runs the same window dots as in three separate launches,
  so fused and sequential outputs agree **bitwise** (pinned by
  tests/test_megakernel).
* **Gated FFN** (:func:`nmg_ffn_pallas`): the gated-MLP packs ``w1`` and
  ``gate`` into one ``[D, 2F]`` weight; the fusion is the in-kernel gate
  epilogue.  Each output group carries *two* f32 accumulators — the ``u``
  group and its ``v`` partner at row offset +F — and the last step casts
  both to the activation dtype and emits ``act(u) * v`` directly, exactly
  the op order ``models/transformer._sublayer_ffn`` runs after a
  sequential projection (split -> act -> multiply).  silu is
  bitwise-stable (the logistic lowers to one primitive); approximate-
  gelu's tanh polynomial may differ by ulps depending on what XLA fuses
  it with.

Both keep the gemv contract: f32 accumulation, one dtype cast in the
epilogue.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core.layouts import GroupedNMTensor
from repro.kernels.nmg_spmm import nmg_pallas_call, pad_k, storage_views

__all__ = [
    "act_fn",
    "fusable_qkv",
    "fusable_ffn",
    "fused_segments",
    "nmg_qkv_pallas",
    "nmg_ffn_pallas",
]


def act_fn(name: str):
    """The model stack's activation by name (gelu is the tanh approximation
    ``models/transformer._act`` uses — the fused epilogue must match it
    bitwise).  silu is spelled out as ``x / (1 + exp(-x))`` op by op: XLA
    expands ``jax.nn.silu``'s logistic into exactly these ops (bitwise
    equal in f32 and bf16), and Mosaic cannot lower a bf16 logistic."""
    if name == "silu":
        return lambda x: x * (1 / (1 + jnp.exp(-x)))
    return functools.partial(jax.nn.gelu, approximate=True)


def _canon_R(w: GroupedNMTensor) -> int:
    return w.dense_shape[1 - (w.sparse_dim % 2)]


def fusable_qkv(ws: Sequence) -> bool:
    """Static (trace-time) eligibility of a projection list for the fused
    QKV launch: all grouped n:m:g, same (n, m, g, gr) format, same
    contraction extent, same stored dtype, sparse along the input axis."""
    if not ws or not all(isinstance(w, GroupedNMTensor) for w in ws):
        return False
    w0 = ws[0]
    for w in ws:
        if (w.n, w.m, w.g, w.gr) != (w0.n, w0.m, w0.g, w0.gr):
            return False
        if w.sparse_dim % 2 != 0:  # canonical view must be [R(out), K(in)]
            return False
        if w.dense_shape[0] != w0.dense_shape[0]:  # shared K
            return False
        if w.val.shape[1:] != w0.val.shape[1:] or w.val.dtype != w0.val.dtype:
            return False
        if w.blk_idx.shape[1:] != w0.blk_idx.shape[1:]:
            return False
        if w.val.shape[0] != w.blk_idx.shape[0] * w.gr:  # rows pad to gr
            return False
    return True


def fusable_ffn(w, F: int) -> bool:
    """Static eligibility of a packed ``[D, 2F]`` gated-MLP weight for the
    dual-accumulator kernel: grouped n:m:g, sparse along the input axis,
    exactly 2F unpadded rows, and the u/v halves splitting on a fiber-group
    boundary (F divisible by gr)."""
    if not isinstance(w, GroupedNMTensor) or w.sparse_dim % 2 != 0:
        return False
    if _canon_R(w) != 2 * F or F <= 0:
        return False
    # no row padding (group boundaries must be real rows) + aligned halves
    return w.val.shape[0] == 2 * F and F % w.gr == 0


def fused_segments(ws: Sequence) -> list:
    """Per-projection (row offset in the concatenated padded operand,
    canonical row count) — where each output lands after a fused launch."""
    segs, off = [], 0
    for w in ws:
        segs.append((off, _canon_R(w)))
        off += w.val.shape[0]
    return segs


@functools.partial(
    jax.jit, static_argnames=("out_dtype", "tm", "interpret", "target_depth")
)
def nmg_qkv_pallas(ws: Sequence, b: jnp.ndarray, *, out_dtype=None,
                   tm: int = 128, interpret: bool = True,
                   target_depth: int = 128) -> tuple:
    """All projections of ``ws`` against one decode-shaped ``b`` [K, M] in
    a single launch.  Returns one [R_i, M] array per projection, in
    ``out_dtype`` (default f32)."""
    assert fusable_qkv(ws), "operands not fusable; route per-projection"
    w0 = ws[0]
    val2 = jnp.concatenate([storage_views(w)[0] for w in ws], axis=0)
    cols3 = jnp.concatenate([storage_views(w)[1] for w in ws], axis=0)
    out = nmg_pallas_call(
        val2, cols3, pad_k(w0, b.T), n=w0.n, m=w0.m, g=w0.g, gr=w0.gr,
        out_dtype=jnp.float32 if out_dtype is None else out_dtype, tm=tm,
        target_depth=target_depth, stream=True, interpret=interpret)
    return tuple(out[:, off:off + R].T for off, R in fused_segments(ws))


@functools.partial(
    jax.jit,
    static_argnames=("act", "out_dtype", "tm", "interpret", "target_depth"),
)
def nmg_ffn_pallas(w: GroupedNMTensor, b: jnp.ndarray, *, act: str = "silu",
                   out_dtype=None, tm: int = 128, interpret: bool = True,
                   target_depth: int = 128) -> jnp.ndarray:
    """Gated-MLP pair in one launch: ``w`` is the packed [D, 2F] weight
    (sparse_dim=0), ``b`` [D, M] the decode activations.  Returns
    ``act(u) * v`` = [F, M] in ``out_dtype`` (default f32)."""
    F = _canon_R(w) // 2
    assert fusable_ffn(w, F), "weight not fusable; route per-projection"
    val2, cols3 = storage_views(w)
    out = nmg_pallas_call(
        val2, cols3, pad_k(w, b.T), n=w.n, m=w.m, g=w.g, gr=w.gr,
        out_dtype=jnp.float32 if out_dtype is None else out_dtype, tm=tm,
        target_depth=target_depth, stream=True, interpret=interpret,
        gate_act=act_fn(act))
    return out.T
