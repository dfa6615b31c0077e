"""Pallas TPU kernel for n:m:g sparse-dense matmul (paper §5.1, Fig 6 —
re-architected for the MXU).

Computes ``Y[M, R] = X[M, K] @ A^T`` where A is the canonical [R, K(sparse)]
view of a :class:`GroupedNMTensor` and X the activations (one row per
token).  ``nmg_spmm_pallas`` keeps the historical ``C[R, N] = A @ B``
contract on top of it.

TPU adaptation of the paper's AVX microkernel:

* The CPU kernel broadcasts each sparse value into a vector register and
  indirectly loads B rows (Fig 6 steps 1-4).  A TPU core has no cheap
  dynamic row gather: Mosaic refuses row slices that are not aligned to
  the (8, 128) tile, and single-row slices of B are exactly that.  So the
  kernel moves the indirection onto the MXU: for one fiber group (``gr``
  rows sharing a chunk permutation) and one *window* of compressed
  positions, it builds the one-hot selection ``S[k, p] = (k == cols[p])``
  from an iota and the group's gather plan (``SpmmPlan.cols``, the
  absolute K row of every stored value), scatters the compressed values
  into a dense ``[gr, window K]`` tile with one matmul (``val @ S^T`` —
  exact, every entry is one value or zero), and contracts that tile with
  the activations' window of K.  Weights are read from HBM compressed
  (``n/m`` of the dense bytes plus the plan); the MXU does dense work on
  the decompressed tile.
* Windows are static: a whole number of chunks (chunk position p carries
  pattern ``p // g``, so a chunk maps onto a contiguous K range) and a
  multiple of the 128-lane tile, so every slice in the kernel is static
  and aligned.  ``target_depth`` widens the window.
* Every output group is computed by the same sequence of window dots, so
  a group's result does not depend on how many groups share a grid step,
  on the other groups of a launch (fused QKV), or on the schedule.

Storage is read through free reshapes of the layout's arrays: ``val``
``[R_pad, nblocks, n]`` as a lane-dense ``[R_pad, nblocks*n]`` and the plan
``cols [Gr, nblocks*n]`` as ``[Gr, 1, nblocks*n]``.  The output is written
per group, ``[Gr, M_pad, gr]`` (a ``gr``-wide lane block equals the array's
last dim, so any ``gr`` is legal), and transposed to ``[M, R]`` by XLA.

Two schedules:

* ``stream=True`` (default) — grid ``(M tiles, group tiles)``; the whole
  ``[TM, K_pad]`` activation slab stays resident across the row groups
  (its block index does not change), the compressed value and plan blocks
  are pipelined by Pallas, and the windows loop inside the kernel.
* ``stream=False`` — grid ``(M tiles, group tiles, windows)`` with the
  window innermost and an f32 accumulator carried across it: only one
  window of activations and values is resident, for K extents whose slab
  would not fit VMEM.  Windows must then be uniform; a K extent with a
  ragged last window runs its windows in one grid step, as the streamed
  schedule does.

Both accumulate each group's windows in the same order, so their outputs
agree bitwise.  VMEM per step at bert-base widths (bf16, 1:4:16, gr=64,
K=3072, 4 groups, TM=128): activation slab 0.75 MiB, value block 0.38 MiB,
plan 0.1 MiB, each double-buffered, plus a 0.25 MiB accumulator and the
one-hot and tile temporaries — well inside v5e's 16 MiB scoped limit.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.layouts import GroupedNMTensor

__all__ = ["nmg_spmm_pallas", "nmg_pallas_call", "nmg_rows", "pad_k",
           "storage_views", "windows"]

#: lane width of a TPU vreg: window widths are multiples of it
_LANES = 128

#: output rows one grid step aims to cover (groups are batched per step
#: until they reach it), amortizing the per-step pipeline overhead
_ROWS_PER_STEP = 256

_NT = (((1,), (1,)), ((), ()))  # contract the last dims of both operands


def windows(n: int, m: int, g: int, nbn: int, target_depth: int) -> list:
    """Static windows ``(c0, c1, k0, k1)`` over one group's ``nbn``
    compressed positions: positions ``[c0, c1)`` multiply activation
    columns ``[k0, k1)``.  Each window is a whole number of chunks and a
    multiple of 128 positions (the last one may be shorter)."""
    cg = math.comb(m, n) * g
    cgn, cgm = cg * n, cg * m
    unit = math.lcm(cgn, _LANES)
    tc = unit * max(1, target_depth // unit)
    return [(c0, min(c0 + tc, nbn), c0 // cgn * cgm,
             min(c0 + tc, nbn) // cgn * cgm)
            for c0 in range(0, nbn, tc)]


def _window_dot(cols, vals, x, k0, cdt, precision):
    """One window of one group: decompress ``vals`` [gr, tc] into a dense
    [gr, tk] tile through the one-hot of ``cols`` [1, tc] (absolute K rows,
    window starting at ``k0``), then contract with ``x`` [TM, tk]."""
    tk, tc = x.shape[1], cols.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (tk, tc), 0) + k0
    onehot = jnp.where(rows == cols, 1.0, 0.0).astype(cdt)      # [tk, tc]
    tile = jax.lax.dot_general(vals.astype(cdt), onehot, _NT,
                               precision=precision,
                               preferred_element_type=jnp.float32)
    return jax.lax.dot_general(x, tile.astype(cdt), _NT,
                               precision=precision,
                               preferred_element_type=jnp.float32)


def _kernel(*refs, nsrc, wins, gr, tg, k_steps, tk_step, cdt, precision,
            epilogue):
    cols_refs, val_refs = refs[:nsrc], refs[nsrc:2 * nsrc]
    x_ref, o_ref = refs[2 * nsrc], refs[2 * nsrc + 1]
    acc_refs = refs[2 * nsrc + 2:]
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        for acc_ref in acc_refs:
            acc_ref[...] = jnp.zeros_like(acc_ref)

    kbase = ki * tk_step
    for cols_ref, val_ref, acc_ref in zip(cols_refs, val_refs, acc_refs):
        def group(gi, carry, cols_ref=cols_ref, val_ref=val_ref,
                  acc_ref=acc_ref):
            r0 = pl.multiple_of(gi * gr, gr)
            acc = acc_ref[gi]
            for c0, c1, k0, k1 in wins:
                acc = acc + _window_dot(
                    cols_ref[gi, :, c0:c1], val_ref[pl.ds(r0, gr), c0:c1],
                    x_ref[:, k0:k1], kbase + k0, cdt, precision)
            acc_ref[gi] = acc
            return carry

        jax.lax.fori_loop(0, tg, group, 0)

    @pl.when(ki == k_steps - 1)
    def _done():
        out = epilogue(*(acc_ref[...] for acc_ref in acc_refs))
        o_ref[...] = out.astype(o_ref.dtype)


def _sublanes(*dtypes) -> int:
    """Rows of one (sublane x 128) tile for the narrowest dtype."""
    return 32 // min(jnp.dtype(d).itemsize for d in dtypes)


def _groups_per_step(G: int, gr: int, sub: int) -> int:
    for d in range(1, G + 1):
        if G % d == 0 and d * gr >= _ROWS_PER_STEP and (d * gr) % sub == 0:
            return d
    return G


def nmg_pallas_call(val2, cols3, x, *, n: int, m: int, g: int, gr: int,
                    out_dtype, tm: int, target_depth: int, stream: bool,
                    interpret: bool, gate_act=None) -> jnp.ndarray:
    """The raw launch on the storage arrays: ``val2`` [R_pad, nblocks*n],
    ``cols3`` [Gr, 1, nblocks*n] (the gather plan), ``x`` [M, K_pad].
    Returns the uncropped product [M, R_pad] in ``out_dtype``.

    ``gate_act`` (the fused gated-MLP epilogue) pairs output group ``i``
    with group ``i + Gr/2`` and returns ``act(u) * v`` [M, R_pad/2], both
    halves cast to ``out_dtype`` first — the op order of the sequential
    projection, split, act and multiply."""
    R_pad, nbn = val2.shape
    Gr = cols3.shape[0]
    M, K_pad = x.shape
    nsrc = 2 if gate_act is not None else 1
    G = Gr // nsrc
    cdt = jnp.promote_types(val2.dtype, x.dtype)
    precision = (jax.lax.Precision.HIGHEST if cdt == jnp.float32 else None)
    out_dtype = jnp.dtype(out_dtype)

    sub = _sublanes(cdt, out_dtype)
    TM = min(-(-M // sub) * sub, max(sub, tm // sub * sub))
    M_pad = -(-M // TM) * TM
    x = jnp.pad(x.astype(cdt), ((0, M_pad - M), (0, 0)))

    wins = windows(n, m, g, nbn, target_depth)
    if not stream and len(wins) > 1 and nbn % (wins[0][1] - wins[0][0]) == 0:
        c0, tc, k0, tk = wins[0]
        k_steps, step_wins = len(wins), [(0, tc, 0, tk)]
    else:
        k_steps, tc, tk, step_wins = 1, nbn, K_pad, wins
    tg = _groups_per_step(G, gr, _sublanes(val2.dtype))
    offs = [0, G // tg][:nsrc]

    if gate_act is None:
        epilogue = lambda acc: acc                      # noqa: E731
    else:
        def epilogue(u, v):
            return gate_act(u.astype(out_dtype)) * v.astype(out_dtype)

    out = pl.pallas_call(
        functools.partial(_kernel, nsrc=nsrc, wins=step_wins, gr=gr, tg=tg,
                          k_steps=k_steps, tk_step=tk, cdt=cdt,
                          precision=precision, epilogue=epilogue),
        grid=(M_pad // TM, G // tg, k_steps),
        in_specs=(
            [pl.BlockSpec((tg, 1, tc), lambda mi, gi, ki, o=o: (gi + o, 0, ki))
             for o in offs]
            + [pl.BlockSpec((tg * gr, tc), lambda mi, gi, ki, o=o: (gi + o, ki))
               for o in offs]
            + [pl.BlockSpec((TM, tk), lambda mi, gi, ki: (mi, ki))]),
        out_specs=pl.BlockSpec((tg, TM, gr), lambda mi, gi, ki: (gi, mi, 0)),
        out_shape=jax.ShapeDtypeStruct((G, M_pad, gr), out_dtype),
        scratch_shapes=[pltpu.VMEM((tg, TM, gr), jnp.float32)] * nsrc,
        interpret=interpret,
    )(*[cols3] * nsrc, *[val2] * nsrc, x)
    return out.transpose(1, 0, 2).reshape(M_pad, G * gr)[:M]


def storage_views(a: GroupedNMTensor):
    """The kernel's free reshapes of a layout: (val2, cols3)."""
    cols = a.gather_plan().cols
    return a.val.reshape(a.val.shape[0], -1), cols.reshape(cols.shape[0], 1, -1)


def pad_k(a: GroupedNMTensor, x: jnp.ndarray) -> jnp.ndarray:
    """Zero-pad the activations' K axis to the compressed extent."""
    K_pad = a.val.shape[1] * a.m
    return jnp.pad(x, ((0, 0), (0, K_pad - x.shape[1])))


def nmg_rows(a: GroupedNMTensor, x: jnp.ndarray, *, out_dtype, tm: int,
             target_depth: int, stream: bool, interpret: bool):
    """Y = X @ A_canonical^T: ``x`` [M, K] -> [M, R] in ``out_dtype``."""
    val2, cols3 = storage_views(a)
    y = nmg_pallas_call(val2, cols3, pad_k(a, x), n=a.n, m=a.m, g=a.g,
                        gr=a.gr, out_dtype=out_dtype, tm=tm,
                        target_depth=target_depth, stream=stream,
                        interpret=interpret)
    R = a.dense_shape[1 - a.sparse_dim % 2]
    return y[:, :R]


@functools.partial(
    jax.jit, static_argnames=("tn", "interpret", "target_depth", "stream")
)
def nmg_spmm_pallas(a: GroupedNMTensor, b: jnp.ndarray, *, tn: int = 128,
                    interpret: bool = True, target_depth: int = 128,
                    stream: bool = True) -> jnp.ndarray:
    """C = A_canonical @ B via the Pallas kernel.  Returns f32 [R, N].

    ``tn`` is the tile of B's columns (the kernel's activation rows) per
    grid step; ``stream`` picks the schedule (see the module docstring)."""
    return nmg_rows(a, b.T, out_dtype=jnp.float32, tm=tn,
                    target_depth=target_depth, stream=stream,
                    interpret=interpret).T
