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
  exact, every entry is one value or zero).  Weights are read from HBM
  compressed (``n/m`` of the dense bytes plus the plan); the MXU does
  dense work on the decompressed tile.
* The useful product runs on whole 128-lane tiles.  Consecutive groups'
  decompressed tiles are written side by side into one VMEM scratch tile
  ``[W, window K]``, ``W = tile_width(gr) = lcm(gr, 128)`` (two groups at
  gr=64), and the activations' window of K is contracted against all of
  it in one dot, ``[TM, tk] x [W, tk]^T -> [TM, W]``: a ``gr``-wide dot
  would leave half of the 128x128 MXU empty at gr=64.  A lone last group
  (``Gr`` not a multiple of ``W / gr``) gets zero partner rows.
* Windows are static: a whole number of chunks (chunk position p carries
  pattern ``p // g``, so a chunk maps onto a contiguous K range) and a
  multiple of the 128-lane tile, so every slice in the kernel is static
  and aligned.  ``target_depth`` widens the window.
* Every output group is computed by the same sequence of window dots, all
  of one shape ``[TM, tk] x [W, tk]^T``, and an output column reads only
  its own row of the tile, so a group's result does not depend on how
  many groups share a grid step, on its partner in the tile (another
  group of the launch, as in fused QKV, or the zero pad), or on the
  schedule.

Storage is read through free reshapes of the layout's arrays: ``val``
``[R_pad, nblocks, n]`` as a lane-dense ``[R_pad, nblocks*n]`` and the plan
``cols [Gr, nblocks*n]`` as ``[Gr, 1, nblocks*n]``, each split into its
sources (one, or the u and v halves of a gated pair).  The f32
accumulator is ``[tiles, TM, W]``, lane-dense, and the output is written
in the ``[M_pad, R_pad]`` order the callers read, ``W``-lane tiles at a
time: no transpose follows the call.

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
agree bitwise.  VMEM per step at the shipped default (bf16, 1:4:16, gr=64,
grid schedule, TM=1024, 256-position windows, so a window is 1024 columns
of K whatever K is): activation window 2 MiB, f32 output block [1024, 256]
1 MiB, value block 0.13 MiB and plan 0.03 MiB, each double-buffered, plus
the 1 MiB accumulator [2, 1024, 128], the 0.25 MiB [128, 1024] tile and the
one-hot, group-tile and dot temporaries (~2.3 MiB) — ~10 MiB of v5e's
16 MiB scoped limit.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.layouts import GroupedNMTensor
from repro.obs.registry import REGISTRY

__all__ = ["nmg_spmm_pallas", "nmg_pallas_call", "nmg_rows", "pad_k",
           "storage_views", "windows"]

#: lane width of a TPU vreg: window widths are multiples of it
_LANES = 128

#: output rows one grid step aims to cover (groups are batched per step
#: until they reach it), amortizing the per-step pipeline overhead
_ROWS_PER_STEP = 256

_NT = (((1,), (1,)), ((), ()))  # contract the last dims of both operands

# (kernel, path) -> number of traces routed there.  A ``repro.obs``
# registry family: same Counter semantics at every call site, but the
# counts join the unified telemetry snapshot and each routing decision
# becomes a timestamped ``kernel_route`` event on the kernel track when
# the flight recorder is enabled.  ``kernels/ops.py`` counts its routes
# here; every launch adds ``("nmg_pallas", "w<tile_width>")``.
_KERNEL_COUNTS = REGISTRY.family(
    "kernel_routes",
    help="trace-time kernel routing: (kernel, path) -> traces",
    trace_as="kernel_route", track="kernel")


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


def tile_width(gr: int) -> int:
    """Output lanes of one useful dot: whole fiber groups side by side,
    ``lcm(gr, 128)`` (two groups at gr=64, one at gr=128)."""
    return math.lcm(gr, _LANES)


def _decompress(cols, vals, k0, tk, cdt, precision):
    """One window of one group: scatter ``vals`` [gr, tc] into a dense
    [gr, tk] tile through the one-hot of ``cols`` [1, tc] (absolute K rows,
    window starting at ``k0``)."""
    tc = cols.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (tk, tc), 0) + k0
    onehot = jnp.where(rows == cols, 1.0, 0.0).astype(cdt)      # [tk, tc]
    tile = jax.lax.dot_general(vals.astype(cdt), onehot, _NT,
                               precision=precision,
                               preferred_element_type=jnp.float32)
    return tile.astype(cdt)


def _kernel(*refs, nsrc, wins, gr, per, tw, G, k_steps, tk_step, cdt,
            precision, epilogue):
    cols_refs, val_refs = refs[:nsrc], refs[nsrc:2 * nsrc]
    x_ref, o_ref = refs[2 * nsrc], refs[2 * nsrc + 1]
    acc_refs, tile_ref = refs[2 * nsrc + 2:-1], refs[-1]
    gi, ki = pl.program_id(1), pl.program_id(2)
    W = per * gr

    @pl.when(ki == 0)
    def _init():
        for acc_ref in acc_refs:
            acc_ref[...] = jnp.zeros_like(acc_ref)

    kbase = ki * tk_step
    for cols_ref, val_ref, acc_ref in zip(cols_refs, val_refs, acc_refs):
        def wide(t, carry, cols_ref=cols_ref, val_ref=val_ref,
                 acc_ref=acc_ref):
            acc = acc_ref[t]                                    # [TM, W]
            for c0, c1, k0, k1 in wins:
                tk = k1 - k0

                def fill(j, carry, c0=c0, c1=c1, k0=k0, tk=tk):
                    q, rows = t * per + j, pl.ds(j * gr, gr)

                    def put():
                        tile_ref[rows, :tk] = _decompress(
                            cols_ref[q, :, c0:c1],
                            val_ref[pl.ds(pl.multiple_of(q * gr, gr), gr),
                                    c0:c1],
                            kbase + k0, tk, cdt, precision)

                    if G % per == 0:
                        put()
                    else:                 # a lone last group: zero partner
                        real = gi * tw * per + q < G
                        pl.when(real)(put)

                        @pl.when(jnp.logical_not(real))
                        def _pad():
                            tile_ref[rows, :tk] = jnp.zeros((gr, tk), cdt)
                    return carry

                if per <= 8:              # static rows in the tile
                    for j in range(per):
                        fill(j, 0)
                else:
                    jax.lax.fori_loop(0, per, fill, 0)
                acc = acc + jax.lax.dot_general(
                    x_ref[:, k0:k1], tile_ref[:, :tk], _NT,
                    precision=precision, preferred_element_type=jnp.float32)
            acc_ref[t] = acc
            return carry

        jax.lax.fori_loop(0, tw, wide, 0)

    @pl.when(ki == k_steps - 1)
    def _done():
        for t in range(tw):
            out = epilogue(*(acc_ref[t] for acc_ref in acc_refs))
            o_ref[:, t * W:(t + 1) * W] = out.astype(o_ref.dtype)


def _sublanes(*dtypes) -> int:
    """Rows of one (sublane x 128) tile for the narrowest dtype."""
    return 32 // min(jnp.dtype(d).itemsize for d in dtypes)


def _groups_per_step(G: int, gr: int) -> int:
    """Fiber groups one grid step covers: whole ``tile_width`` tiles, the
    fewest that reach ``_ROWS_PER_STEP`` output lanes and divide the
    ``ceil(G / per)`` tiles of ``per`` groups, else all of them."""
    W = tile_width(gr)
    per = W // gr
    tiles = -(-G // per)
    for d in range(1, tiles + 1):
        if tiles % d == 0 and d * W >= _ROWS_PER_STEP:
            return d * per
    return tiles * per


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
    if interpret:
        # when the interpreted grid and loops collapse to one step, XLA's
        # CPU backend folds a transposed ``x`` into the bf16 dot, a form
        # its dot runtime refuses; the barrier keeps ``x`` as it is
        x = jax.lax.optimization_barrier(x)

    wins = windows(n, m, g, nbn, target_depth)
    if not stream and len(wins) > 1 and nbn % (wins[0][1] - wins[0][0]) == 0:
        c0, tc, k0, tk = wins[0]
        k_steps, step_wins = len(wins), [(0, tc, 0, tk)]
    else:
        k_steps, tc, tk, step_wins = 1, nbn, K_pad, wins
    W = tile_width(gr)
    per, tg = W // gr, _groups_per_step(G, gr)
    steps = -(-G // tg)
    _KERNEL_COUNTS[("nmg_pallas", f"w{W}")] += 1

    if gate_act is None:
        epilogue = lambda acc: acc                      # noqa: E731
    else:
        def epilogue(u, v):
            return gate_act(u.astype(out_dtype)) * v.astype(out_dtype)

    # each source (the u and v halves of a gated pair) as its own slab of
    # groups: free reshapes, and a lone last group's tile stays in its half
    val3 = val2.reshape(nsrc, G * gr, nbn)
    cols4 = cols3.reshape(nsrc, G, 1, nbn)
    tk_max = max(k1 - k0 for _, _, k0, k1 in step_wins)
    out = pl.pallas_call(
        functools.partial(_kernel, nsrc=nsrc, wins=step_wins, gr=gr,
                          per=per, tw=tg // per, G=G, k_steps=k_steps,
                          tk_step=tk, cdt=cdt, precision=precision,
                          epilogue=epilogue),
        grid=(M_pad // TM, steps, k_steps),
        in_specs=(
            [pl.BlockSpec((None, tg, 1, tc),
                          lambda mi, gi, ki, s=s: (s, gi, 0, ki))
             for s in range(nsrc)]
            + [pl.BlockSpec((None, tg * gr, tc),
                            lambda mi, gi, ki, s=s: (s, gi, ki))
               for s in range(nsrc)]
            + [pl.BlockSpec((TM, tk), lambda mi, gi, ki: (mi, ki))]),
        out_specs=pl.BlockSpec((TM, tg * gr), lambda mi, gi, ki: (mi, gi)),
        out_shape=jax.ShapeDtypeStruct((M_pad, steps * tg * gr), out_dtype),
        scratch_shapes=([pltpu.VMEM((tg // per, TM, W), jnp.float32)] * nsrc
                        + [pltpu.VMEM((W, tk_max), cdt)]),
        interpret=interpret,
    )(*[cols4] * nsrc, *[val3] * nsrc, x)
    return out[:M, :G * gr]


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
