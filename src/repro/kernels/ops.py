"""Public jit'd entry points for the sparsity kernels.

Each op picks the Pallas kernel on TPU and interpret-mode (or a pure-XLA
production path) on CPU, pads/crops shapes, and exposes a layout-level API
that core/ops.py registers with the dispatcher.

The n:m:g matmul family is **shape-routed** (the Scorch argument: sparse
kernel choice depends on format *and* operand shape):

  right operand        path                       regime
  -----------------    ------------------------   -------------------------
  M <= decode_m_max    ``nmg_gemv``  (decode)     serving decode GEMV: tiny
                                                  activation batch, dtype
                                                  epilogue
  M >  decode_m_max    ``nmg_spmm``  (prefill)    wide right operand, token
                                                  tiled, f32 accumulator out

The routing decisions — the gemv/spmm crossover ``decode_m_max``, the
spmm gathered-block cap, and the Pallas gemv tile config — come from
``repro.tune.routing``: a lookup into the active
:class:`~repro.tune.table.TuningTable` (device kind + shape bucket) with
shipped defaults (``DECODE_M_MAX``, ``_SPMM_BLOCK_ELEMS`` below) that
reproduce the historical hard-coded heuristics exactly when no table is
loaded.  A table changes only *which* path runs, never its output — save
a Pallas window depth (``target_depth``), which reassociates the f32 sum.
Lookups happen at trace time, so load tables before compiling consumers
(the serving warmup hook does this in the right order).

Both paths consume the :class:`~repro.core.layouts.SpmmPlan` gather plan
the conversion precomputed (``GroupedNMTensor.gather_plan``) instead of
re-deriving index math per call.  ``kernel_counters`` records which path
each *trace* took — including the router's choice and its provenance,
e.g. ``("nmg_matmul", "gemv[table]")`` — the no-dense-fallback evidence
the serving perf smoke asserts on (dispatch is trace-time, so counters
count compilations, not calls).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.layouts import GroupedNMTensor
from repro.kernels import ref as kref
from repro.tune import routing
from repro.kernels.fused_sparse_matmul import matmul_threshold_pallas
from repro.kernels.nm_mask import nm_mask_pallas
from repro.kernels.nmg_fused import (
    act_fn,
    fusable_ffn,
    fusable_qkv,
    fused_segments,
    nmg_ffn_pallas,
    nmg_qkv_pallas,
)
from repro.kernels.nmg_gemv import nmg_gemv_pallas
from repro.kernels.nmg_spmm import _KERNEL_COUNTS, nmg_spmm_pallas

__all__ = [
    "on_tpu",
    "DECODE_M_MAX",
    "nmg_matmul",
    "nmg_spmm",
    "nmg_spmm_xla",
    "nmg_gemv",
    "nmg_gemv_xla",
    "nmg_linear",
    "nmg_qkv",
    "nmg_qkv_xla",
    "nmg_ffn",
    "nmg_ffn_xla",
    "maybe_fused_qkv",
    "maybe_fused_ffn",
    "nm_mask",
    "matmul_threshold",
    "kernel_counters",
    "reset_kernel_counters",
    "predict_route",
]

#: shipped-default decode width (single source of truth:
#: ``repro.tune.routing``); the router consults the active tuning table
#: first and falls back to this, so the name stays importable for code
#: and docs that reference the heuristic
DECODE_M_MAX = routing.DEFAULT_DECODE_M_MAX

#: shipped-default cap on the gathered-operand size (elements) of one XLA
#: spmm block — bounds peak memory like the old per-group scan did,
#: without its group-at-a-time serialization; tuned per device via
#: ``spmm_block_elems`` table entries
_SPMM_BLOCK_ELEMS = routing.DEFAULT_SPMM_BLOCK_ELEMS


def kernel_counters() -> dict:
    """Trace-time routing evidence: {(kernel, path): count}."""
    return dict(_KERNEL_COUNTS)


def reset_kernel_counters() -> None:
    _KERNEL_COUNTS.clear()


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# prefill-shaped path: wide right operand
# ---------------------------------------------------------------------------


def nmg_spmm(a: GroupedNMTensor, b: jnp.ndarray, *, use_pallas: bool | None = None
             ) -> jnp.ndarray:
    """C = A_canonical[R, K] @ B[K, N] (f32).

    Pallas kernel on TPU (interpret-mode validation on CPU via tests);
    the batched gather-einsum XLA path otherwise.
    """
    if use_pallas is None:
        use_pallas = on_tpu()
    _KERNEL_COUNTS[("nmg_spmm", "pallas" if use_pallas else "xla")] += 1
    if use_pallas:
        cfg, src = routing.spmm_pallas_config(**_route_ctx(a, b.dtype))
        sched = "stream" if cfg["stream"] else "grid"
        _KERNEL_COUNTS[("nmg_spmm_pallas", f"{sched}[{src}]")] += 1
        with jax.named_scope(f"repro.nmg_spmm_pallas[{sched}]"):
            return nmg_spmm_pallas(a, b, interpret=not on_tpu(),
                                   tn=cfg["tn"],
                                   target_depth=cfg["target_depth"],
                                   stream=cfg["stream"])
    return nmg_spmm_xla(a, b)


def _gather_block(b_p, cols, val_g):
    """One activation-stationary block: gather the compressed B rows for a
    slab of fiber-groups and contract in a single einsum.

    cols  [G, nb*n]  compressed-column plan slab
    val_g [G, gr, nb*n]
    -> [G, gr, N] f32
    """
    bg = jnp.take(b_p, cols.reshape(-1), axis=0)
    bg = bg.reshape(*cols.shape, b_p.shape[1])           # [G, nb*n, N]
    return jnp.einsum(
        "grk,gkn->grn",
        val_g.astype(jnp.float32), bg.astype(jnp.float32),
    )


def nmg_spmm_xla(a: GroupedNMTensor, b: jnp.ndarray, *,
                 block_elems: int | None = None) -> jnp.ndarray:
    """Pure-XLA production path: one batched gather + blocked einsum over
    the precomputed column plan.  Replaces the old per-fiber-group
    ``lax.scan`` (Gr sequential micro-matmuls) with ceil(Gr / block)
    vectorized blocks, where the block size caps the gathered operand at
    ``block_elems`` elements (the old scan's memory-safety property,
    without its serialization).  ``block_elems`` defaults to the routing
    lookup (tuned per device; shipped default ``_SPMM_BLOCK_ELEMS``) and
    is resolved at trace time."""
    if block_elems is None:
        block_elems, _ = routing.spmm_block_elems()
    return _nmg_spmm_xla(a, b, block_elems=int(block_elems))


@functools.partial(jax.jit, static_argnames=("block_elems",))
def _nmg_spmm_xla(a: GroupedNMTensor, b: jnp.ndarray, *,
                  block_elems: int) -> jnp.ndarray:
    gr = a.gr
    val = a.val                                # [R_pad, nblocks, n]
    R_pad, nblocks, n = val.shape
    cols = a.gather_plan().cols                # [Gr, nblocks*n]
    Gr = cols.shape[0]
    K_pad = nblocks * a.m
    K, N = b.shape
    b_p = jnp.pad(b, ((0, K_pad - K), (0, 0)))
    val_g = val.reshape(Gr, gr, nblocks * n)

    per_group = nblocks * n * N                # gathered elements per group
    gb = max(1, min(Gr, block_elems // max(1, per_group)))
    nblk = -(-Gr // gb)
    if nblk == 1:
        out = _gather_block(b_p, cols, val_g)  # [Gr, gr, N]
    else:
        pad = nblk * gb - Gr
        cols_b = jnp.pad(cols, ((0, pad), (0, 0))).reshape(nblk, gb, -1)
        val_b = jnp.pad(val_g, ((0, pad), (0, 0), (0, 0))).reshape(
            nblk, gb, gr, -1
        )
        out = jax.lax.map(
            lambda xs: _gather_block(b_p, xs[0], xs[1]), (cols_b, val_b)
        )
        out = out.reshape(nblk * gb, gr, N)[:Gr]
    out = out.reshape(R_pad, N)
    sd = a.sparse_dim % 2
    R = a.dense_shape[1 - sd]
    return out[:R]


# ---------------------------------------------------------------------------
# decode-shaped path: narrow right operand (serving GEMV)
# ---------------------------------------------------------------------------


def nmg_gemv(a: GroupedNMTensor, b: jnp.ndarray, *, out_dtype=None,
             transpose_out: bool = False,
             use_pallas: bool | None = None) -> jnp.ndarray:
    """C = A_canonical[R, K] @ B[K, M] for decode-shaped (narrow) B.

    ``out_dtype`` is honored in the kernel epilogue (single cast after the
    f32 accumulation); default f32 mirrors the SpMM contract so the two
    paths are drop-in interchangeable.  ``transpose_out=True`` returns
    [M, R] — free on the XLA path (the einsum emits that order directly),
    a transpose of the narrow output on the Pallas path."""
    if use_pallas is None:
        use_pallas = on_tpu()
    _KERNEL_COUNTS[("nmg_gemv", "pallas" if use_pallas else "xla")] += 1
    if use_pallas:
        cfg, _ = routing.gemv_pallas_config(**_route_ctx(a, b.dtype))
        with jax.named_scope("repro.nmg_gemv_pallas"):
            out = nmg_gemv_pallas(a, b, out_dtype=out_dtype,
                                  interpret=not on_tpu(),
                                  tm=cfg["tm"],
                                  target_depth=cfg["target_depth"])
        return out.T if transpose_out else out
    return nmg_gemv_xla(a, b, out_dtype=out_dtype,
                        transpose_out=transpose_out)


@functools.partial(jax.jit, static_argnames=("out_dtype", "transpose_out"))
def nmg_gemv_xla(a: GroupedNMTensor, b: jnp.ndarray, *, out_dtype=None,
                 transpose_out: bool = False) -> jnp.ndarray:
    """Activation-stationary XLA decode path: B is small enough to gather
    in one shot, so the whole product is a single gather + einsum over the
    precomputed plan — the contraction :func:`nmg_spmm_xla` runs, so the
    two routes agree bitwise.  ``transpose_out=True`` returns [M, R] (the
    orientation ``nmg_linear`` wants)."""
    gr = a.gr
    val = a.val
    R_pad, nblocks, n = val.shape
    cols = a.gather_plan().cols                # [Gr, nblocks*n]
    Gr = cols.shape[0]
    K_pad = nblocks * a.m
    K, M = b.shape
    b_p = jnp.pad(b, ((0, K_pad - K), (0, 0)))

    xg = jnp.take(b_p, cols.reshape(-1), axis=0)
    xg = xg.reshape(Gr, nblocks * n, M)
    val_g = val.reshape(Gr, gr, nblocks * n)
    sd = a.sparse_dim % 2
    R = a.dense_shape[1 - sd]
    out = jnp.einsum("grk,gkm->grm", val_g.astype(jnp.float32),
                     xg.astype(jnp.float32))
    out = out.reshape(R_pad, M)[:R]
    if out_dtype is not None:
        out = out.astype(out_dtype)
    return out.T if transpose_out else out


# ---------------------------------------------------------------------------
# decode megakernels: fused QKV and fused gated-FFN
# ---------------------------------------------------------------------------


def _fused_ctx(ws, dtype) -> dict:
    """Routing context of a fused projection group: shared contraction
    extent, *summed* output rows."""
    w0 = ws[0]
    sd = w0.sparse_dim % 2
    return dict(K=w0.dense_shape[sd],
                R=sum(w.dense_shape[1 - (w.sparse_dim % 2)] for w in ws),
                fmt=(w0.n, w0.m, w0.g), gr=w0.gr, dtype=dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype", "transpose_out"))
def nmg_qkv_xla(ws, b: jnp.ndarray, *, out_dtype=None,
                transpose_out: bool = False) -> tuple:
    """XLA fused QKV: the per-projection gather-einsum over the
    row-concatenated plan — one take + one einsum for the whole group.
    Each group's contraction is independent and ordered exactly as in
    :func:`nmg_gemv_xla`, so the per-projection slices match the
    sequential path bitwise."""
    w0 = ws[0]
    gr = w0.gr
    val = jnp.concatenate([w.val for w in ws], axis=0)
    cols = jnp.concatenate([w.gather_plan().cols for w in ws], axis=0)
    R_pad, nblocks, n = val.shape
    Gr = cols.shape[0]
    K_pad = nblocks * w0.m
    K, M = b.shape
    b_p = jnp.pad(b, ((0, K_pad - K), (0, 0)))

    xg = jnp.take(b_p, cols.reshape(-1), axis=0)
    xg = xg.reshape(Gr, nblocks * n, M)
    val_g = val.reshape(Gr, gr, nblocks * n)
    out = jnp.einsum("grk,gkm->grm", val_g.astype(jnp.float32),
                     xg.astype(jnp.float32))
    out = out.reshape(R_pad, M)
    if out_dtype is not None:
        out = out.astype(out_dtype)
    outs = tuple(out[off:off + R] for off, R in fused_segments(ws))
    return tuple(o.T for o in outs) if transpose_out else outs


def nmg_qkv(ws, b: jnp.ndarray, *, out_dtype=None,
            transpose_out: bool = False,
            use_pallas: bool | None = None) -> tuple:
    """Fused projection group: every weight of ``ws`` against the same
    decode-shaped B[K, M] in **one** launch.  Returns one [R_i, M] array
    (or [M, R_i] with ``transpose_out``) per projection."""
    if use_pallas is None:
        use_pallas = on_tpu()
    _KERNEL_COUNTS[("nmg_qkv", "pallas" if use_pallas else "xla")] += 1
    if use_pallas:
        cfg, _ = routing.gemv_pallas_config(**_fused_ctx(ws, b.dtype))
        with jax.named_scope("repro.nmg_qkv_pallas"):
            outs = nmg_qkv_pallas(tuple(ws), b, out_dtype=out_dtype,
                                  interpret=not on_tpu(), tm=cfg["tm"],
                                  target_depth=cfg["target_depth"])
        return tuple(o.T for o in outs) if transpose_out else outs
    return nmg_qkv_xla(tuple(ws), b, out_dtype=out_dtype,
                       transpose_out=transpose_out)


@functools.partial(
    jax.jit, static_argnames=("act", "out_dtype", "transpose_out")
)
def nmg_ffn_xla(w: GroupedNMTensor, b: jnp.ndarray, *, act: str = "silu",
                out_dtype=None, transpose_out: bool = False) -> jnp.ndarray:
    """XLA fused gated FFN: literally the sequential ops (projection with
    the decode epilogue, split, act, multiply) under one jit — bitwise
    equal to the unfused model path by construction."""
    hh = nmg_gemv_xla(w, b, out_dtype=out_dtype, transpose_out=True)
    u, v = jnp.split(hh, 2, axis=-1)
    out = act_fn(act)(u) * v                   # [M, F]
    return out if transpose_out else out.T


def nmg_ffn(w: GroupedNMTensor, b: jnp.ndarray, *, act: str = "silu",
            out_dtype=None, transpose_out: bool = False,
            use_pallas: bool | None = None) -> jnp.ndarray:
    """Fused gated-MLP pair: packed [D, 2F] weight against decode-shaped
    B[D, M], gate applied in the kernel epilogue.  Returns [F, M] (or
    [M, F] with ``transpose_out``)."""
    if use_pallas is None:
        use_pallas = on_tpu()
    _KERNEL_COUNTS[("nmg_ffn", "pallas" if use_pallas else "xla")] += 1
    if use_pallas:
        cfg, _ = routing.gemv_pallas_config(**_route_ctx(w, b.dtype))
        with jax.named_scope("repro.nmg_ffn_pallas"):
            out = nmg_ffn_pallas(w, b, act=act, out_dtype=out_dtype,
                                 interpret=not on_tpu(), tm=cfg["tm"],
                                 target_depth=cfg["target_depth"])
        return out.T if transpose_out else out
    return nmg_ffn_xla(w, b, act=act, out_dtype=out_dtype,
                       transpose_out=transpose_out)


def maybe_fused_qkv(x: jnp.ndarray, ws, *, use_pallas: bool | None = None):
    """Linear-level fused-QKV router: y_i = x @ W_i for every projection in
    one launch, or None when the group is ineligible (mixed formats, dense
    weights, prefill-shaped x) or the table vetoes fusion — callers fall
    back to per-projection ``nmg_linear``.  Outputs are in x.dtype and
    bitwise-equal to the sequential path either way."""
    ws = tuple(ws)
    if not fusable_qkv(ws):
        return None
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    M = x2.shape[0]
    ctx = _fused_ctx(ws, x.dtype)
    thr, _ = routing.decode_m_max(**ctx)
    if M > thr:
        return None                            # prefill regime: spmm wins
    fuse, src = routing.fused_qkv(**ctx)
    if not fuse:
        _KERNEL_COUNTS[("nmg_qkv", f"sequential[{src}]")] += 1
        return None
    _KERNEL_COUNTS[("nmg_qkv", f"fused[{src}]")] += 1
    ys = nmg_qkv(ws, x2.T, out_dtype=x.dtype, transpose_out=True,
                 use_pallas=use_pallas)
    return tuple(y.reshape(*lead, -1) for y in ys)


def maybe_fused_ffn(x: jnp.ndarray, w, *, act: str = "silu",
                    use_pallas: bool | None = None):
    """Linear-level fused-FFN router: ``act(u) * v`` for the packed gated
    weight in one launch, or None (ineligible shape/format or table veto)
    so the caller runs the sequential projection + split + gate."""
    if not isinstance(w, GroupedNMTensor):
        return None
    sd = w.sparse_dim % 2
    R = w.dense_shape[1 - sd]
    if R % 2 or not fusable_ffn(w, R // 2):
        return None
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    M = x2.shape[0]
    ctx = _route_ctx(w, x.dtype)
    thr, _ = routing.decode_m_max(**ctx)
    if M > thr:
        return None
    fuse, src = routing.fused_ffn(**ctx)
    if not fuse:
        _KERNEL_COUNTS[("nmg_ffn", f"sequential[{src}]")] += 1
        return None
    _KERNEL_COUNTS[("nmg_ffn", f"fused[{src}]")] += 1
    y = nmg_ffn(w, x2.T, act=act, out_dtype=x.dtype, transpose_out=True,
                use_pallas=use_pallas)
    return y.reshape(*lead, -1)


# ---------------------------------------------------------------------------
# shape routing
# ---------------------------------------------------------------------------


def _route_ctx(a: GroupedNMTensor, dtype) -> dict:
    """The routing-lookup context of a sparse operand: contraction extent,
    output extent, format, row sharing, activation dtype."""
    sd = a.sparse_dim % 2
    return dict(K=a.dense_shape[sd], R=a.dense_shape[1 - sd],
                fmt=(a.n, a.m, a.g), gr=a.gr, dtype=dtype)


def nmg_matmul(a: GroupedNMTensor, b: jnp.ndarray, *,
               use_pallas: bool | None = None) -> jnp.ndarray:
    """Shape-routed sparse @ dense: decode-shaped right operands take the
    GEMV path, everything else the column-tiled SpMM.  f32 output either
    way (the shared kernel contract).  The crossover width comes from the
    routing table (shipped default ``DECODE_M_MAX``); the chosen path and
    its provenance land in ``kernel_counters`` as
    ``("nmg_matmul", "<path>[<table|default>]")``."""
    if b.ndim == 2:
        thr, src = routing.decode_m_max(**_route_ctx(a, b.dtype))
        if b.shape[1] <= thr:
            _KERNEL_COUNTS[("nmg_matmul", f"gemv[{src}]")] += 1
            return nmg_gemv(a, b, use_pallas=use_pallas)
        _KERNEL_COUNTS[("nmg_matmul", f"spmm[{src}]")] += 1
    return nmg_spmm(a, b, use_pallas=use_pallas)


def nmg_linear(x: jnp.ndarray, w: GroupedNMTensor, *,
               use_pallas: bool | None = None) -> jnp.ndarray:
    """y = x @ W for an n:m:g weight W stored with sparse_dim = input axis
    (K) and groups along the output axis (N) — the serving fast path
    (paper §5.3: 'our sparse-dense GEMM kernel during inference').

    x: [..., K]  ->  y: [..., N] in x.dtype.  Decode-shaped x (few rows)
    takes the GEMV kernel, whose epilogue emits x.dtype directly — no f32
    round-trip and (on the XLA path) no output transpose at all; the
    prefill path casts before transposing, so the copy happens at the
    narrow dtype.
    """
    lead = x.shape[:-1]
    K = x.shape[-1]
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    thr, src = routing.decode_m_max(**_route_ctx(w, x.dtype))
    if M <= thr:
        _KERNEL_COUNTS[("nmg_linear", f"gemv[{src}]")] += 1
        y = nmg_gemv(w, x2.T, out_dtype=x.dtype, transpose_out=True,
                     use_pallas=use_pallas)
        return y.reshape(*lead, -1)
    _KERNEL_COUNTS[("nmg_linear", f"spmm[{src}]")] += 1
    yt = nmg_spmm(w, x2.T, use_pallas=use_pallas)  # f32 [N, M]
    return yt.astype(x.dtype).T.reshape(*lead, -1)


# ---------------------------------------------------------------------------
# static route prediction (the checker's differential surface)
# ---------------------------------------------------------------------------


def _predict_linear(w: GroupedNMTensor, M: int, dtype,
                    use_pallas: bool) -> list:
    """Counter keys :func:`nmg_linear` would record for this trace."""
    thr, src = routing.decode_m_max(**_route_ctx(w, dtype))
    if M <= thr:
        return [("nmg_linear", f"gemv[{src}]"),
                ("nmg_gemv", "pallas" if use_pallas else "xla")]
    keys = [("nmg_linear", f"spmm[{src}]"),
            ("nmg_spmm", "pallas" if use_pallas else "xla")]
    if use_pallas:
        cfg, csrc = routing.spmm_pallas_config(**_route_ctx(w, dtype))
        sched = "stream" if cfg["stream"] else "grid"
        keys.append(("nmg_spmm_pallas", f"{sched}[{csrc}]"))
    return keys


def predict_route(op: str, a=None, *, M: int, dtype, ws=None,
                  act: str = "silu", use_pallas: bool | None = None) -> list:
    """Predict, without tracing anything, the ``kernel_counters`` keys one
    trace of ``op`` would record — the same routing lookups the runtime
    branches run, in the same order.  ``repro.check --differential``
    cross-checks these predictions against the counters a real engine
    warmup records; a mismatch means this mirror (or the router) drifted.

    ``op`` is the layout-level op name: ``"nmg_linear"`` / ``"nmg_matmul"``
    (plain projection of an [*, K] activation with ``M`` total rows),
    ``"mm_gated"`` (the model's gated-MLP entry, which may fuse), or
    ``"mm_fused_qkv"`` (projection group ``ws``).  Lookups read the active
    tuning table exactly as the runtime would, so predictions are
    table-sensitive — predict under the same table you serve under."""
    if use_pallas is None:
        use_pallas = on_tpu()

    if op in ("nmg_linear", "nmg_matmul"):
        keys = _predict_linear(a, M, dtype, use_pallas)
        if op == "nmg_matmul":
            thr, src = routing.decode_m_max(**_route_ctx(a, dtype))
            path = "gemv" if M <= thr else "spmm"
            keys = [("nmg_matmul", f"{path}[{src}]")] + [
                k for k in keys if k[0] != "nmg_linear"
            ]
        return keys

    if op == "mm_gated":
        if not isinstance(a, GroupedNMTensor):
            return []                          # dense weight: reference path
        sd = a.sparse_dim % 2
        R = a.dense_shape[1 - sd]
        ctx = _route_ctx(a, dtype)
        thr, _ = routing.decode_m_max(**ctx)
        eligible = R % 2 == 0 and fusable_ffn(a, R // 2)
        if not eligible or M > thr:
            return _predict_linear(a, M, dtype, use_pallas)
        fuse, src = routing.fused_ffn(**ctx)
        if fuse:
            return [("nmg_ffn", f"fused[{src}]"),
                    ("nmg_ffn", "pallas" if use_pallas else "xla")]
        return [("nmg_ffn", f"sequential[{src}]")] + _predict_linear(
            a, M, dtype, use_pallas
        )

    if op == "mm_fused_qkv":
        ws = tuple(ws if ws is not None else a)
        if not fusable_qkv(ws):
            return [k for w in ws
                    for k in _predict_linear(w, M, dtype, use_pallas)]
        ctx = _fused_ctx(ws, dtype)
        thr, _ = routing.decode_m_max(**ctx)
        if M > thr:
            return [k for w in ws
                    for k in _predict_linear(w, M, dtype, use_pallas)]
        fuse, src = routing.fused_qkv(**ctx)
        if fuse:
            return [("nmg_qkv", f"fused[{src}]"),
                    ("nmg_qkv", "pallas" if use_pallas else "xla")]
        return [("nmg_qkv", f"sequential[{src}]")] + [
            k for w in ws for k in _predict_linear(w, M, dtype, use_pallas)
        ]

    raise ValueError(f"predict_route: unknown op {op!r}")


# ---------------------------------------------------------------------------
# other kernels
# ---------------------------------------------------------------------------


def nm_mask(x: jnp.ndarray, n: int, m: int, *, use_pallas: bool | None = None
            ) -> jnp.ndarray:
    """Boolean per-m-block top-n keep mask along the last axis."""
    if use_pallas is None:
        use_pallas = on_tpu()
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    if use_pallas:
        with jax.named_scope("repro.nm_mask_pallas"):
            mask = nm_mask_pallas(x2, n, m, interpret=not on_tpu())
        return mask.astype(jnp.bool_).reshape(shape)
    return kref.nm_mask_ref(x2, n, m).reshape(shape)


def matmul_threshold(a, b, threshold: float, *, use_pallas: bool | None = None):
    """Matmul with fused streaming threshold sparsifier.
    Returns (masked values, bool mask)."""
    if use_pallas is None:
        use_pallas = on_tpu()
    if use_pallas:
        with jax.named_scope("repro.matmul_threshold_pallas"):
            val, mask = matmul_threshold_pallas(
                a, b, threshold=threshold, interpret=not on_tpu()
            )
        return val, mask.astype(jnp.bool_)
    val, mask = kref.matmul_threshold_ref(a, b, threshold)
    return val, mask
