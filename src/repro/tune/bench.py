"""Microbenchmark harness: the measurements behind the tuning table.

One timing loop (:func:`time_us`) and one right-operand-width sweep
(:func:`sweep_m`) serve every consumer: the ``python -m repro.tune`` CLI,
the serving warmup hook (:func:`autotune_for_serving`) and
``benchmarks/fig6_spmm.py`` (which used to own this machinery; it now
imports it from here so the fig-6 plot and the tuner can never disagree
about what was measured).

Every tuner mutates a :class:`~repro.tune.table.TuningTable` in place and
returns what it measured; persistence and activation are the caller's
business (the CLI saves, the warmup hook activates).
"""

from __future__ import annotations

import time
from typing import Iterable, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.tune import routing
from repro.tune.table import TuningTable, bucket, shape_key

__all__ = [
    "time_us",
    "sweep_m",
    "measured_crossover",
    "tune_decode_threshold",
    "tune_spmm_block",
    "tune_gemv_pallas",
    "tune_spmm_pallas",
    "tune_fused_qkv",
    "tune_conversion_costs",
    "autotune_for_serving",
]


def time_us(fn, *args, reps: int = 5, inner: int = 5) -> float:
    """Median-of-``reps`` wall time of ``inner`` back-to-back calls (us).
    The first (untimed) call absorbs compilation."""
    jax.block_until_ready(fn(*args))
    best = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(inner):
            out = fn(*args)
        jax.block_until_ready(out)
        best.append((time.perf_counter() - t0) / inner)
    best.sort()
    return best[len(best) // 2] * 1e6


def sweep_m(t, key, ms: Sequence[int], *, reps: int = 5,
            include_dense: bool = True, dtype=jnp.float32) -> list[dict]:
    """Time the gemv / spmm (/ dense) paths for right operands [K, M] over
    the width sweep ``ms``.  ``t`` is the GroupedNMTensor under test; the
    right operand is random in ``dtype``.  Returns one record
    ``{"path", "M", "us"}`` per (path, M).

    What is timed is what the router actually chooses between on the
    serving entry point (``nmg_linear``): the backend-routed ``nmg_gemv``
    path *with* its dtype-preserving transposed-output epilogue vs the
    backend-routed ``nmg_spmm`` path plus the cast-and-transpose it
    forces — both emitting [M, R] in ``dtype``.  Going through the public
    routed entry points (not the ``_xla`` variants) matters on TPU, where
    the router dispatches the Pallas kernels: the measurements must come
    from the implementations that will actually run.  (On CPU the bare
    f32 kernels would lower to near-identical XLA programs at small M, so
    the epilogue difference is the real routing consequence there.)"""
    from repro.kernels import ops as kops

    dt = jnp.dtype(dtype)
    K = kops._route_ctx(t, dt)["K"]  # the router's own K/R derivation
    sd = t.sparse_dim % 2
    paths = [
        ("gemv",
         jax.jit(lambda a, b: kops.nmg_gemv(a, b, out_dtype=dt,
                                            transpose_out=True)),
         lambda b: (t, b)),
        ("spmm",
         jax.jit(lambda a, b: kops.nmg_spmm(a, b).astype(dt).T),
         lambda b: (t, b)),
    ]
    if include_dense:
        wd = t.to_dense()
        if sd == 0:  # canonical view is the transpose
            wd = wd.T
        dense = jax.jit(lambda b, w: b.T @ w.T)  # same [M, R] orientation
        paths.append(("dense", dense, lambda b: (b, wd)))

    records = []
    for m in ms:
        b = jax.random.normal(jax.random.fold_in(key, m), (K, m), jnp.float32
                              ).astype(dt)
        for name, fn, mkargs in paths:
            records.append({
                "path": name, "M": int(m),
                "us": time_us(fn, *mkargs(b), reps=reps),
            })
    return records


def measured_crossover(records: Iterable[dict], *, tol: float = 0.05) -> int:
    """The measured gemv/spmm crossover: the widest M (scanning the sweep
    upward) at which the gemv path is still no slower than the spmm path —
    i.e. the empirical ``decode_m_max`` for the swept shape.  0 means the
    gemv path never won (route everything to spmm).

    ``tol`` keeps timing noise from flipping the route where the two paths
    are effectively tied (at tiny M they often lower to near-identical
    programs): gemv holds the route until spmm beats it by more than the
    tolerance fraction.  A *single* losing M does not end the scan — one
    noisy sample at the narrow end must not zero the threshold while gemv
    genuinely wins at the real decode widths — but two losses in a row
    (or a loss closing the sweep) are treated as the crossover."""
    gemv = {r["M"]: r["us"] for r in records if r["path"] == "gemv"}
    spmm = {r["M"]: r["us"] for r in records if r["path"] == "spmm"}
    crossover = 0
    losses = 0
    for m in sorted(gemv.keys() & spmm.keys()):
        if gemv[m] <= spmm[m] * (1.0 + tol):
            crossover = m
            losses = 0
        else:
            losses += 1
            if losses >= 2:
                break
    return crossover


# ---------------------------------------------------------------------------
# tuners: measure -> table entry
# ---------------------------------------------------------------------------


def _probe_tensor(key, K: int, R: int, fmt: tuple, gr: int,
                  dtype=jnp.float32):
    """Random probe weight in the dtype under test: stored-value dtype
    changes the gathered-weight traffic and einsum compute dtype, so a
    bf16 bucket must be measured on bf16-stored values."""
    from repro.core import nmg

    n, m, g = fmt
    w = jax.random.normal(key, (R, K), jnp.float32).astype(dtype)
    return nmg.dense_to_grouped_nm(w, n=n, m=m, g=g, gr=gr, sparse_dim=1)


def tune_decode_threshold(table: TuningTable, *, K: int, R: int, fmt: tuple,
                          gr: int, dtype=jnp.float32,
                          ms: Sequence[int] = (1, 2, 4, 8, 16, 32, 64, 128),
                          reps: int = 5, t=None,
                          key: Optional[jax.Array] = None) -> int:
    """Measure the gemv/spmm crossover for one (shape bucket, format) and
    record it as that bucket's ``decode_m_max``.  ``t`` optionally
    supplies an existing (unbatched) tensor to sweep in place of the
    random probe the shape parameters otherwise build.

    The same sweep also yields absolute numbers, so each swept width's
    best-path latency is recorded as the bucket's
    ``matmul_latency/.../M{bucket}`` entry (best over the M values that
    share a bucket) — the admission-time cost predictions the serving SLO
    controller reads back through
    :func:`repro.tune.routing.matmul_latency_us`."""
    key = jax.random.PRNGKey(0) if key is None else key
    if t is None:
        t = _probe_tensor(key, K, R, fmt, gr, dtype=dtype)
    records = sweep_m(t, key, ms, reps=reps, include_dense=False,
                      dtype=dtype)
    crossover = measured_crossover(records)
    table.put(shape_key("decode_m_max", K=K, R=R, fmt=fmt, gr=gr,
                        dtype=dtype), crossover)
    best_by_m: dict = {}
    for r in records:
        m = int(r["M"])
        best_by_m[m] = min(best_by_m.get(m, float("inf")), r["us"])
    lat_key = shape_key("matmul_latency", K=K, R=R, fmt=fmt, gr=gr,
                        dtype=dtype)
    best_by_bucket: dict = {}
    for m, us in best_by_m.items():
        b = bucket(m)
        best_by_bucket[b] = min(best_by_bucket.get(b, float("inf")), us)
    for b, us in best_by_bucket.items():
        table.put(f"{lat_key}/M{b}", us)
    return crossover


def tune_spmm_block(table: TuningTable, *, K: int = 4096, R: int = 4096,
                    N: int = 256, fmt: tuple = (1, 4, 8), gr: int = 64,
                    candidates: Sequence[int] = (1 << 18, 1 << 20, 1 << 22,
                                                 1 << 24),
                    reps: int = 5) -> int:
    """Sweep the XLA spmm gathered-block cap and record the fastest as the
    device-wide ``spmm_block_elems``.

    The probe must be large enough that the candidates *compile
    differently*: a cap only binds when ``per_group = (K/m) * n * N``
    gathered elements times ``Gr = R/gr`` fiber groups exceeds it.  The
    defaults give per_group = 2^18 and Gr = 64, so the candidate ladder
    maps to group-block sizes 1/4/16/64 — four genuinely distinct
    programs.  (A too-small probe would make every candidate lower to the
    same single-block program and the winner would be timing noise.)"""
    from repro.kernels import ops as kops

    key = jax.random.PRNGKey(1)
    t = _probe_tensor(key, K, R, fmt, gr)
    b = jax.random.normal(jax.random.fold_in(key, 1), (K, N), jnp.float32)
    best, best_us = None, float("inf")
    for cand in candidates:
        fn = jax.jit(lambda a, bb, c=int(cand):
                     kops.nmg_spmm_xla(a, bb, block_elems=c))
        us = time_us(fn, t, b, reps=reps)
        if us < best_us:
            best, best_us = int(cand), us
    table.put("spmm_block_elems", best)
    return best


def tune_gemv_pallas(table: TuningTable, *, K: int = 1024, R: int = 1024,
                     M: int = 8, fmt: tuple = (1, 4, 8), gr: int = 64,
                     dtype=jnp.float32,
                     tms: Sequence[int] = (128,),
                     depths: Sequence[int] = (64, 128, 256),
                     reps: int = 3, interpret: Optional[bool] = None) -> dict:
    """Sweep the Pallas gemv activation-row tile / window depth and
    record the fastest config for the shape bucket.  On CPU this runs
    the kernel in interpret mode — meaningful only as a smoke test, so the
    CLI gates it behind ``--pallas`` off-TPU."""
    from repro.kernels import ops as kops
    from repro.kernels.nmg_gemv import nmg_gemv_pallas

    if interpret is None:
        interpret = not kops.on_tpu()
    key = jax.random.PRNGKey(2)
    t = _probe_tensor(key, K, R, fmt, gr)
    b = jax.random.normal(jax.random.fold_in(key, 1), (K, M), jnp.float32
                          ).astype(dtype)
    best, best_us = None, float("inf")
    for tm in tms:
        for depth in depths:
            fn = jax.jit(lambda a, bb, tm=tm, d=depth: nmg_gemv_pallas(
                a, bb, tm=tm, target_depth=d, interpret=interpret))
            us = time_us(fn, t, b, reps=reps, inner=1 if interpret else 5)
            if us < best_us:
                best = {"tm": int(tm), "target_depth": int(depth)}
                best_us = us
    table.put(shape_key("gemv_pallas", K=K, R=R, fmt=fmt, gr=gr,
                        dtype=dtype), best)
    return best


def tune_spmm_pallas(table: TuningTable, *, K: int = 1024, R: int = 1024,
                     N: int = 256, fmt: tuple = (1, 4, 8), gr: int = 64,
                     dtype=jnp.float32,
                     tns: Sequence[int] = (128,),
                     depths: Sequence[int] = (128,),
                     reps: int = 3, interpret: Optional[bool] = None) -> dict:
    """Sweep the Pallas spmm schedule (streamed: activation slab resident;
    grid: windows on the grid) and tile config, recording the fastest as the shape bucket's
    ``spmm_pallas`` entry.  Interpret-mode timings off-TPU are smoke only
    (the CLI gates this behind ``--pallas`` there)."""
    from repro.kernels import ops as kops
    from repro.kernels.nmg_spmm import nmg_spmm_pallas

    if interpret is None:
        interpret = not kops.on_tpu()
    key = jax.random.PRNGKey(5)
    t = _probe_tensor(key, K, R, fmt, gr)
    b = jax.random.normal(jax.random.fold_in(key, 1), (K, N), jnp.float32
                          ).astype(dtype)
    best, best_us = None, float("inf")
    for stream in (True, False):
        for tn in tns:
            for depth in depths:
                fn = jax.jit(lambda a, bb, tn=tn, d=depth, s=stream:
                             nmg_spmm_pallas(a, bb, tn=tn, target_depth=d,
                                             stream=s, interpret=interpret))
                us = time_us(fn, t, b, reps=reps,
                             inner=1 if interpret else 5)
                if us < best_us:
                    best = {"tn": int(tn), "target_depth": int(depth),
                            "stream": bool(stream)}
                    best_us = us
    table.put(shape_key("spmm_pallas", K=K, R=R, fmt=fmt, gr=gr,
                        dtype=dtype), best)
    return best


def tune_fused_qkv(table: TuningTable, *, K: int = 256,
                   Rs: Sequence[int] = (256, 256, 256),
                   fmt: tuple = (1, 4, 8), gr: int = 64, M: int = 4,
                   dtype=jnp.float32, reps: int = 3,
                   use_pallas: Optional[bool] = None) -> bool:
    """Measure the fused-QKV megakernel against the per-projection gemv
    path at a decode width and record the winner as the bucket's
    ``fused_qkv`` bool (the summed output rows key the bucket, matching
    the router's fused-group context).  Fusion should win wherever the
    per-launch gather overhead dominates; a bucket where it does not gets
    an explicit veto instead of a silent slowdown."""
    from repro.kernels import ops as kops

    if use_pallas is None:
        use_pallas = kops.on_tpu()
    key = jax.random.PRNGKey(6)
    ws = tuple(_probe_tensor(jax.random.fold_in(key, i), K, R, fmt, gr,
                             dtype=dtype)
               for i, R in enumerate(Rs))
    b = jax.random.normal(jax.random.fold_in(key, 9), (K, M), jnp.float32
                          ).astype(dtype)
    # weights are closed over, as in the engine's jitted decode step —
    # only the activation is a per-call argument on either path
    fused_fn = jax.jit(lambda bb: kops.nmg_qkv(ws, bb, out_dtype=dtype,
                                               use_pallas=use_pallas))
    # per-launch sequential baseline (one dispatch per projection) — the
    # structure the megakernel collapses, same framing as fig6's series
    launches = tuple(
        jax.jit(lambda bb, w=w: kops.nmg_gemv(w, bb, out_dtype=dtype,
                                              use_pallas=use_pallas))
        for w in ws)

    def seq_fn(bb):
        return tuple(f(bb) for f in launches)
    inner = 1 if (use_pallas and not kops.on_tpu()) else 20
    # interleaved best-of rounds: the decision hinges on tens-of-us launch
    # overhead, and a contended runner inflates the two paths asymmetrically
    fused_us = min(time_us(fused_fn, b, reps=reps, inner=inner)
                   for _ in range(3))
    seq_us = min(time_us(seq_fn, b, reps=reps, inner=inner)
                 for _ in range(3))
    win = bool(fused_us <= seq_us)
    table.put(shape_key("fused_qkv", K=K, R=sum(int(r) for r in Rs), fmt=fmt,
                        gr=gr, dtype=dtype), win)
    return win


def tune_conversion_costs(table: TuningTable, *, side: int = 256,
                          reps: int = 3) -> dict:
    """Measure lossless layout-conversion costs among the interchange
    layouts (Dense/Csr/Coo/FixedMask) and record them; the dispatcher's
    conversion tie-breaker consults these via
    :func:`repro.tune.routing.conversion_cost`."""
    import importlib

    conv = importlib.import_module("repro.core.convert")
    from repro.core.layouts import (CooTensor, CsrTensor, DenseTensor,
                                    FixedMaskTensor)

    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (side, side), jnp.float32)
    x = x * (jax.random.uniform(jax.random.fold_in(key, 1),
                                (side, side)) < 0.25)
    insts = {DenseTensor: conv.as_layout(x)}
    for cls in (CsrTensor, CooTensor, FixedMaskTensor):
        insts[cls] = conv.convert(insts[DenseTensor], cls)
    measured = {}
    for src_cls, inst in insts.items():
        for dst_cls in conv.lossless_targets(src_cls):
            if dst_cls is src_cls or dst_cls not in insts:
                continue
            us = time_us(lambda i=inst, d=dst_cls: conv.convert(i, d),
                         reps=reps, inner=3)
            k = f"convert_cost/{src_cls.__name__}->{dst_cls.__name__}"
            table.put(k, us)
            measured[k] = us
    return measured


# ---------------------------------------------------------------------------
# serving warmup hook: tune the engine's actual shapes
# ---------------------------------------------------------------------------


def autotune_for_serving(params, *, max_slots: int, prompt_lens: Sequence[int],
                         dtype=None, reps: int = 3,
                         table: Optional[TuningTable] = None,
                         activate: bool = True) -> TuningTable:
    """Tune the decode/prefill routing for the *actual* sparse-weight
    shapes an engine will serve.

    Walks ``params`` for distinct :class:`GroupedNMTensor` shape/format
    signatures and measures each one's gemv/spmm crossover at the widths
    the engine produces — ``max_slots`` single-token rows per decode step,
    one ``prompt_len``-row block per admission — plus powers of two
    bracketing them.  Each signature is measured on a same-shaped random
    probe rather than the weight itself: gather cost is independent of the
    stored values, and model weights may be layer-stacked (a leading scan
    axis on ``val``) while the routed matmuls always see one layer's
    logical ``dense_shape``, which is exactly what the probe rebuilds.
    Entries land in ``table`` (default: the active table, or a fresh one),
    which is activated so the engine's subsequent first-trace compiles
    against the tuned thresholds.
    """
    from repro.core.layouts import GroupedNMTensor
    from repro.kernels import ops as kops

    if table is None:
        table = routing.active_table() or TuningTable.for_device()
    ms = sorted({1, 2, 4, 8, 16, 32, int(max_slots),
                 *(int(p) for p in prompt_lens)})
    seen = set()
    key = jax.random.PRNGKey(4)
    for leaf in jax.tree_util.tree_leaves(
            params, is_leaf=lambda x: isinstance(x, GroupedNMTensor)):
        if not isinstance(leaf, GroupedNMTensor):
            continue
        dt = jnp.dtype(dtype) if dtype is not None else leaf.val.dtype
        # the router's own context derivation: table entries must land in
        # exactly the buckets nmg_matmul/nmg_linear will look up
        ctx = kops._route_ctx(leaf, dt)
        sig = shape_key("decode_m_max", **ctx)
        if sig in seen:
            continue
        seen.add(sig)
        tune_decode_threshold(table, ms=ms, reps=reps, key=key, **ctx)
    if activate:
        routing.set_active_table(table)
    return table
