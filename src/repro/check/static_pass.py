"""Trace-evidence detectors (dispatch counters, conversion log, routed
VMEM estimates, device-kind budgets): R1's counter half, R2, R6, R7.

The VMEM estimators mirror the block shapes of ``kernels/nmg_spmm.py``
(which the decode GEMV shares) — per grid step, the double-buffered
blocks, accumulator and window temporaries a routed
``(tm | tn, target_depth, stream)`` config makes resident — and compare
them against the scoped VMEM limit in ``launch/hlo_analysis.HW_BY_KIND``.
An oversized tuned tile is caught *here*, before it reaches the compiler.
``tests/test_tpu_compile.py`` checks the shipped defaults against the
compiler itself.
"""

from __future__ import annotations

import collections

import jax.numpy as jnp

from repro.check.diagnostics import Diagnostic, Severity
from repro.launch.hlo_analysis import hw_for_device
from repro.tune import routing

__all__ = ["static_r1", "static_r2", "static_r6", "static_r7",
           "gemv_vmem", "spmm_vmem"]


def static_r1(program) -> list:
    """Dense-fallback traces recorded by the dispatcher while this program
    traced: a sparse layout was materialized for a reference dense op."""
    diags = []
    for (outcome, op, sig), count in sorted(program.fallbacks.items()):
        if outcome != "dense_fallback":
            continue
        diags.append(Diagnostic(
            rule="R1", severity=Severity.ERROR, entry=program.name,
            message=f"dispatcher fell back to the dense implementation of "
                    f"{op!r} for signature {list(sig)} ({count} trace(s)) "
                    f"— the sparse operand was silently densified",
            op=op, location="dispatch-counters",
            fix=f"register a sparse implementation for ({op}, "
                f"{list(sig)}) or convert the operand to a supported "
                f"layout before the call",
        ))
    return diags


def static_r2(program) -> list:
    """Conversion churn: the same (layout -> layout, shape) conversion ran
    more than once while tracing one program — each repeat re-materializes
    and re-compresses the same weight."""
    counts = collections.Counter(
        (src, dst, shape) for src, dst, shape in program.conversions
        if src != "DenseTensor"
    )
    diags = []
    for (src, dst, shape), n in sorted(counts.items()):
        if n <= 1:
            continue
        diags.append(Diagnostic(
            rule="R2", severity=Severity.WARNING, entry=program.name,
            message=f"{src} -> {dst} conversion of shape {list(shape)} ran "
                    f"{n}x in one traced program — convert once and reuse "
                    f"the converted layout",
            op=f"{src}->{dst}", location="conversion-log",
            fix="hoist the conversion out of the traced function (convert "
                "at load/sparsify time, not per call)",
        ))
    return diags


# ---------------------------------------------------------------------------
# R6: routed-config VMEM working sets (mirrors the Pallas block shapes)
# ---------------------------------------------------------------------------


def _fmt_ctx(w, dtype) -> dict:
    sd = w.sparse_dim % 2
    return dict(K=int(w.dense_shape[sd]), R=int(w.dense_shape[1 - sd]),
                fmt=(w.n, w.m, w.g), gr=w.gr, dtype=jnp.dtype(dtype))


def _step_vmem(w, dtype, M: int, tm: int, target_depth: int,
               stream: bool) -> int:
    """VMEM bytes of one grid step of ``kernels/nmg_spmm.nmg_pallas_call``:
    double-buffered plan, value, activation and output blocks, the f32
    accumulator and the wide tile scratch, and the one-hot, group-tile and
    dot temporaries of one window."""
    from repro.kernels.nmg_spmm import (_groups_per_step, _sublanes,
                                        tile_width, windows)

    n, m, g, gr = w.n, w.m, w.g, w.gr
    R_pad, nblocks, _ = w.val.shape[-3:]   # stacked layers lead
    nbn, k_pad, G = nblocks * n, nblocks * m, R_pad // gr
    vb = jnp.dtype(dtype).itemsize
    sub = _sublanes(dtype)
    TM = min(-(-M // sub) * sub, max(sub, tm // sub * sub))
    W, tg = tile_width(gr), _groups_per_step(G, gr)
    wins = windows(n, m, g, nbn, target_depth)
    tc = max(c1 - c0 for c0, c1, _, _ in wins)
    tk = max(k1 - k0 for _, _, k0, k1 in wins)
    step_tc, step_tk = (tc, tk) if not stream else (nbn, k_pad)
    lanes = lambda x: -(-x // 128) * 128          # noqa: E731
    blocks = (tg * 8 * lanes(step_tc) * 4                     # plan
              + tg * gr * lanes(step_tc) * vb                 # values
              + TM * lanes(step_tk) * vb                      # activations
              + TM * tg * gr * 4)                             # output
    temps = (tk * lanes(tc) * (4 + vb)                        # one-hot
             + gr * lanes(tk) * (4 + vb)                      # group tile
             + W * lanes(tk) * vb                             # wide tile
             + TM * W * 4                                     # one dot
             + TM * tg * gr * 4)                              # accumulator
    return 2 * blocks + temps


def gemv_vmem(w, dtype, M: int, device_kind: str, *, weight: str = "") -> dict:
    """VMEM bytes of one grid step of the routed decode GEMV config."""
    cfg, src = routing.gemv_pallas_config(**_fmt_ctx(w, dtype))
    nbytes = _step_vmem(w, dtype, M, int(cfg["tm"]),
                        int(cfg["target_depth"]), stream=True)
    return {"kernel": "nmg_gemv", "weight": weight, "config": dict(cfg),
            "source": src, "M": int(M), "bytes": int(nbytes),
            "budget": int(hw_for_device(device_kind)["vmem_bytes"]),
            "device": device_kind}


def spmm_vmem(w, dtype, N: int, device_kind: str, *, weight: str = "") -> dict:
    """VMEM bytes of one grid step of the routed prefill SpMM config."""
    cfg, src = routing.spmm_pallas_config(**_fmt_ctx(w, dtype))
    nbytes = _step_vmem(w, dtype, N, int(cfg["tn"]),
                        int(cfg["target_depth"]),
                        stream=bool(cfg.get("stream", True)))
    return {"kernel": "nmg_spmm", "weight": weight, "config": dict(cfg),
            "source": src, "N": int(N), "bytes": int(nbytes),
            "budget": int(hw_for_device(device_kind)["vmem_bytes"]),
            "device": device_kind}


def static_r6(program) -> list:
    """Routed Pallas working set exceeds the per-device VMEM budget."""
    diags = []
    for est in program.vmem_estimates:
        if est["bytes"] <= est["budget"]:
            continue
        diags.append(Diagnostic(
            rule="R6", severity=Severity.ERROR, entry=program.name,
            message=f"routed {est['kernel']} config {est['config']} "
                    f"(source: {est['source']}) for weight "
                    f"{est['weight'] or '?'} needs "
                    f"~{est['bytes'] / 2**20:.1f} MiB VMEM per grid step — "
                    f"budget is {est['budget'] / 2**20:.0f} MiB on "
                    f"{est['device']}",
            op=est["kernel"], location="vmem-estimate",
            fix="shrink the tuned tile (tm/tn/target_depth) for this shape "
                "bucket, or regenerate the tuning table on this device",
        ))
    return diags


def static_r7(program) -> list:
    """Device kind with no HW entry: there are no peaks or VMEM budget to
    judge it by, and none is assumed (``hw_for_device`` raises)."""
    try:
        hw_for_device(program.device_kind)
    except KeyError as e:
        return [Diagnostic(
            rule="R7", severity=Severity.ERROR, entry=program.name,
            message=str(e.args[0]), op=program.device_kind,
            location="hw-model",
            fix="add this device kind's published peaks and its scoped "
                "VMEM limit to launch/hlo_analysis.HW_BY_KIND",
        )]
    return []
