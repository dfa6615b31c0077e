"""CheckedProgram: one traced (and optionally compiled) entry program plus
the trace-time evidence the rules inspect.

``build_program`` traces ``fn`` with ``jax.make_jaxpr`` while snapshotting
the dispatcher's fallback counters, the conversion log, and the kernel
routing counters, so each program carries exactly the dispatch decisions
*its own* trace caused (deltas, not process-wide totals).  VMEM estimates
for the routed Pallas configs are computed here, at build time, because
routing lookups resolve against whatever tuning table is active *now* —
the same trace-time contract the kernels themselves live by.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax

from repro.core.layouts import (
    FixedMaskTensor,
    GroupedNMTensor,
    SparsityLayout,
)

__all__ = ["CheckedProgram", "build_program", "collect_sparse_weights"]


@dataclasses.dataclass
class CheckedProgram:
    """Everything the rules need to know about one entry program."""

    name: str
    model_dtype: Any                    # jnp dtype the program's math is in
    decode_path: bool                   # R3 (dtype) applies to this program
    jaxpr: Any = None                   # ClosedJaxpr | None
    hlo_text: Optional[str] = None      # compiled module text | None
    sparse_weights: dict = dataclasses.field(default_factory=dict)
    fallbacks: dict = dataclasses.field(default_factory=dict)   # dispatch delta
    conversions: list = dataclasses.field(default_factory=list)  # convert delta
    routes: dict = dataclasses.field(default_factory=dict)      # kernel delta
    vmem_estimates: list = dataclasses.field(default_factory=list)
    device_kind: str = ""


def collect_sparse_weights(tree) -> dict:
    """{path: layout} for every sparse-layout leaf of a params pytree."""
    out = {}
    leaves = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, SparsityLayout)
    )[0]
    for path, leaf in leaves:
        if isinstance(leaf, (GroupedNMTensor, FixedMaskTensor)):
            out[jax.tree_util.keystr(path)] = leaf
    return out


def build_program(name: str, fn: Callable, example_args: tuple, *,
                  model_dtype, decode_path: bool = False,
                  sparse_weights: Optional[dict] = None,
                  hlo: bool = False, decode_m: Optional[int] = None,
                  prefill_n: Optional[int] = None,
                  device_kind: Optional[str] = None) -> CheckedProgram:
    """Trace ``fn(*example_args)`` into a :class:`CheckedProgram`.

    ``decode_m`` / ``prefill_n`` are the activation widths the VMEM
    estimator sizes the routed gemv / spmm configs at; omit either to skip
    that estimate.  ``hlo=True`` additionally jit-compiles the program and
    stores the module text for the HLO pass (slower; the CLI default).
    """
    import importlib

    disp = importlib.import_module("repro.core.dispatch")
    conv = importlib.import_module("repro.core.convert")
    kops = importlib.import_module("repro.kernels.ops")
    from repro.tune.table import device_kind as _device_kind

    if sparse_weights is None:
        sparse_weights = collect_sparse_weights(example_args)

    disp_before = disp.dispatch_counters()
    kern_before = kops.kernel_counters()
    conv_before = len(conv.conversion_log())

    jaxpr = jax.make_jaxpr(fn)(*example_args)

    fallbacks = {
        k: v - disp_before.get(k, 0)
        for k, v in disp.dispatch_counters().items()
        if v > disp_before.get(k, 0)
    }
    routes = {
        k: v - kern_before.get(k, 0)
        for k, v in kops.kernel_counters().items()
        if v > kern_before.get(k, 0)
    }
    conversions = conv.conversion_log()[conv_before:]

    kind = device_kind or _device_kind()
    vmem = _vmem_estimates(sparse_weights, model_dtype, kind,
                           decode_m=decode_m, prefill_n=prefill_n)

    hlo_text = None
    if hlo:
        lowered = (fn.lower(*example_args) if hasattr(fn, "lower")
                   else jax.jit(fn).lower(*example_args))
        hlo_text = lowered.compile().as_text()

    return CheckedProgram(
        name=name, model_dtype=model_dtype, decode_path=decode_path,
        jaxpr=jaxpr, hlo_text=hlo_text, sparse_weights=dict(sparse_weights),
        fallbacks=fallbacks, conversions=conversions, routes=routes,
        vmem_estimates=vmem, device_kind=kind,
    )


def _vmem_estimates(sparse_weights: dict, model_dtype, device_kind: str, *,
                    decode_m: Optional[int], prefill_n: Optional[int]
                    ) -> list:
    """Routed-config VMEM working sets per GroupedNM weight — resolved now,
    while the active tuning table (if any) is the one the program traced
    against."""
    from repro.check.static_pass import gemv_vmem, spmm_vmem
    from repro.launch.hlo_analysis import HW_BY_KIND

    if device_kind not in HW_BY_KIND:
        return []       # no budget to judge by: R7 reports the kind
    ests = []
    for path, w in sparse_weights.items():
        if not isinstance(w, GroupedNMTensor):
            continue
        if decode_m is not None:
            ests.append(gemv_vmem(w, model_dtype, decode_m, device_kind,
                                  weight=path))
        if prefill_n is not None:
            ests.append(spmm_vmem(w, model_dtype, prefill_n, device_kind,
                                  weight=path))
    return ests
