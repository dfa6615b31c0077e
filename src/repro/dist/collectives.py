"""Sparse-gradient collectives (paper §6.1 + beyond-paper fast path).

The paper's distributed masked-sparse training exchanges gradients the
portable way: densify, all-reduce the dense buffer, re-sparsify
(:func:`densify_allreduce_resparsify`).  Because a
:class:`~repro.core.layouts.FixedMaskTensor`'s pattern is *fixed* across
steps and identical on every data-parallel replica, the exchange only needs
the value buffer — :func:`fixed_mask_value_allreduce` skips the densify and
the mask re-apply entirely (and, for genuinely compressed layouts, would
move nnz-sized payloads; see dist/compression.py for the top-k variant).

All reductions are *mean* reductions (the data-parallel gradient
convention), implemented with a real ``pmean`` under ``shard_map`` so the
collective appears in lowered HLO.  Under the single-controller test
harness the inputs are replicated over the mesh axis; on a multi-host fleet
the same functions apply per-replica contributions.
"""

from __future__ import annotations

import functools

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.layouts import FixedMaskTensor

__all__ = [
    "allreduce_mean",
    "densify_allreduce_resparsify",
    "fixed_mask_value_allreduce",
]


def allreduce_mean(x, mesh: Mesh, axis: str):
    """Mean-all-reduce a dense array over one mesh axis.

    The input is treated as each replica's full (unsharded) contribution;
    the body runs per-device and ``pmean``s over ``axis``.
    """
    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(P(),), out_specs=P(),
        check_vma=False,
    )
    def _mean(v):
        return jax.lax.pmean(v, axis)

    return _mean(x)


def densify_allreduce_resparsify(g: FixedMaskTensor, mesh: Mesh,
                                 axis: str) -> FixedMaskTensor:
    """The paper-faithful exchange: ``to_dense`` -> all-reduce -> re-mask.

    Moves a full dense buffer per layer and re-applies the mask afterwards
    (the re-sparsify step of SameFormatSparsifier specialized to a fixed
    pattern).  Correct for any mask configuration, including replicas whose
    masks disagree mid-recompute.
    """
    dense = allreduce_mean(g.to_dense(), mesh, axis)
    mask = g.mask
    return FixedMaskTensor(dense * mask.astype(dense.dtype), mask, g.origin)


def fixed_mask_value_allreduce(g: FixedMaskTensor, mesh: Mesh,
                               axis: str) -> FixedMaskTensor:
    """Beyond-paper fast path: all-reduce *values only* under a shared mask.

    Valid whenever every replica holds the same mask — true between pattern
    recomputes in masked sparse training (the common case; recomputes are
    collective-scheduled).  Skips the densify and the post-reduce masking:
    masked-out value slots may accumulate garbage, but ``to_dense`` masks
    them out by construction, so the result equals
    :func:`densify_allreduce_resparsify` exactly when masks agree.
    """
    return FixedMaskTensor(
        allreduce_mean(g.val, mesh, axis), g.mask, g.origin
    )
