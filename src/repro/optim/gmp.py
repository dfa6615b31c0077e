"""Magnitude-pruning schedules (paper §2, §6.2): one-shot, iterative
(gradual magnitude pruning, Zhu & Gupta), and layer-wise.

These drive the Table-2 productivity study: each sparsifier differs only in
its schedule, a handful of lines on top of the shared machinery.

Every query exists in two spellings: the host-side one over Python ints
(``sparsity_at`` / ``recompute_at``) and a traced one over jnp step counters
(``sparsity_at_traced`` / ``recompute_at_traced``) so the decisions can live
inside a jitted multi-step trainer (launch/train.py) as ``lax.cond``
predicates instead of host syncs.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

__all__ = ["GMPSchedule", "gmp_sparsity"]


@dataclasses.dataclass(frozen=True)
class GMPSchedule:
    mode: str = "iterative"     # one_shot | iterative | layer_wise
    target_sparsity: float = 0.5
    begin_step: int = 0
    end_step: int = 1000
    recompute_every: int = 100  # pattern-recompute cadence during ramp
    num_layers: int = 12        # layer_wise: layers pruned one at a time

    def sparsity_at(self, step: int) -> float:
        return gmp_sparsity(self, step)

    def recompute_at(self, step: int) -> bool:
        if self.mode == "one_shot":
            return step == self.begin_step
        if step < self.begin_step or step > self.end_step:
            return False
        # the ramp ends exactly at end_step: always fire a final recompute
        # there so the pattern reaches target_sparsity even when the span is
        # not a multiple of the cadence
        if step == self.end_step:
            return True
        return (step - self.begin_step) % max(1, self.recompute_every) == 0

    # -- traced spellings (jnp step counters, usable inside jit) ----------

    def sparsity_at_traced(self, step) -> jnp.ndarray:
        """``sparsity_at`` over a traced step counter (f32 scalar out).

        The cubic ramp is evaluated with the same f32 operation sequence as
        the host spelling (``gmp_sparsity``), so the two produce bitwise-
        equal levels — and therefore identical top-k counts in
        ``unstructured_mask`` — at every step.
        """
        step = jnp.asarray(step, jnp.float32)
        tgt = jnp.float32(self.target_sparsity)
        if self.mode == "one_shot":
            return jnp.where(step >= self.begin_step, tgt, 0.0)
        span = jnp.float32(max(1, self.end_step - self.begin_step))
        frac = jnp.clip((step - jnp.float32(self.begin_step)) / span,
                        0.0, 1.0)
        om = jnp.float32(1.0) - frac
        return tgt * (jnp.float32(1.0) - om * om * om)

    def recompute_at_traced(self, step) -> jnp.ndarray:
        """``recompute_at`` over a traced step counter (bool scalar out)."""
        step = jnp.asarray(step, jnp.int32)
        if self.mode == "one_shot":
            return step == self.begin_step
        in_ramp = (step >= self.begin_step) & (step <= self.end_step)
        on_cadence = (
            (step - self.begin_step) % max(1, self.recompute_every) == 0
        )
        return in_ramp & (on_cadence | (step == self.end_step))

    def layers_pruned_at(self, step: int) -> int:
        """layer_wise: how many leading layers are sparse at ``step``."""
        if self.mode != "layer_wise":
            return self.num_layers
        if step >= self.end_step:
            # the ramp is over: every layer is pruned, even when the span is
            # shorter than num_layers (integer-span schedules would
            # otherwise strand trailing layers dense forever)
            return self.num_layers
        span = max(1, (self.end_step - self.begin_step) // self.num_layers)
        return min(self.num_layers, max(0, (step - self.begin_step) // span + 1))


def gmp_sparsity(s: GMPSchedule, step: int) -> float:
    """Cubic ramp (Zhu & Gupta 2017) for iterative; step function for
    one-shot; per-layer target for layer-wise.

    The ramp is evaluated in float32 with the exact operation sequence of
    ``sparsity_at_traced`` so the host-driven reference loop and the in-jit
    fast path quantize to the same level (and hence recompute bitwise-equal
    masks) at every step — a float64 host ramp would round top-k counts
    differently on large tensors.  The ramp ends at the float32-rounded
    target, as the traced spelling does: the float64 target can lie below
    the float32 levels just before it, and the ramp must not step down.
    """
    import numpy as _np

    if s.mode == "one_shot":
        return s.target_sparsity if step >= s.begin_step else 0.0
    if step <= s.begin_step:
        return 0.0
    tgt = _np.float32(s.target_sparsity)
    if step >= s.end_step:
        return float(tgt)
    span = _np.float32(max(1, s.end_step - s.begin_step))
    frac = (_np.float32(step) - _np.float32(s.begin_step)) / span
    om = _np.float32(1.0) - frac
    return float(tgt * (_np.float32(1.0) - om * om * om))
