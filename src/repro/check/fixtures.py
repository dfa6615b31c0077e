"""Seeded regression fixtures: for every rule, one program that triggers
it and one that is clean.

These are the checker's own test vectors — ``tests/test_check_meta.py``
asserts the registry and this table stay in lockstep, and
``tests/test_check.py`` asserts each trigger actually fails (nonzero exit
under ``--strict``) while each clean program passes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.check.program import build_program
from repro.core.layouts import CsrTensor, FixedMaskTensor, GroupedNMTensor
from repro.tune.routing import clear_active_table, set_active_table
from repro.tune.table import TuningTable, device_kind

__all__ = ["FIXTURES", "fixture_programs"]

_N, _M, _G, _GR = 1, 4, 8, 4


def _weight(K: int = 64, R: int = 64) -> GroupedNMTensor:
    x = jax.random.normal(jax.random.PRNGKey(0), (K, R), jnp.float32)
    return GroupedNMTensor.from_dense(x, _N, _M, _G, gr=_GR, sparse_dim=0)


def _x(rows: int = 4, K: int = 64):
    return jnp.ones((rows, K), jnp.float32)


# -- R1: silent densify ------------------------------------------------------


def _r1_trigger():
    w = _weight()

    def f(x):
        return x @ w.to_dense()      # densified projection: the bug

    return build_program("fixture/r1:trigger", f, (_x(),),
                         model_dtype=jnp.float32, decode_path=True,
                         sparse_weights={"w": w}, hlo=True, decode_m=4)


def _r1_clean():
    from repro.models.common import mm
    w = _weight()

    def f(x):
        return mm(x, w)              # dispatched sparse fast path

    return build_program("fixture/r1:clean", f, (_x(),),
                         model_dtype=jnp.float32, decode_path=True,
                         sparse_weights={"w": w}, hlo=True, decode_m=4)


# -- R2: conversion churn ----------------------------------------------------


def _csr():
    d = jnp.where(jnp.arange(64).reshape(8, 8) % 3 == 0, 1.0, 0.0)
    return CsrTensor.from_dense(d)


def _r2_trigger():
    import importlib
    conv = importlib.import_module("repro.core.convert")
    c = _csr()

    def f(x):
        a = conv.convert(c, FixedMaskTensor)
        b = conv.convert(c, FixedMaskTensor)   # the same conversion, again
        return x + a.to_dense() + b.to_dense()

    return build_program("fixture/r2:trigger", f, (jnp.ones((8, 8)),),
                         model_dtype=jnp.float32)


def _r2_clean():
    import importlib
    conv = importlib.import_module("repro.core.convert")
    c = _csr()

    def f(x):
        a = conv.convert(c, FixedMaskTensor)   # converted once, reused
        ad = a.to_dense()
        return x + ad + ad

    return build_program("fixture/r2:clean", f, (jnp.ones((8, 8)),),
                         model_dtype=jnp.float32)


# -- R3: dtype promotion on the decode path ---------------------------------


def _r3_trigger():
    def f(x):
        return x.astype(jnp.float32) * 2.0     # elementwise math widened

    return build_program("fixture/r3:trigger", f,
                         (jnp.ones((4, 8), jnp.bfloat16),),
                         model_dtype=jnp.bfloat16, decode_path=True)


def _r3_clean():
    y = jnp.ones((8, 4), jnp.float32)

    def f(x):
        # widening that feeds only the matmul accumulation is the
        # kernels' own f32-accumulator contract — allowed
        return (x.astype(jnp.float32) @ y).astype(jnp.bfloat16)

    return build_program("fixture/r3:clean", f,
                         (jnp.ones((4, 8), jnp.bfloat16),),
                         model_dtype=jnp.bfloat16, decode_path=True)


# -- R4: host sync inside the decode loop -----------------------------------


def _r4_trigger():
    def f(x):
        def body(c, _):
            y = jax.pure_callback(
                lambda v: v, jax.ShapeDtypeStruct(c.shape, c.dtype), c
            )
            return y, ()

        out, _ = jax.lax.scan(body, x, None, length=2)
        return out

    return build_program("fixture/r4:trigger", f, (jnp.ones((4,)),),
                         model_dtype=jnp.float32, decode_path=True,
                         hlo=True)


def _r4_clean():
    def f(x):
        def body(c, _):
            return c * 2.0, ()

        out, _ = jax.lax.scan(body, x, None, length=2)
        return out

    return build_program("fixture/r4:clean", f, (jnp.ones((4,)),),
                         model_dtype=jnp.float32, decode_path=True,
                         hlo=True)


# -- R5: weak-typed signature (recompile hazard) ----------------------------


def _r5_trigger():
    def f(x):
        return x + 1

    # a Python float argument traces weak-typed
    return build_program("fixture/r5:trigger", f, (1.0,),
                         model_dtype=jnp.float32)


def _r5_clean():
    def f(x):
        return x + 1

    return build_program("fixture/r5:clean", f, (np.float32(1.0),),
                         model_dtype=jnp.float32)


# -- R6: VMEM overrun from a bad tuned tile ---------------------------------


def _r6_program(name, decode_m=4):
    from repro.models.common import mm
    w = _weight()

    def f(x):
        return mm(x, w)

    return build_program(name, f, (_x(),), model_dtype=jnp.float32,
                         decode_path=True, sparse_weights={"w": w},
                         decode_m=decode_m)


def _r6_trigger():
    # a tuned (corrupt) tile so large that, at a wide activation batch, the
    # activation block alone blows the budget; estimates bake at build
    # time, while this table is active
    bad = TuningTable(device=device_kind(),
                      entries={"gemv_pallas": {"tm": 1 << 20,
                                               "target_depth": 128}})
    set_active_table(bad)
    try:
        return _r6_program("fixture/r6:trigger", decode_m=1 << 16)
    finally:
        clear_active_table()


def _r6_clean():
    return _r6_program("fixture/r6:clean")


# -- R7: unmodelled device kind ---------------------------------------------


def _r7_program(name, kind):
    def f(x):
        return x * 2.0

    return build_program(name, f, (_x(),), model_dtype=jnp.float32,
                         device_kind=kind)


def _r7_trigger():
    return _r7_program("fixture/r7:trigger", "tpu:tpu_v99")


def _r7_clean():
    return _r7_program("fixture/r7:clean", None)


FIXTURES = {
    "R1": {"trigger": _r1_trigger, "clean": _r1_clean},
    "R2": {"trigger": _r2_trigger, "clean": _r2_clean},
    "R3": {"trigger": _r3_trigger, "clean": _r3_clean},
    "R4": {"trigger": _r4_trigger, "clean": _r4_clean},
    "R5": {"trigger": _r5_trigger, "clean": _r5_clean},
    "R6": {"trigger": _r6_trigger, "clean": _r6_clean},
    "R7": {"trigger": _r7_trigger, "clean": _r7_clean},
}


def fixture_programs(rule_id: str, kind: str):
    """Build the ``kind`` ('trigger' | 'clean') fixture for ``rule_id``."""
    return FIXTURES[rule_id][kind]()
