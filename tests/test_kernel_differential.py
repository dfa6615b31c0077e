"""Golden + differential tests for the n:m:g matmul kernels.

``kernels/nmg_spmm.py`` (interpret mode on CPU) is swept against the
densify-then-matmul oracle in ``kernels/ref.py`` across a grid of
(n, m, g, gr) formats and shapes with explicit tolerances, plus a golden
exact-arithmetic case and a regression assertion on the output dtype
(the kernel contract is an f32 accumulator regardless of input dtype).

The decode-optimized GEMV path gets the same treatment: an M-sweep
asserting ``gemv == spmm == oracle`` across formats and right-operand
widths (including the shape-router boundary), the dtype-preserving
epilogue contract, and plan-caching properties (a precomputed SpmmPlan
changes nothing but the work saved).
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import nmg
from repro.core.layouts import nm_patterns
from repro.kernels import ops as kops
from repro.kernels import nmg_spmm as nmgk
from repro.kernels import ref as kref
from repro.kernels.nmg_fused import nmg_ffn_pallas, nmg_qkv_pallas
from repro.kernels.nmg_gemv import nmg_gemv_pallas
from repro.kernels.nmg_spmm import nmg_spmm_pallas

KEY = jax.random.PRNGKey(42)

# (n, m, g, gr) format grid: paper CPU format (gr=1), TPU row-shared
# formats, single-pattern n=m corner, and wide-m patterns
FORMATS = [
    (1, 4, 1, 1),
    (1, 4, 4, 2),
    (2, 4, 2, 1),
    (2, 4, 2, 4),
    (2, 4, 16, 8),
    (3, 6, 1, 2),
    (1, 2, 8, 8),
    (2, 6, 2, 1),
]

# (R, K, N) including non-multiples of the chunk extent (padding paths)
SHAPES = [(8, 96, 32), (16, 192, 64), (5, 100, 33)]

TOL = {jnp.dtype(jnp.float32): 1e-4, jnp.dtype(jnp.bfloat16): 5e-2}


@pytest.mark.parametrize("fmt", FORMATS,
                         ids=lambda f: "{}:{}:{}gr{}".format(*f))
@pytest.mark.pallas_interpret
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_nmg_spmm_grid_vs_ref(fmt, shape):
    n, m, g, gr = fmt
    R, K, N = shape
    x = jax.random.normal(KEY, (R, K))
    b = jax.random.normal(jax.random.PRNGKey(1), (K, N))
    t = nmg.dense_to_grouped_nm(x, n=n, m=m, g=g, gr=gr)
    ref = kref.nmg_spmm_ref(t, b)
    out = nmg_spmm_pallas(t, b, interpret=True)
    assert out.shape == (R, N)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.pallas_interpret
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_nmg_spmm_output_dtype_regression(dtype):
    """Contract: the kernel accumulates and returns f32 for every input
    dtype (bf16 inputs must NOT demote the output)."""
    x = jax.random.normal(KEY, (8, 96)).astype(dtype)
    b = jax.random.normal(jax.random.PRNGKey(1), (96, 32)).astype(dtype)
    t = nmg.dense_to_grouped_nm(x, n=2, m=4, g=2, gr=4)
    out = nmg_spmm_pallas(t, b, interpret=True)
    assert out.dtype == jnp.float32, (
        f"kernel output demoted to {out.dtype} for {dtype} inputs"
    )
    tol = TOL[jnp.dtype(dtype)]
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(kref.nmg_spmm_ref(t, b)),
                               rtol=tol, atol=tol)


@pytest.mark.pallas_interpret
def test_nmg_spmm_golden_exact():
    """Golden case in exact f32 arithmetic: a matrix that is already
    2:4-sparse with small-integer values, multiplied by an identity-padded
    B, must reproduce the canonical dense view bit-exactly."""
    n, m, g = 2, 4, 2
    C = math.comb(m, n)
    R, K = 4, m * C * g  # one chunk per row fiber
    x = np.zeros((R, K), np.float32)
    rng = np.random.default_rng(0)
    pats = nm_patterns(n, m)
    for r in range(R):
        # each pattern used exactly g times per chunk — the format's
        # capacity constraint — in a shuffled block order, so the layout
        # is lossless by construction
        order = rng.permutation(np.repeat(np.arange(C), g))
        for blk, pat in enumerate(order):
            x[r, blk * m + pats[pat]] = rng.integers(1, 8, size=n)
    t = nmg.dense_to_grouped_nm(jnp.asarray(x), n=n, m=m, g=g)
    # lossless by construction
    np.testing.assert_array_equal(np.asarray(t.to_dense()), x)
    out = nmg_spmm_pallas(t, jnp.eye(K, dtype=jnp.float32), interpret=True)
    np.testing.assert_array_equal(np.asarray(out), x)


# ---------------------------------------------------------------------------
# decode GEMV path: gemv == spmm == oracle across the M sweep
# ---------------------------------------------------------------------------

# right-operand widths: decode batches (1..8), the router boundary (16),
# and a prefill-shaped width (128) to pin both sides of the crossover
M_SWEEP = (1, 2, 4, 8, 16, 128)


@pytest.mark.parametrize("fmt", [(1, 4, 4, 2), (2, 4, 2, 4), (2, 4, 16, 8),
                                 (3, 6, 1, 2)],
                         ids=lambda f: "{}:{}:{}gr{}".format(*f))
@pytest.mark.parametrize("M", M_SWEEP)
def test_nmg_gemv_matches_spmm_and_oracle(fmt, M):
    n, m, g, gr = fmt
    R, K = 16, 192
    x = jax.random.normal(KEY, (R, K))
    b = jax.random.normal(jax.random.PRNGKey(1), (K, M))
    t = nmg.dense_to_grouped_nm(x, n=n, m=m, g=g, gr=gr)
    oracle = np.asarray(kref.nmg_spmm_ref(t, b))
    spmm = np.asarray(kops.nmg_spmm_xla(t, b))
    gemv = np.asarray(kops.nmg_gemv_xla(t, b))
    assert gemv.shape == spmm.shape == (R, M)
    # same contraction order in f32 => the two XLA paths agree tightly
    np.testing.assert_allclose(gemv, spmm, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(gemv, oracle, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("fmt", [(1, 4, 4, 2), (2, 4, 2, 4)],
                         ids=lambda f: "{}:{}:{}gr{}".format(*f))
@pytest.mark.pallas_interpret
def test_nmg_gemv_pallas_interpret_matches_oracle(fmt):
    n, m, g, gr = fmt
    x = jax.random.normal(KEY, (8, 96))
    b = jax.random.normal(jax.random.PRNGKey(1), (96, 4))
    t = nmg.dense_to_grouped_nm(x, n=n, m=m, g=g, gr=gr)
    out = nmg_gemv_pallas(t, b, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(kref.nmg_spmm_ref(t, b)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.pallas_interpret
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_nmg_gemv_dtype_preserving_epilogue(dtype):
    """Contract: accumulation is f32, but the epilogue emits the requested
    dtype — the serving path asks for the activation dtype and must not get
    a silent f32 round-trip (and default stays f32, the SpMM contract)."""
    x = jax.random.normal(KEY, (8, 96)).astype(dtype)
    b = jax.random.normal(jax.random.PRNGKey(1), (96, 4)).astype(dtype)
    t = nmg.dense_to_grouped_nm(x, n=2, m=4, g=2, gr=4)
    assert kops.nmg_gemv_xla(t, b).dtype == jnp.float32
    assert kops.nmg_gemv_xla(t, b, out_dtype=dtype).dtype == dtype
    assert nmg_gemv_pallas(t, b, out_dtype=dtype, interpret=True).dtype \
        == dtype
    tol = TOL[jnp.dtype(dtype)]
    np.testing.assert_allclose(
        np.asarray(kops.nmg_gemv_xla(t, b, out_dtype=jnp.float32)),
        np.asarray(kref.nmg_spmm_ref(t, b)), rtol=tol, atol=tol,
    )


def test_nmg_linear_dtype_and_value_both_regimes():
    """nmg_linear keeps x.dtype on both the decode (gemv) and prefill
    (spmm) routes and matches the densified product."""
    w = jax.random.normal(KEY, (96, 64))
    wt = nmg.dense_to_grouped_nm(w, n=2, m=4, g=2, gr=4, sparse_dim=0)
    for rows, dtype in [(4, jnp.bfloat16), (4, jnp.float32),
                        (64, jnp.bfloat16), (64, jnp.float32)]:
        x = jax.random.normal(jax.random.PRNGKey(2), (rows, 96)).astype(dtype)
        y = kops.nmg_linear(x, wt)
        assert y.dtype == dtype, (rows, dtype, y.dtype)
        tol = 1e-3 if dtype == jnp.float32 else 5e-2
        np.testing.assert_allclose(
            np.asarray(y.astype(jnp.float32)),
            np.asarray(x.astype(jnp.float32) @ wt.to_dense()),
            rtol=tol, atol=tol,
        )


def test_nmg_matmul_shape_routing():
    """The router sends decode-shaped right operands to the GEMV path and
    wide ones to the SpMM path (trace-time counters as evidence)."""
    x = jax.random.normal(KEY, (8, 96))
    t = nmg.dense_to_grouped_nm(x, n=1, m=4, g=4, gr=2)
    kops.reset_kernel_counters()
    kops.nmg_matmul(t, jnp.ones((96, kops.DECODE_M_MAX)), use_pallas=False)
    kops.nmg_matmul(t, jnp.ones((96, kops.DECODE_M_MAX + 1)),
                    use_pallas=False)
    counts = kops.kernel_counters()
    assert counts.get(("nmg_gemv", "xla")) == 1
    assert counts.get(("nmg_spmm", "xla")) == 1


# ---------------------------------------------------------------------------
# SpmmPlan caching properties
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fmt", FORMATS,
                         ids=lambda f: "{}:{}:{}gr{}".format(*f))
def test_spmm_plan_planned_equals_plan_free(fmt):
    """A conversion-time plan is pure caching: stripping it changes no
    result bit (the kernels re-derive identical indices from blk_idx)."""
    n, m, g, gr = fmt
    x = jax.random.normal(KEY, (8, 96))
    b = jax.random.normal(jax.random.PRNGKey(1), (96, 4))
    t = nmg.dense_to_grouped_nm(x, n=n, m=m, g=g, gr=gr)
    assert t.plan is not None
    bare = dataclasses.replace(t, plan=None)
    assert bare.plan is None
    # derived plan == stored plan
    np.testing.assert_array_equal(np.asarray(bare.gather_plan().cols),
                                  np.asarray(t.plan.cols))
    # identical results on every path, bitwise
    np.testing.assert_array_equal(np.asarray(kops.nmg_gemv_xla(t, b)),
                                  np.asarray(kops.nmg_gemv_xla(bare, b)))
    np.testing.assert_array_equal(np.asarray(kops.nmg_spmm_xla(t, b)),
                                  np.asarray(kops.nmg_spmm_xla(bare, b)))
    np.testing.assert_array_equal(np.asarray(t.to_dense()),
                                  np.asarray(bare.to_dense()))


def test_spmm_plan_survives_pytree_roundtrip():
    """The plan rides along through flatten/unflatten (jit/scan boundary)
    and the layout roundtrip is unaffected by its presence."""
    x = jax.random.normal(KEY, (8, 96))
    t = nmg.dense_to_grouped_nm(x, n=2, m=4, g=2, gr=2)
    leaves, treedef = jax.tree_util.tree_flatten(t)
    t2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert t2.plan is not None
    np.testing.assert_array_equal(np.asarray(t2.plan.cols),
                                  np.asarray(t.plan.cols))
    np.testing.assert_array_equal(np.asarray(t2.plan.pat_onehot),
                                  np.asarray(t.plan.pat_onehot))
    np.testing.assert_array_equal(np.asarray(t2.to_dense()),
                                  np.asarray(t.to_dense()))


@pytest.mark.pallas_interpret
def test_nmg_spmm_zero_and_ones_b():
    """B = 0 gives exactly 0; B = ones gives per-row sums of kept values
    (catches accumulator-init and index-offset bugs independently of the
    oracle)."""
    x = jax.random.normal(KEY, (8, 96))
    t = nmg.dense_to_grouped_nm(x, n=1, m=4, g=4, gr=2)
    z = nmg_spmm_pallas(t, jnp.zeros((96, 16)), interpret=True)
    np.testing.assert_array_equal(np.asarray(z), np.zeros((8, 16)))
    o = nmg_spmm_pallas(t, jnp.ones((96, 16)), interpret=True)
    want = np.asarray(t.to_dense()).sum(axis=1, keepdims=True)
    np.testing.assert_allclose(np.asarray(o), np.broadcast_to(want, (8, 16)),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# tail shapes: nothing aligned to anything
# ---------------------------------------------------------------------------

# (R, K, N) where R is not a gr multiple, K is not a chunk-extent multiple,
# and N is not a lane/tile multiple — the aligned grid above never exercises
# the padding/crop paths where Pallas index bugs hide
TAIL_SHAPES = [
    (7, 100, 129),
    (13, 52, 31),
    (33, 200, 257),
    (1, 96, 1),
]


@pytest.mark.pallas_interpret
@pytest.mark.parametrize("fmt", [(1, 4, 4, 2), (2, 4, 2, 4), (2, 4, 16, 8)],
                         ids=lambda f: "{}:{}:{}gr{}".format(*f))
@pytest.mark.parametrize("shape", TAIL_SHAPES,
                         ids=lambda s: "x".join(map(str, s)))
def test_nmg_spmm_tail_shapes_both_schedules(fmt, shape):
    """Unaligned R/K/N through both Pallas schedules: each matches the
    oracle, and streamed == grid **bitwise** (identical chunk accumulation
    order is the schedule contract)."""
    n, m, g, gr = fmt
    R, K, N = shape
    x = jax.random.normal(KEY, (R, K))
    b = jax.random.normal(jax.random.PRNGKey(1), (K, N))
    t = nmg.dense_to_grouped_nm(x, n=n, m=m, g=g, gr=gr)
    ref = np.asarray(kref.nmg_spmm_ref(t, b))
    grid = nmg_spmm_pallas(t, b, interpret=True, stream=False)
    strm = nmg_spmm_pallas(t, b, interpret=True, stream=True)
    assert grid.shape == strm.shape == (R, N)
    np.testing.assert_array_equal(np.asarray(strm), np.asarray(grid))
    np.testing.assert_allclose(np.asarray(strm), ref, rtol=1e-4, atol=1e-4)


@pytest.mark.pallas_interpret
@pytest.mark.parametrize("shape", TAIL_SHAPES,
                         ids=lambda s: "x".join(map(str, s)))
def test_nmg_gemv_tail_shapes(shape):
    """Unaligned R/K through the decode kernel (narrow B): padding rows
    must be cropped, not leak into the product."""
    R, K, _ = shape
    x = jax.random.normal(KEY, (R, K))
    b = jax.random.normal(jax.random.PRNGKey(1), (K, 3))
    t = nmg.dense_to_grouped_nm(x, n=1, m=4, g=4, gr=2)
    out = nmg_gemv_pallas(t, b, interpret=True)
    assert out.shape == (R, 3)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(kref.nmg_spmm_ref(t, b)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("delta", [-1, 0, 1])
def test_nmg_linear_straddles_decode_m_max(delta):
    """M = decode_m_max - 1 / exactly / + 1: the route flips at the
    boundary but values and dtype never change."""
    w = jax.random.normal(KEY, (96, 64))
    wt = nmg.dense_to_grouped_nm(w, n=2, m=4, g=2, gr=4, sparse_dim=0)
    rows = kops.DECODE_M_MAX + delta
    x = jax.random.normal(jax.random.PRNGKey(2), (rows, 96))
    kops.reset_kernel_counters()
    y = kops.nmg_linear(x, wt)
    counts = kops.kernel_counters()
    path = "spmm" if delta > 0 else "gemv"
    assert counts.get(("nmg_linear", f"{path}[default]")) == 1, counts
    assert y.dtype == x.dtype and y.shape == (rows, 64)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ wt.to_dense()),
                               rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# wide tiles: consecutive fiber groups share one 128-lane useful dot
# ---------------------------------------------------------------------------

WIDE_FMT = (1, 4, 16)   # the serving pattern; K = 1024 gives two windows


def _wide_weight(R, gr, K=1024, key=KEY):
    w = jax.random.normal(key, (R, K))
    return nmg.dense_to_grouped_nm(w, *WIDE_FMT, gr=gr)


@pytest.mark.pallas_interpret
@pytest.mark.parametrize("stream", [True, False], ids=["stream", "grid"])
@pytest.mark.parametrize("gr", [16, 32, 64, 128])
def test_wide_tile_matches_oracle_by_gr(gr, stream):
    """Every gr the serving layouts use, on both schedules, with two
    windows a group: 384 output rows are 24 to 3 fiber groups, 8 to 1 of
    them to a 128-lane tile."""
    t = _wide_weight(384, gr)
    b = jax.random.normal(jax.random.PRNGKey(1), (1024, 40))
    out = nmg_spmm_pallas(t, b, interpret=True, stream=stream, tn=16)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(kref.nmg_spmm_ref(t, b)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.pallas_interpret
@pytest.mark.parametrize("stream", [True, False], ids=["stream", "grid"])
def test_lone_last_group_matches_oracle(stream):
    """Five groups of 64 rows: the fifth fills half of its tile, the other
    half is the zero pad, and nothing of the pad reaches the output."""
    t = _wide_weight(5 * 64, 64)
    b = jax.random.normal(jax.random.PRNGKey(1), (1024, 24))
    out = nmg_spmm_pallas(t, b, interpret=True, stream=stream, tn=16)
    assert out.shape == (320, 24)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(kref.nmg_spmm_ref(t, b)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.pallas_interpret
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("stream", [True, False], ids=["stream", "grid"])
def test_group_bits_do_not_depend_on_its_partner(stream, dtype):
    """Group 4 of a six-group weight shares its tile with group 5; cut to
    five groups, it shares it with the zero pad.  Its columns agree bit
    for bit, and so do those of groups 0-3."""
    t = _wide_weight(6 * 64, 64)
    val2, cols3 = nmgk.storage_views(t)
    x = jax.random.normal(jax.random.PRNGKey(1), (24, 1024)).astype(dtype)
    val2 = val2.astype(dtype)
    kw = dict(n=t.n, m=t.m, g=t.g, gr=64, out_dtype=jnp.float32, tm=16,
              target_depth=128, stream=stream, interpret=True)
    paired = nmgk.nmg_pallas_call(val2, cols3, nmgk.pad_k(t, x), **kw)
    lone = nmgk.nmg_pallas_call(val2[:320], cols3[:5], nmgk.pad_k(t, x),
                                **kw)
    assert paired.shape == (24, 384) and lone.shape == (24, 320)
    np.testing.assert_array_equal(np.asarray(lone),
                                  np.asarray(paired[:, :320]))


@pytest.mark.pallas_interpret
@pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
def test_fused_qkv_segment_boundary_inside_a_tile(out_dtype):
    """q of three groups, k and v of one: q's third group and k share a
    128-lane tile, and v sits beside the pad.  Fused equals the three
    launches bit for bit, and the oracle."""
    ks = jax.random.split(KEY, 3)
    ws = tuple(nmg.dense_to_grouped_nm(jax.random.normal(k, (256, R)),
                                       *WIDE_FMT, gr=64, sparse_dim=0)
               for k, R in zip(ks, (192, 64, 64)))
    b = jax.random.normal(jax.random.PRNGKey(1), (256, 4))
    fused = nmg_qkv_pallas(ws, b, out_dtype=out_dtype, interpret=True)
    for w, f, want in zip(ws, fused, kref.nmg_qkv_ref(ws, b)):
        s = nmg_gemv_pallas(w, b, out_dtype=out_dtype, interpret=True)
        np.testing.assert_array_equal(np.asarray(f), np.asarray(s))
        np.testing.assert_allclose(np.asarray(f, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-2, atol=2e-2)


@pytest.mark.pallas_interpret
def test_fused_ffn_odd_groups_per_half():
    """A gated pair whose halves hold three groups each: the u tile and
    its v partner both end on a lone group, and the fused epilogue still
    pairs group i with group i + 3."""
    w = nmg.dense_to_grouped_nm(jax.random.normal(KEY, (256, 384)),
                                *WIDE_FMT, gr=64, sparse_dim=0)
    b = jax.random.normal(jax.random.PRNGKey(2), (256, 4))
    hh = nmg_gemv_pallas(w, b, out_dtype=jnp.float32, interpret=True)
    u, v = jnp.split(hh.T, 2, axis=-1)
    seq = (jax.nn.silu(u) * v).T
    fused = nmg_ffn_pallas(w, b, act="silu", out_dtype=jnp.float32,
                           interpret=True)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(seq))


@pytest.mark.parametrize("gr,width", [(16, 128), (64, 128), (128, 128),
                                      (256, 256)])
def test_wide_tile_counter_engages(gr, width):
    """Every launch records its dot width in ``kernel_routes`` at trace
    time."""
    t = _wide_weight(2 * gr, gr, K=256)
    val2, cols3 = nmgk.storage_views(t)
    x = jnp.zeros((8, 256), jnp.float32)
    kops.reset_kernel_counters()
    nmgk.nmg_pallas_call(val2, cols3, x, n=t.n, m=t.m, g=t.g, gr=gr,
                         out_dtype=jnp.float32, tm=8, target_depth=128,
                         stream=True, interpret=True)
    assert kops.kernel_counters() == {("nmg_pallas", f"w{width}"): 1}


def test_nmg_rows_writes_rows_with_no_transpose_after_the_kernel():
    """The kernel writes [M, R] in the order its callers read: nothing
    after the ``pallas_call`` transposes its output."""
    t = _wide_weight(256, 64, K=256)
    x = jnp.zeros((16, 256), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda t, x: nmgk.nmg_rows(
        t, x, out_dtype=jnp.bfloat16, tm=16, target_depth=128, stream=True,
        interpret=True))(t, x).jaxpr
    prims = [e.primitive.name for e in jaxpr.eqns]
    assert "pallas_call" in prims
    after = prims[prims.index("pallas_call") + 1:]
    assert "transpose" not in after, after
