"""The main path's Pallas kernels compile for a TPU v5e at bert-base widths.

Each test lowers one kernel with ``interpret=False`` for a *described*
``v5e:2x2`` topology (no chip attached) and compiles it with the TPU
compiler, which refuses misaligned tiles, unsupported layouts and VMEM
overruns that interpret mode never sees.  A compile that passes proves
nothing about values or times; the interpret-mode differential tests and
``chip_smoke.py`` cover those.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import nmg
from repro.kernels.nm_mask import nm_mask_pallas
from repro.kernels.nmg_fused import nmg_ffn_pallas, nmg_qkv_pallas
from repro.kernels.nmg_gemv import nmg_gemv_pallas
from repro.kernels.nmg_spmm import nmg_spmm_pallas

pytestmark = pytest.mark.pallas_interpret  # Pallas kernels, CPU runner

D, F = 768, 3072          # bert-base d_model, d_ff
FMT = (1, 4, 16, 64)      # the serving format: n:m:g 1:4:16, gr=64
DECODE_M, PREFILL_M = 8, 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(x, sharding):
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def _weight(K, R, dtype, sharding):
    """Shapes of a [K, R] weight in the serving layout (sparse along K)."""
    n, m, g, gr = FMT
    shapes = jax.eval_shape(
        functools.partial(nmg.dense_to_grouped_nm, n=n, m=m, g=g, gr=gr,
                          sparse_dim=0),
        jax.ShapeDtypeStruct((K, R), dtype))
    return jax.tree_util.tree_map(lambda s: _spec(s, sharding), shapes)


def _compiles_to_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("K,R", [(D, F), (F, D)], ids=["wi", "wo"])
def test_gemv_compiles(one_chip, K, R, dtype):
    w = _weight(K, R, dtype, one_chip)
    b = jax.ShapeDtypeStruct((K, DECODE_M), dtype, sharding=one_chip)
    _compiles_to_kernel(
        lambda w, b: nmg_gemv_pallas(w, b, out_dtype=dtype, interpret=False),
        w, b)


@pytest.mark.parametrize("stream", [True, False], ids=["stream", "grid"])
@pytest.mark.parametrize("K,R", [(D, F), (F, D)], ids=["wi", "wo"])
def test_spmm_compiles(one_chip, K, R, stream):
    w = _weight(K, R, jnp.bfloat16, one_chip)
    b = jax.ShapeDtypeStruct((K, PREFILL_M), jnp.bfloat16, sharding=one_chip)
    _compiles_to_kernel(
        lambda w, b: nmg_spmm_pallas(w, b, stream=stream, interpret=False),
        w, b)


def test_fused_qkv_compiles(one_chip):
    ws = tuple(_weight(D, D, jnp.bfloat16, one_chip) for _ in range(3))
    b = jax.ShapeDtypeStruct((D, DECODE_M), jnp.bfloat16, sharding=one_chip)
    _compiles_to_kernel(
        lambda ws, b: nmg_qkv_pallas(ws, b, out_dtype=jnp.bfloat16,
                                     interpret=False),
        ws, b)


@pytest.mark.parametrize("act", ["gelu", "silu"])
def test_fused_ffn_compiles(one_chip, act):
    w = _weight(D, 2 * F, jnp.bfloat16, one_chip)
    b = jax.ShapeDtypeStruct((D, DECODE_M), jnp.bfloat16, sharding=one_chip)
    _compiles_to_kernel(
        lambda w, b: nmg_ffn_pallas(w, b, act=act, out_dtype=jnp.bfloat16,
                                    interpret=False),
        w, b)


@pytest.mark.parametrize("n,m", [(1, 4), (2, 4)])
def test_nm_mask_compiles(one_chip, n, m):
    x = jax.ShapeDtypeStruct((F, D), jnp.float32, sharding=one_chip)
    _compiles_to_kernel(
        lambda x: nm_mask_pallas(x, n, m, interpret=False), x)


# -- StarCoder2-15B's FFN widths: d_model 6144, d_ff 24576 --------------------

SC_D, SC_F = 6144, 24576

#: largest double-buffered activation slab (tokens x K, bf16) the streamed
#: schedule fits in the kernel's scoped VMEM on a v5e: K = 12288 at 128
#: tokens (3 MiB) compiles, K = 24576 (6 MiB) does not
STREAM_SLAB_BYTES = 4 << 20


@pytest.mark.parametrize("K,R", [(SC_D, SC_F), (SC_F, SC_D)],
                         ids=["c_fc", "c_proj"])
def test_starcoder2_gemv_compiles(one_chip, K, R):
    w = _weight(K, R, jnp.bfloat16, one_chip)
    b = jax.ShapeDtypeStruct((K, 16), jnp.bfloat16, sharding=one_chip)
    _compiles_to_kernel(
        lambda w, b: nmg_gemv_pallas(w, b, out_dtype=jnp.bfloat16,
                                     interpret=False),
        w, b)


@pytest.mark.parametrize("schedule", ["stream", "grid", "routed"])
@pytest.mark.parametrize("M", [16, 1024, 3072])
@pytest.mark.parametrize("K,R", [(SC_D, SC_F), (SC_F, SC_D)],
                         ids=["c_fc", "c_proj"])
def test_starcoder2_spmm_compiles(one_chip, K, R, M, schedule):
    """Both schedules, and the config the routing picks by default; the
    streamed one at the widest token tile whose resident activation slab
    fits (128 tokens at K = 6144, 64 at K = 24576)."""
    from repro.tune import routing

    if schedule == "routed":
        cfg, _ = routing.spmm_pallas_config(K=K, R=R, fmt=FMT[:3],
                                            gr=FMT[3], dtype=jnp.bfloat16)
    else:
        stream = schedule == "stream"
        tn = min(128, STREAM_SLAB_BYTES // (K * 2)) if stream \
            else 128
        cfg = {"stream": stream, "tn": tn, "target_depth": 128}
    w = _weight(K, R, jnp.bfloat16, one_chip)
    b = jax.ShapeDtypeStruct((K, M), jnp.bfloat16, sharding=one_chip)
    _compiles_to_kernel(
        lambda w, b: nmg_spmm_pallas(w, b, interpret=False, **cfg), w, b)


@pytest.mark.parametrize("K,R", [(SC_D, SC_F), (SC_F, SC_D)],
                         ids=["c_fc", "c_proj"])
def test_starcoder2_spmm_default_compiles_at_2048(one_chip, K, R):
    """The middle prompt bucket on the routed default (1024 and 3072 are
    in ``test_starcoder2_spmm_compiles``): two 1024-token tiles, the
    [2, 1024, 128] accumulator and the [128, 1024] tile scratch."""
    from repro.tune import routing

    cfg, _ = routing.spmm_pallas_config(K=K, R=R, fmt=FMT[:3], gr=FMT[3],
                                        dtype=jnp.bfloat16)
    w = _weight(K, R, jnp.bfloat16, one_chip)
    b = jax.ShapeDtypeStruct((K, 2048), jnp.bfloat16, sharding=one_chip)
    _compiles_to_kernel(
        lambda w, b: nmg_spmm_pallas(w, b, interpret=False, **cfg), w, b)


@pytest.mark.parametrize("K,R", [(768, 3072), (3072, 768), (SC_D, SC_F),
                                 (SC_F, SC_D)])
def test_default_spmm_config_by_width(K, R):
    """bert-base's and StarCoder2-15B's FFN widths take the one shipped
    default, 1024-token tiles on the grid schedule, which holds no
    activation slab (the streamed slab overruns VMEM at K = 24576)."""
    from repro.tune import routing

    cfg, src = routing.spmm_pallas_config(K=K, R=R, fmt=(1, 4, 16), gr=64,
                                          dtype=jnp.bfloat16)
    assert src == "default"
    assert cfg == routing.DEFAULT_SPMM_PALLAS
    assert not cfg["stream"]


@pytest.mark.parametrize("M", [32, 256, 12288])
@pytest.mark.parametrize("K,R", [(768, 3072), (3072, 768)],
                         ids=["wi", "wo"])
def test_bert_spmm_default_compiles(one_chip, K, R, M):
    """The routed default at bert-base's FFN widths: decode slots, the
    widest prompt bucket and a training batch of 32 x 384 tokens."""
    from repro.tune import routing

    cfg, _ = routing.spmm_pallas_config(K=K, R=R, fmt=FMT[:3], gr=FMT[3],
                                        dtype=jnp.bfloat16)
    w = _weight(K, R, jnp.bfloat16, one_chip)
    b = jax.ShapeDtypeStruct((K, M), jnp.bfloat16, sharding=one_chip)
    _compiles_to_kernel(
        lambda w, b: nmg_spmm_pallas(w, b, interpret=False, **cfg), w, b)


#: a weight of 13 fiber groups (832 rows at gr=64): the last 128-lane tile
#: holds one group beside the zero pad, and the last value block reaches
#: past the array
ODD_R = 13 * 64


@pytest.mark.parametrize("kernel", ["gemv", "spmm_stream", "spmm_routed",
                                    "qkv_boundary", "ffn_odd_half"])
def test_odd_group_count_compiles(one_chip, kernel):
    """Lone last groups in every kernel: a GEMV and an SpMM over 13 groups,
    a fused QKV whose q/k boundary falls inside a tile (3 + 1 + 1 groups),
    and a gated pair of 3 groups a half."""
    from repro.tune import routing

    M = 16 if kernel in ("gemv", "qkv_boundary", "ffn_odd_half") else 2048
    b = jax.ShapeDtypeStruct((D, M), jnp.bfloat16, sharding=one_chip)
    if kernel == "qkv_boundary":
        ws = tuple(_weight(D, R, jnp.bfloat16, one_chip)
                   for R in (192, 64, 64))
        _compiles_to_kernel(
            lambda ws, b: nmg_qkv_pallas(ws, b, out_dtype=jnp.bfloat16,
                                         interpret=False), ws, b)
        return
    if kernel == "ffn_odd_half":
        w = _weight(D, 2 * 192, jnp.bfloat16, one_chip)
        _compiles_to_kernel(
            lambda w, b: nmg_ffn_pallas(w, b, act="silu",
                                        out_dtype=jnp.bfloat16,
                                        interpret=False), w, b)
        return
    w = _weight(D, ODD_R, jnp.bfloat16, one_chip)
    if kernel == "gemv":
        fn = lambda w, b: nmg_gemv_pallas(w, b, out_dtype=jnp.bfloat16,  # noqa: E731
                                          interpret=False)
    else:
        cfg = {"stream": True, "tn": 128, "target_depth": 128}
        if kernel == "spmm_routed":
            cfg, _ = routing.spmm_pallas_config(
                K=D, R=ODD_R, fmt=FMT[:3], gr=FMT[3], dtype=jnp.bfloat16)
        fn = lambda w, b: nmg_spmm_pallas(w, b, interpret=False, **cfg)  # noqa: E731
    _compiles_to_kernel(fn, w, b)
