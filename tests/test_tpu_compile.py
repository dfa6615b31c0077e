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
