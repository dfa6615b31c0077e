"""ShardingRules edge cases not covered by the integration dist tests:
empty rules, rank-mismatched leaves, divisibility/dedup guards, and the
sparse-leaf (FixedMaskTensor) value/mask co-sharding invariant."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.layouts import FixedMaskTensor
from repro.dist.sharding import (
    ShardingRules,
    batch_spec,
    param_specs,
    tree_shardings,
)

EMPTY = ShardingRules(batch=None, seq=None, embed=None, heads=None,
                      ff=None, vocab=None, expert=None)


class FakeMesh:
    """Mesh stand-in for pure spec logic (param_specs/batch_spec only use
    axis_names and shape); lets unit tests exercise >1-sized axes without
    the subprocess device-count harness."""

    def __init__(self, **shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


MESH_2x4 = FakeMesh(data=2, model=4)


def spec_leaves(tree):
    return jax.tree_util.tree_leaves(
        tree, is_leaf=lambda x: isinstance(x, P))


def test_resolve_filters_to_available_axes():
    r = ShardingRules()
    assert r.resolve("batch", {"data", "model"}) == "data"
    assert r.resolve("batch", {"pod", "data", "model"}) == ("pod", "data")
    assert r.resolve("heads", {"data"}) is None
    assert r.resolve("no_such_logical_axis", {"data", "model"}) is None


def test_resolve_comma_string():
    # the CLI hillclimb form: --opt heads=data,model
    r = ShardingRules(heads="data,model", ff="model")
    assert r.resolve("heads", {"data", "model"}) == ("data", "model")
    assert r.resolve("ff", {"data", "model"}) == "model"
    assert ShardingRules(ff="").resolve("ff", {"model"}) is None


def test_empty_rules_replicate_everything():
    params = {
        "embedding": jnp.zeros((16, 8)),
        "layers": {"mlp": {"wi": jnp.zeros((2, 8, 32)),
                           "wo": jnp.zeros((2, 32, 8))}},
    }
    specs = param_specs(params, EMPTY, MESH_2x4)
    for s in spec_leaves(specs):
        assert s == P(*([None] * len(s)))
    assert batch_spec(jnp.zeros((8, 4)), EMPTY, MESH_2x4) == P(None, None)


def test_rank_mismatched_leaves_never_crash():
    # leaves whose rank is below what the name-pattern rule expects must
    # degrade to replicated, not index out of range
    params = {
        "embedding": jnp.zeros((16,)),          # rule wants 2 dims
        "layers": {"mlp": {"wi": jnp.zeros((32,)),
                           "wo": jnp.zeros(())},  # scalar
                   "attn": {"wo": jnp.zeros((8,))}},
    }
    specs = param_specs(params, ShardingRules(), MESH_2x4)
    assert specs["layers"]["mlp"]["wo"] == P()
    # embedding [16]: vocab rule targets dim -2 (absent); embed dim -1 is
    # None by default -> fully replicated
    assert specs["embedding"] == P(None)
    assert specs["layers"]["attn"]["wo"] == P(None)


def test_non_divisible_dims_fall_back_to_replicated():
    params = {"layers": {"mlp": {"wi": jnp.zeros((2, 8, 30))}}}  # 30 % 4 != 0
    specs = param_specs(params, ShardingRules(), MESH_2x4)
    assert specs["layers"]["mlp"]["wi"] == P(None, None, None)
    # batch dim not divisible by the dp axis -> replicated
    assert batch_spec(jnp.zeros((3, 4)), ShardingRules(), MESH_2x4) == \
        P(None, None)


def test_mesh_axis_never_used_twice_per_leaf():
    # moe wi [E, D, F']: expert and ff both resolve to "model"; only the
    # expert dim may take it
    params = {"layers": {"moe": {"wi": jnp.zeros((4, 8, 16))}}}
    specs = param_specs(params, ShardingRules(), MESH_2x4)
    assert specs["layers"]["moe"]["wi"] == P("model", None, None)


def test_fixed_mask_value_and_mask_shard_identically():
    val = jnp.ones((8, 16))
    mask = jnp.ones((8, 16), bool)
    params = {"layers": {"mlp": {"wi": FixedMaskTensor(val, mask)}}}
    specs = param_specs(params, ShardingRules(), MESH_2x4)
    node = specs["layers"]["mlp"]["wi"]
    assert isinstance(node, FixedMaskTensor)
    assert node.val == node.mask == P(None, "model")


def test_sparse_leaf_shardings_round_trip_device_put():
    # on a real (1-device) mesh the spec tree must match the params treedef
    # exactly: tree_shardings + device_put round-trips sparse leaves
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    val = jnp.arange(32, dtype=jnp.float32).reshape(4, 8)
    mask = (val % 2 == 0)
    params = {"layers": {"mlp": {"wi": FixedMaskTensor(val, mask)}},
              "final_norm": jnp.zeros((8,))}
    sh = tree_shardings(param_specs(params, ShardingRules(), mesh), mesh)
    node = sh["layers"]["mlp"]["wi"]
    assert isinstance(node.val, NamedSharding)
    assert node.val.spec == node.mask.spec
    placed = jax.device_put(params, sh)
    np.testing.assert_array_equal(
        np.asarray(placed["layers"]["mlp"]["wi"].to_dense()),
        np.asarray(val * mask))
