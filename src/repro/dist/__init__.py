"""Distributed sparsity: sharding rules, sparse collectives, compression,
and elasticity (see docs/architecture.md §distributed).

Submodules:
  * ``sharding``    — logical-axis rules, param/batch specs, constraints
  * ``collectives`` — densify-allreduce-resparsify + value-only fast path
  * ``compression`` — top-k + error-feedback gradient exchange
  * ``elastic``     — straggler watchdog and remesh planning
"""

from repro.dist.collectives import (
    allreduce_mean,
    densify_allreduce_resparsify,
    fixed_mask_value_allreduce,
)
from repro.dist.compression import compressed_allreduce, ef_step
from repro.dist.elastic import StragglerWatchdog, plan_remesh
from repro.dist.sharding import (
    ShardingRules,
    active_rules,
    batch_spec,
    logical_constraint,
    param_specs,
    tree_shardings,
    use_rules,
)
