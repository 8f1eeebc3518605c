"""mxnet_tpu.sharding — named-mesh GSPMD partitioning for the Module /
executor stack.

ROADMAP item 1: multi-device training used to be data-parallel replication
on a hard-coded 1-D mesh inside executor_group.  This subsystem makes the
parallel layout DATA instead of code:

* :func:`build_mesh` — multi-axis named meshes (``("data", "model")``)
  from ``jax.devices()`` with ``-1`` axis inference and a process-aware
  device layout (mesh.py);
* :func:`match_partition_rules` / :class:`PartitionRules` — ordered regex
  rules over parameter names -> a ``PartitionSpec`` per parameter, with a
  replicated fallback, scalar short-circuit, explainable resolution, and
  presets for the bench models (rules.py);
* :func:`shard_params` / :func:`gather_params` — place or collect a param
  dict against the mesh through committed ``NamedSharding``s
  (placement.py).

The executor stack consumes these through ``Module.bind(..., mesh=...,
partition_rules=...)``: the fused train step is lowered ONCE under the
resulting shardings and XLA's SPMD partitioner inserts the collectives —
data-, tensor-, and (later) pipeline-parallelism become spec changes, not
code changes.  With no rules passed, nothing changes: the replicated
data-parallel path is bit-identical to before.

Env knobs (see docs/how_to/sharding.md):

* ``MXNET_SHARDING_MESH`` / ``MXNET_SHARDING_RULES`` activate a layout
  for any existing training script without code changes;
* ``MXNET_SHARDING_VALIDATE`` gates the uneven-split error;
* ``MXNET_SHARDING_EXPLAIN`` logs the resolved rule table at bind.
"""
from ..base import register_env

from .mesh import MeshConfig, build_mesh, mesh_axes, mesh_fingerprint
from .rules import (PartitionRules, PRESETS, as_rules,
                    explain_partition_rules, get_preset,
                    match_partition_rules)
from .placement import (collective_compiler_options, gather_params,
                        make_shardings, param_bytes, place, shard_params,
                        spec_shard_factor, validate_specs)

__all__ = [
    "MeshConfig", "build_mesh", "mesh_axes", "mesh_fingerprint",
    "PartitionRules", "PRESETS", "as_rules", "get_preset",
    "match_partition_rules", "explain_partition_rules",
    "shard_params", "gather_params", "make_shardings", "place",
    "param_bytes", "spec_shard_factor", "validate_specs",
    "collective_compiler_options",
]

register_env("MXNET_SHARDING_MESH", "", str,
             "Mesh layout ('data=-1,model=2') applied by Module.bind when "
             "no mesh argument is passed. Empty keeps the default "
             "replicated data-parallel layout.")
register_env("MXNET_SHARDING_RULES", "", str,
             "Partition-rule preset name (see sharding.PRESETS) applied by "
             "Module.bind when no partition_rules argument is passed. "
             "Requires a mesh (argument or MXNET_SHARDING_MESH).")
register_env("MXNET_SHARDING_VALIDATE", 1, int,
             "Reject PartitionSpecs whose sharded dims don't divide evenly "
             "by their mesh axes (GSPMD would silently pad). 0 allows "
             "uneven splits.")
register_env("MXNET_SHARDING_EXPLAIN", 0, int,
             "Log the resolved rule table (param -> rule -> spec) at bind "
             "time.")
