"""Unified distribution layer — the paper's consolidation move applied to
parallelism.

The paper replaces application-specific logging with one "client events"
layer every downstream job consumes; ``repro.dist`` does the same for
distribution machinery. Everything that touches a mesh lives here.

Public API by module:

* ``sharding`` — logical-axis sharding rules: ``ShardingRules`` (named
  logical dims -> mesh axes), ``REPLICATED``, ``LOGICAL_AXES``,
  ``constrain`` (with_sharding_constraint by logical name), ``tree_spec``
  (axes pytree -> PartitionSpec pytree), ``tree_shardings`` (same but
  device-placeable ``NamedSharding``s — how the serving scheduler places
  params and the KV-cache slab), ``arch_rules`` (per-architecture rule
  derivation), ``adapt_rules_for_mesh`` (elastic degradation when an axis
  does not divide).
* ``mesh`` — mesh construction, functions not module constants (importing
  never touches device state): ``make_production_mesh`` (256-chip pods,
  optional multi-pod), ``make_host_mesh`` (small explicit test meshes).
* ``collectives`` — the reusable dataflow primitives: ``mix64`` /
  ``shard_of_user`` (avalanched key hashing), ``bucket_by_destination``
  (fixed-capacity pytree bucketing, shared by MoE dispatch and the log
  pipeline), ``keyed_all_to_all`` (bucketing + all_to_all as one keyed
  repartition stage), ``make_distributed_sessionize`` and
  ``make_distributed_histogram`` (standalone shuffle/psum jobs), and
  ``gossip_all_gather`` (the serving fleet's fixed-shape occupancy
  exchange — identity host-local, all-gather over a mesh axis). The
  multi-stage log pipeline composing these lives in
  ``repro.data.distpipe``.
* ``compat`` — the jax spellings the tree shares, in one place:
  ``enable_x64`` (the log tier's scoped 64-bit context), ``shard_map``
  (replication check off by default), ``use_mesh`` (``jax.set_mesh``),
  ``make_mesh`` (Auto axis types), ``abstract_mesh``, ``active_mesh``,
  ``cost_analysis``.

Back-compat shims (kept so pre-PR-1 callers keep working; new code imports
from ``repro.dist``): ``repro.core.distributed`` re-exports the collectives
with the old private names and 2-tuple ``_bucket_by_destination`` contract;
``repro.launch.mesh`` re-exports the mesh builders.
"""
from .compat import shard_map, use_mesh, make_mesh, abstract_mesh, \
    active_mesh
from .sharding import (ShardingRules, REPLICATED, LOGICAL_AXES, constrain,
                       tree_spec, tree_shardings, arch_rules,
                       adapt_rules_for_mesh)
from .mesh import make_production_mesh, make_host_mesh
from .collectives import (mix64, shard_of_user, bucket_by_destination,
                          keyed_all_to_all, make_distributed_sessionize,
                          make_distributed_histogram, gossip_all_gather)

__all__ = [
    "shard_map", "use_mesh", "make_mesh", "abstract_mesh", "active_mesh",
    "ShardingRules", "REPLICATED", "LOGICAL_AXES", "constrain",
    "tree_spec", "tree_shardings", "arch_rules", "adapt_rules_for_mesh",
    "make_production_mesh", "make_host_mesh",
    "mix64", "shard_of_user", "bucket_by_destination", "keyed_all_to_all",
    "make_distributed_sessionize", "make_distributed_histogram",
    "gossip_all_gather",
]
