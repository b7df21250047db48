"""The few jax spellings the tree shares, in one place.

Every module in ``repro.dist`` (and everything built on it) goes through
these names, so a change of the jax surface is made here once.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def enable_x64():
    """Context manager tracing and running its body with 64-bit types on.

    The log tier keeps int64 user ids and timestamps end to end; each of
    its entry points opens this scope itself, so callers never have to.
    """
    return jax.enable_x64(True)


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with the replication check off by default."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axis types (sharding by rules, not types)."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def abstract_mesh(axis_shapes, axis_names):
    """Device-less mesh carrying only (axis_names, shape) — enough for rule
    manipulation (arch_rules / adapt_rules_for_mesh) on meshes larger than
    the local device count."""
    return jax.sharding.AbstractMesh(tuple(axis_shapes), tuple(axis_names))


def use_mesh(mesh):
    """Context manager activating ``mesh`` for jit / with_sharding_constraint."""
    return jax.set_mesh(mesh)


def cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()``, or ``{}`` where the backend gives none."""
    return compiled.cost_analysis() or {}


def active_mesh():
    """The mesh currently activated by ``use_mesh``, or None.

    Works inside jit tracing — the mesh context is live while the traced
    function body runs.
    """
    m = jax.sharding.get_abstract_mesh()
    return None if m is None or m.empty else m
