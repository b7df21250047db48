"""Reusable collectives (moved here from repro.core.distributed).

Distributed sessionization is the paper's Hadoop shuffle on a TPU mesh.
The paper reconstructs sessions with a MapReduce shuffle keyed on
``(user_id, session_id)``. On a TPU pod the identical dataflow is:

1. each ``data``-axis shard holds an arbitrary slice of the hour's events
   (that is exactly how the log mover deposits them: partially ordered,
   arbitrarily partitioned);
2. every shard buckets its rows by ``hash(user_id) % n_shards`` and an
   ``all_to_all`` collective performs the keyed repartition over ICI — all
   events of a user land on one shard;
3. each shard runs the local fused sort + segment pass (sessionize.py).

Bucketing uses fixed per-destination capacity (the MoE dispatch pattern):
overflowed rows are counted and reported, never silently lost — the caller
re-runs with a larger capacity factor, mirroring how the production job
sizes itself from the previous histogram job.

The primitives are deliberately generic: ``bucket_by_destination`` handles
payload rows of any rank (the MoE expert dispatch in models/moe.py routes
(T, D) activations through the same function the sessionizer uses for
scalar event columns), and ``keyed_all_to_all`` is the bucketing +
``all_to_all`` repartition as one reusable stage for future pipeline work.

Also here: the distributed histogram (local segment_sum + psum) used by the
dictionary-building job.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .compat import enable_x64, shard_map, use_mesh
from ..core.sessionize import _sessionize, DEFAULT_GAP_MS


def mix64(x: jax.Array) -> jax.Array:
    """splitmix64 finalizer — avalanche so modulo sharding is uniform."""
    x = x.astype(jnp.uint64)
    x = (x ^ (x >> 30)) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> 27)) * jnp.uint64(0x94D049BB133111EB)
    x = x ^ (x >> 31)
    return x


def shard_of_user(user_id: jax.Array, n_shards: int) -> jax.Array:
    return (mix64(user_id) % jnp.uint64(n_shards)).astype(jnp.int32)


def bucket_by_destination(cols, dest: jax.Array, n_dest: int, capacity: int):
    """Scatter rows into (n_dest, capacity) buckets.

    ``cols`` is any pytree of arrays sharing leading dim ``len(dest)`` — a
    flat column dict (the sessionizer), activations with trailing dims (the
    MoE dispatch routes (T, D) rows through here), or nested rollup payload
    trees (the distributed pipeline ships column dicts plus per-row rollup
    structs in one call). Rows are stably sorted by destination, positions
    within a destination are contiguous ranks; rows ranked beyond capacity
    are dropped (counted, never silent). Buckets get shape
    (n_dest, capacity, *payload).

    Returns ``(buckets, order, dest_sorted, pos, dropped)``; callers that
    only repartition use ``(buckets, dropped)``, the MoE combine path also
    needs the sort permutation to route results back.
    """
    n = dest.shape[0]
    with jax.named_scope("sort"):
        order = jnp.argsort(dest, stable=True)
    d_sorted = dest[order]
    idx = jnp.arange(n, dtype=jnp.int32)
    start = jax.ops.segment_min(idx, d_sorted, num_segments=n_dest)
    pos = idx - start[d_sorted]
    dropped = jnp.sum((pos >= capacity).astype(jnp.int32))

    def scatter(v):
        v_sorted = v[order]
        buf = jnp.zeros((n_dest, capacity) + v.shape[1:], v.dtype)
        return buf.at[d_sorted, pos].set(v_sorted, mode="drop")

    out = jax.tree.map(scatter, cols)
    return out, order, d_sorted, pos, dropped


@jax.named_scope("repartition")
def keyed_all_to_all(cols, dest: jax.Array, axis: str, n_shards: int,
                     capacity: int):
    """Keyed repartition over mesh axis ``axis`` (call inside shard_map).

    Buckets local rows by destination shard and performs the all_to_all
    shuffle; ``cols`` is any pytree of same-leading-dim arrays (see
    ``bucket_by_destination``). Returns the received pytree with flat
    leading dim ``n_shards * capacity`` (zero-padded — receivers must mask
    on a validity column) plus the local dropped-row count.
    """
    buckets, _, _, _, dropped = bucket_by_destination(
        cols, dest, n_shards, capacity)
    recv = jax.tree.map(
        lambda v: jax.lax.all_to_all(v, axis, split_axis=0, concat_axis=0),
        buckets)
    flat = jax.tree.map(lambda v: v.reshape((-1,) + v.shape[2:]), recv)
    return flat, dropped


def make_distributed_sessionize(mesh: Mesh, axis: str = "data", *,
                                gap_ms: int = DEFAULT_GAP_MS,
                                capacity_factor: float = 2.0,
                                max_sessions_per_shard: int,
                                max_len: int):
    """Build a jitted distributed sessionize over ``mesh[axis]``.

    Inputs are event columns sharded on the leading dim over ``axis``;
    outputs are per-shard Sessionized fields stacked on a leading shard dim
    (still sharded over ``axis``), plus the global dropped-row count.
    """
    n_shards = mesh.shape[axis]

    def local_fn(user_id, session_id, timestamp, code, ip, valid):
        n_local = user_id.shape[0]
        capacity = int(np.ceil(n_local * capacity_factor / n_shards))
        dest = shard_of_user(user_id, n_shards)
        # Invalid rows must not consume capacity: route them to shard of
        # their hash anyway but mark invalid (they're masked later); cheaper
        # than compaction and correct because sessionize drops invalids.
        cols = dict(user_id=user_id, session_id=session_id,
                    timestamp=timestamp, code=code, ip=ip,
                    valid=valid.astype(jnp.int32))
        flat, dropped = keyed_all_to_all(cols, dest, axis, n_shards, capacity)
        # Received padding rows: zero-initialized buckets have valid=0.
        out = _sessionize(
            flat["user_id"], flat["session_id"], flat["timestamp"],
            flat["code"], flat["ip"], flat["valid"].astype(bool),
            gap_ms=gap_ms, max_sessions=max_sessions_per_shard,
            max_len=max_len)
        total_dropped = jax.lax.psum(dropped, axis)
        # Add leading per-shard dim for out_specs concatenation.
        out = {k: v[None] for k, v in out.items()}
        return out, total_dropped[None]

    in_spec = P(axis)
    fn = shard_map(local_fn, mesh=mesh,
                   in_specs=(in_spec,) * 6,
                   out_specs=({k: P(axis) for k in
                               ("symbols", "length", "user_id", "session_id",
                                "ip", "start_ts", "duration_s", "num_sessions",
                                "num_events", "truncated")}, P(axis)))

    def wrapper(user_id, session_id, timestamp, code, ip=None, valid=None):
        n = len(user_id)
        if ip is None:
            ip = np.zeros(n, np.int64)
        if valid is None:
            valid = np.ones(n, bool)
        with enable_x64():
            with use_mesh(mesh):
                out, dropped = jax.jit(fn)(
                    jnp.asarray(user_id, jnp.int64),
                    jnp.asarray(session_id, jnp.int64),
                    jnp.asarray(timestamp, jnp.int64),
                    jnp.asarray(code, jnp.int32),
                    jnp.asarray(ip, jnp.int64),
                    jnp.asarray(valid, bool))
        return out, int(np.asarray(dropped)[0])

    return wrapper


# one compiled gossip exchange per (mesh, axis) — the vectors are tiny and
# fixed-shape, so a single jitted all-gather serves every router tick
# without retracing
_GOSSIP_FNS: dict = {}


def gossip_all_gather(vecs, mesh: Mesh | None = None,
                      axis: str = "data") -> np.ndarray:
    """Exchange fixed-shape occupancy vectors between fleet replicas.

    ``vecs`` is ``(n_replicas, k)`` int-like — one small stats vector per
    replica (the serving fleet gossips ``[free, pending, active]``). With
    ``mesh=None`` every replica is host-local and the exchange is the
    identity (the degenerate single-host fleet the tests and benchmarks
    run). With a mesh, each shard holds its replicas' rows and the rows
    are all-gathered over ``mesh[axis]`` so every shard sees the full
    fleet — the same code path host-local tests exercise on 1-device
    meshes. Always returns a host ``np.ndarray`` of shape
    ``(n_replicas_total, k)`` int32: the router consumes it with plain
    python, and a tiny device round-trip per tick would dwarf the gossip.
    """
    arr = np.asarray(vecs, np.int32)
    if arr.ndim != 2:
        raise ValueError(
            f"gossip vectors must be (n_replicas, k), got {arr.shape}")
    if mesh is None:
        return arr
    n_shards = mesh.shape[axis]
    if arr.shape[0] % n_shards:
        raise ValueError(
            f"{arr.shape[0]} gossip rows do not shard evenly over "
            f"mesh axis {axis!r} of size {n_shards}")
    key = (mesh, axis)
    fn = _GOSSIP_FNS.get(key)
    if fn is None:
        def local_fn(x):
            return jax.lax.all_gather(x, axis, axis=0, tiled=True)

        fn = jax.jit(shard_map(local_fn, mesh=mesh,
                               in_specs=(P(axis),), out_specs=P()))
        _GOSSIP_FNS[key] = fn
    with use_mesh(mesh):
        return np.asarray(fn(jnp.asarray(arr)))


def make_distributed_histogram(mesh: Mesh, axis: str = "data", *,
                               num_names: int):
    """Distributed event histogram: local segment_sum + psum (the daily
    dictionary job, §4.2, over the mesh instead of a Pig job)."""

    def local_fn(name_ids, valid):
        ids = jnp.where(valid, name_ids, num_names)
        local = jax.ops.segment_sum(
            jnp.ones_like(ids, jnp.int32), ids,
            num_segments=num_names + 1)[:num_names]
        return jax.lax.psum(local, axis)

    fn = shard_map(local_fn, mesh=mesh, in_specs=(P(axis), P(axis)),
                   out_specs=P())

    def wrapper(name_ids, valid=None):
        if valid is None:
            valid = np.ones(len(name_ids), bool)
        with use_mesh(mesh):
            return np.asarray(jax.jit(fn)(
                jnp.asarray(name_ids, jnp.int32), jnp.asarray(valid, bool)))

    return wrapper
