"""DecodeState: one cache abstraction so every family serves continuously.

The paper's core move is replacing application-specific log formats with
one unified client-events schema so every downstream consumer speaks the
same language. This module is that normalization applied to decode state:
before it, dense/moe spoke the scheduler's KV-slab dialect while
ssm/hybrid/encdec/vlm each carried bespoke cache layouts and fell back to
a fixed-batch path. Now every family's state lives behind one protocol and
the ``ContinuousScheduler`` is a pure consumer — admit/evict/backfill,
paged admission, and serving metrics work identically for all of them.

The protocol (duck-typed; ``DecodeState`` is the reference base):

* ``init(batch, budget)``      — allocate the zero slot-table state.
* ``can_admit(n, budget)``     — resource gate beyond free rows (paged:
  blocks reservable; others: always true).
* ``admit(slot, n, budget)``   — reserve row resources (paged: worst-case
  block reservation + prompt-block allocation).
* ``prefill_insert(row_state, slot, length, bucket)`` — insert one
  prefilled ``(1, bucket)`` row into the table (jitted once per row
  shape).
* ``decode_view(positions, active)`` — the device state for this decode
  step (paged: grows block tables lazily and refreshes the device copy).
* ``commit(new_state)``        — store ``decode_step``'s returned state.
* ``evict(slot)``              — release row resources (paged: free blocks
  + point the dead row at the trash block).
* ``max_positions()``          — cache-position bound (None = unbounded
  recurrent state).
* ``occupancy(num_active)`` / ``resident_bytes(num_active)`` — live/total
  units + device bytes for ``ServeMetrics.record_kv_usage``.

**Row-layout discovery.** Families stack the slot axis differently (vlm's
grouped self caches batch on axis 2; everything else on axis 1), so the
base class probes ``api.prefill`` via ``jax.eval_shape`` at batch 1 and 2
and records, per state leaf, the one axis that scaled — no family ever
has to register its layout by hand, and a new family that decodes through
``ModelApi`` is continuous-batchable on day one. The per-family
subclasses (``DenseKVState``, ``RecurrentState``, ``HybridState``,
``CrossAttnState``) validate the discovered layout against what the
family contract promises; ``PagedKVState`` swaps the dense K/V leaves for
the shared ``BlockPool`` slab and writes prompt K/V into bucket-covering
blocks directly at insert (paged prefill — no ``max_cache_len``
intermediate).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..dist.sharding import tree_shardings
from ..models.registry import ModelApi
from .paged import (BlockPool, PoolExhausted, PrefixPlan, PREFIX_SEED,
                    blocks_for, prefix_hashes)


def _uncounted(name, fn):
    return fn


def _leaf_paths(tree, prefix=""):
    """Flatten a nested-dict pytree into (path, leaf) pairs."""
    if isinstance(tree, dict):
        out = []
        for k in sorted(tree):
            out += _leaf_paths(tree[k], f"{prefix}{k}.")
        return out
    return [(prefix.rstrip("."), tree)]


class DecodeState:
    """Reference slot-table state: one generic row-insert over discovered
    batch axes. Hosts any family whose decode state is a pytree of arrays
    with exactly one slot axis per leaf."""

    def __init__(self, api: ModelApi, cfg, params, mesh=None,
                 counted=None):
        self.api = api
        self.cfg = cfg                      # SchedulerConfig
        self.params = params
        self.mesh = mesh
        self.data = None
        self.batch = 0
        counted = counted or _uncounted
        self._row_shapes, self._axes = self._probe()
        self._validate()
        self._insert = jax.jit(counted("insert", self._insert_fn))

    # -- layout discovery --------------------------------------------------

    def _probe_batch(self, b: int, bucket: int):
        batch = dict(
            tokens=jax.ShapeDtypeStruct((b, bucket), jnp.int32),
            lengths=jax.ShapeDtypeStruct((b,), jnp.int32))
        for key, shape_fn, dt in self.api.caps.extras:
            batch[key] = jax.ShapeDtypeStruct(
                shape_fn(self.api.cfg, b), jnp.dtype(dt))
        return jax.eval_shape(
            lambda p, bt: self.api.prefill(p, bt)[1], self.params, batch)

    def _probe(self):
        """Row state shapes (batch=1) + per-leaf slot axis, by comparing
        ``eval_shape`` at batch 1 vs 2: the one axis that scales with the
        batch is the slot axis."""
        b0 = self.cfg.buckets[0]
        s1, s2 = self._probe_batch(1, b0), self._probe_batch(2, b0)
        if jax.tree.structure(s1) != jax.tree.structure(s2):
            raise ValueError("prefill state structure depends on batch size")

        def axis_of(a, b):
            diffs = [i for i, (x, y) in enumerate(zip(a.shape, b.shape))
                     if x != y]
            if len(a.shape) != len(b.shape) or len(diffs) != 1:
                raise ValueError(
                    f"cannot identify the slot axis of a state leaf: "
                    f"batch 1 -> {a.shape}, batch 2 -> {b.shape}")
            return diffs[0]

        axes = jax.tree.map(axis_of, s1, s2)
        return s1, axes

    def _validate(self):
        pass

    # -- allocation --------------------------------------------------------

    def _zero_state(self, batch: int):
        def grow(leaf, ax):
            shape = list(leaf.shape)
            shape[ax] = batch
            return jnp.zeros(shape, leaf.dtype)
        return jax.tree.map(grow, self._row_shapes, self._axes)

    def _place(self, state):
        """``repro.dist`` placement: the family's declared state axes when
        the tree matches, else replicated. Either way every leaf lives on
        the mesh, so the jitted insert and decode step see the same input
        types on the first call as on every later one (an array made off
        the mesh has a different type, and would retrace them)."""
        if self.mesh is None:
            return state
        axes_fn = self.api.caps.state_axes
        if axes_fn is not None:
            try:
                return jax.device_put(state, tree_shardings(
                    axes_fn(self.api.cfg), self.api.rules, self.mesh))
            except ValueError:
                pass
        return jax.device_put(state, NamedSharding(self.mesh, P()))

    def init(self, batch: int, budget: int) -> None:
        self.batch = batch
        self.data = self._place(self._zero_state(batch))

    # -- admission / insert / decode / eviction ----------------------------

    def max_positions(self) -> int | None:
        cap = self.api.cfg.max_cache_len
        if cap <= 0:
            raise ValueError(
                f"{type(self).__name__} is position-bounded and needs "
                f"max_cache_len > 0, got {cap}")
        return cap

    def validate_request(self, prompt_len: int, bucket: int,
                         budget: int) -> None:
        pass

    def prefix_plan(self, tokens, budget: int):
        """Prefix-cache admission plan for one request, or None when the
        state does not share prefixes (everything but ``PagedKVState``
        with ``cfg.prefix_cache``)."""
        return None

    def can_admit(self, prompt_len: int, budget: int, plan=None) -> bool:
        return True

    def admit(self, slot: int, prompt_len: int, budget: int,
              plan=None) -> None:
        pass

    def prefill_cache_len(self, cover: int) -> int | None:
        """Static cache length for an admission prefill that must hold
        positions ``0..cover-1`` (= prefill start offset + tail bucket;
        start is 0 without prefix sharing, so this is the bucket length).
        None keeps the family default (``max_cache_len``)."""
        return None

    def prefill_prefix_inputs(self, plan, cache_len: int | None) -> dict:
        """Extra prefill-batch inputs realizing ``plan`` (resident-prefix
        gather spec + tail start offset); empty without a prefix hit."""
        return {}

    def referenced(self, num_active: int) -> int:
        """Total state-unit references across requests (== live units
        unless the state shares blocks between requests)."""
        return self.occupancy(num_active)[0]

    def _insert_fn(self, state, row_state, slot):
        return jax.tree.map(
            lambda c, r, ax: jax.lax.dynamic_update_slice_in_dim(
                c, r.astype(c.dtype), slot, axis=ax),
            state, row_state, self._axes)

    def prefill_insert(self, row_state, slot: int, length: int,
                       bucket: int) -> None:
        self.data = self._insert(self.data, row_state, jnp.int32(slot))

    def decode_view(self, positions, active):
        return self.data

    def commit(self, new_state) -> None:
        self.data = new_state

    def evict(self, slot: int) -> None:
        pass

    # -- metrics -----------------------------------------------------------

    def row_bytes(self) -> int:
        """Device bytes one resident row pins (every state leaf)."""
        return sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                   for _, leaf in _leaf_paths(self._row_shapes))

    def occupancy(self, num_active: int) -> tuple[int, int, int]:
        """(live units, total units, bytes per unit) — one unit = one slot
        row here; ``PagedKVState`` reports pool blocks instead."""
        return num_active, self.batch, self.row_bytes()


class DenseKVState(DecodeState):
    """dense/moe: dict(k, v) caches of ``(L, B, KVH, max_cache_len, Dh)``
    — every row pins a full cache stripe (see ``PagedKVState`` for the
    shared-slab alternative)."""

    def _validate(self):
        if not isinstance(self._row_shapes, dict) or \
                not {"k", "v"} <= set(self._row_shapes):
            raise ValueError(
                f"{type(self).__name__} expects a dict(k, v) decode state, "
                f"got {type(self._row_shapes).__name__} with leaves "
                f"{[p for p, _ in _leaf_paths(self._row_shapes)]}")


class RecurrentState(DecodeState):
    """ssm: O(1) per-row recurrent state (conv tails + SSM heads), no
    position bound — ``max_positions`` is None, so a request's budget is
    limited only by its token budget."""

    def max_positions(self) -> int | None:
        return None


class HybridState(DecodeState):
    """hybrid: Mamba recurrent rows + the shared attention block's
    per-invocation KV stack; the KV part keeps the ``max_cache_len``
    position bound."""

    def _validate(self):
        if not isinstance(self._row_shapes, dict) or \
                "mamba" not in self._row_shapes:
            raise ValueError(
                f"HybridState expects a dict with a 'mamba' sub-state, got "
                f"{[p for p, _ in _leaf_paths(self._row_shapes)]}")


class CrossAttnState(DecodeState):
    """encdec/vlm: self-attention KV plus a frozen per-row cross-attention
    stack (encoder output K/V), resident for the row's whole lifetime —
    the cross stack batches on its own axis per leaf (vlm's grouped self
    caches sit at axis 2), which the probed axes tree absorbs."""

    def _validate(self):
        if not self.api.caps.extras:
            raise ValueError(
                "CrossAttnState expects per-request encoder inputs "
                "(caps.extras); none declared for family "
                f"{self.api.cfg.family!r}")


class PagedKVState(DenseKVState):
    """dense/moe paged mode: the per-row K/V stripes are replaced by one
    shared ``BlockPool`` slab + per-row block tables. Admission reserves a
    request's worst case up front, allocation is lazy per block boundary,
    and **prefill is paged**: the admission prefill runs against a
    bucket-covering cache (``blocks_for(bucket) * block_size`` positions,
    not ``max_cache_len``) and its K/V blocks are scattered straight into
    the pool — the only dense intermediate is the prompt-sized K/V that
    flash attention needs anyway.

    With ``cfg.prefix_cache`` admission first consults the pool's chained
    content-hash registry (``prefix_plan``): prompt blocks already
    resident under an identical prefix are mapped copy-free (refcount
    bump, reservation shrinks by the match), a partially-covered boundary
    block is **copied** out of its donor before anything is written
    (copy-on-write — a shared block is never scattered into), and the
    admission prefill computes only the divergent tail: the matched
    prefix K/V is gathered from the slab into the prefill cache and the
    model runs from ``start`` with RoPE positions offset accordingly. The
    last prompt token is always re-prefilled (its logits sample token 0),
    so a full-prompt match still runs a one-token tail."""

    def __init__(self, api, cfg, params, mesh=None, counted=None):
        if api.cfg.max_cache_len % cfg.block_size != 0:
            raise ValueError(
                f"block_size={cfg.block_size} must divide "
                f"max_cache_len={api.cfg.max_cache_len}")
        self._max_blocks = api.cfg.max_cache_len // cfg.block_size
        num_blocks = (cfg.batch * self._max_blocks
                      if cfg.num_blocks is None else cfg.num_blocks)
        self.pool = BlockPool.for_model(
            api.cfg, num_blocks=num_blocks, block_size=cfg.block_size,
            overcommit=getattr(cfg, "overcommit", 1.0),
            debug=getattr(cfg, "debug", False))
        super().__init__(api, cfg, params, mesh=mesh, counted=counted)

    def _validate(self):
        super()._validate()
        if not self.api.caps.paged:
            raise ValueError(
                f"family {self.api.cfg.family!r} does not support the "
                "paged KV slab (caps.paged); its state keeps the dense "
                "layout")
        nested = [k for k, v in self._row_shapes.items()
                  if isinstance(v, dict)]
        if nested:
            raise ValueError(
                "paged KV expects a flat dict(k, v, ...) decode state; "
                f"nested sub-states {nested} keep the dense layout")
        for key in ("k", "v"):
            leaf, ax = self._row_shapes[key], self._axes[key]
            if len(leaf.shape) != 5 or ax != 1:
                raise ValueError(
                    f"paged KV expects (L, B, KVH, S, Dh) '{key}' leaves "
                    f"with the slot axis at 1, got {leaf.shape} axis {ax}")

    def init(self, batch: int, budget: int) -> None:
        self.batch = batch
        self._blocks: list[list[int]] = [[] for _ in range(batch)]
        self._reserved = np.zeros(batch, np.int32)
        self._shared = np.zeros(batch, np.int32)   # leading shared blocks
        self._table = np.zeros((batch, self._max_blocks), np.int32)
        state = dict(self.pool.init_slab())
        for path, leaf in _leaf_paths(self._row_shapes):
            if path in ("k", "v"):
                continue
            shape = list(leaf.shape)
            shape[self._axes[path]] = batch
            state[path] = jnp.zeros(shape, leaf.dtype)
        state["table"] = jnp.asarray(self._table)
        self.data = self._place_paged(state)

    def _place_paged(self, state):
        if self.mesh is None:
            return state
        from ..models import layers as L
        try:
            axes = dict(L.paged_kv_cache_axes(),
                        **{k: None for k in state if k not in ("k", "v")})
            return jax.device_put(
                state, tree_shardings(axes, self.api.rules, self.mesh))
        except ValueError:
            return jax.device_put(state, NamedSharding(self.mesh, P()))

    # -- admission ---------------------------------------------------------

    def validate_request(self, prompt_len: int, bucket: int,
                         budget: int) -> None:
        need = self.pool.blocks_needed(prompt_len, budget)
        if need > self.pool.capacity:
            raise ValueError(
                f"prompt length {prompt_len} (bucket {bucket}) + budget "
                f"{budget} requires {need} KV blocks of "
                f"{self.pool.block_size} tokens, but the pool holds "
                f"only {self.pool.capacity} blocks total")

    def prefix_plan(self, tokens, budget: int) -> PrefixPlan | None:
        """Match the prompt against the pool's chained-hash registry.

        Pure planning — no pool side effects (the scheduler may still
        drop the request if it terminates at admission); ``admit``
        realizes the plan. Matching walks leading *full* prompt blocks
        through ``lookup`` but never past ``(prompt_len - 1) //
        block_size``: the block holding the last prompt token is always
        owned and re-prefilled (its logits sample token 0, and sharing it
        would mean writing a block another request references). When every
        block before that boundary matched, a resident donor covering the
        boundary tokens (an aligned full block, or a registered block
        extending the matched chain) is recorded for copy-on-write."""
        if not getattr(self.cfg, "prefix_cache", False):
            return None
        toks = np.asarray(tokens, np.int32).reshape(-1)
        n, bs = len(toks), self.cfg.block_size
        hashes = prefix_hashes(toks, bs)
        limit = (n - 1) // bs             # first block the request writes
        shared: list[int] = []
        while len(shared) < min(len(hashes), limit):
            blk = self.pool.lookup(hashes[len(shared)])
            if blk is None:
                break
            shared.append(blk)
        m = len(shared)
        cow = None
        if m == limit and m * bs < n - 1:
            if m < len(hashes):           # boundary is itself a full block
                cow = self.pool.lookup(hashes[m])
            if cow is None:
                parent = hashes[m - 1] if m else PREFIX_SEED
                cow = self.pool.find_extension(parent, toks[m * bs:n - 1])
        start = n - 1 if cow is not None else m * bs
        return PrefixPlan(shared=shared, cow=cow, start=start,
                          hashes=hashes, tokens=toks)

    def can_admit(self, prompt_len: int, budget: int, plan=None) -> bool:
        m = len(plan.shared) if plan is not None else 0
        # shared blocks are already resident: they shrink both the
        # worst-case reservation and the prompt blocks taken at admission
        need = self.pool.blocks_needed(prompt_len, budget) - m
        own_now = blocks_for(prompt_len, self.cfg.block_size) - m
        # under over-commit the reservation gate alone is not enough: the
        # prompt's own blocks are taken *at admission*, so they must exist
        # on the free list right now (admission never preempts — only
        # mid-decode growth does)
        return self.pool.can_reserve(need) and self.pool.free_blocks >= own_now

    def admit(self, slot: int, prompt_len: int, budget: int,
              plan=None) -> None:
        bs = self.cfg.block_size
        shared = list(plan.shared) if plan is not None else []
        m = len(shared)
        # reservation covers only blocks this request will own: the shared
        # prefix is resident already, so its capacity is counted once
        need = self.pool.blocks_needed(prompt_len, budget) - m
        self.pool.reserve(need)
        self._reserved[slot] = need
        for blk in shared:
            self.pool.share(blk)
        ids = shared + [self.pool.take()
                        for _ in range(blocks_for(prompt_len, bs) - m)]
        self._blocks[slot] = ids
        self._shared[slot] = m
        self._table[slot, :] = 0
        self._table[slot, :len(ids)] = ids
        if plan is not None:
            # publish this request's owned full prompt blocks for future
            # sharers (first registration of a hash wins)
            for j in range(m, len(plan.hashes)):
                parent = plan.hashes[j - 1] if j else PREFIX_SEED
                self.pool.register(plan.hashes[j], parent, ids[j],
                                   plan.tokens[j * bs:(j + 1) * bs])

    # -- paged prefill insert ----------------------------------------------

    def prefill_cache_len(self, cover: int) -> int | None:
        """Block-covering cache for the admission prefill: the row K/V
        comes back already block-shaped, so the insert is a pure scatter
        into the pool (the ROADMAP "paged prefill" item). ``cover`` is
        prefill start + tail bucket — just the bucket length without
        prefix sharing."""
        return blocks_for(cover, self.cfg.block_size) * self.cfg.block_size

    def prefill_prefix_inputs(self, plan, cache_len: int | None) -> dict:
        """Prefill-batch inputs that realize a prefix hit: the tail start
        offset plus the block ids whose slab content is gathered into the
        prefill cache before the model runs (shared prefix, then the COW
        donor — gathering the donor and scattering the boundary back into
        an *owned* block is the copy-on-write duplication)."""
        if plan is None or (not plan.shared and plan.cow is None):
            return {}
        nb = cache_len // self.cfg.block_size
        ids = np.zeros(nb, np.int32)
        ids[:len(plan.shared)] = plan.shared
        if plan.cow is not None:
            ids[len(plan.shared)] = plan.cow
        return dict(start=jnp.int32(plan.start),
                    prefix_ids=jnp.asarray(ids),
                    pool_k=self.data["k"], pool_v=self.data["v"])

    def _insert_fn(self, state, row_state, slot, ids):
        """Scatter a prefilled row into the shared slab: K/V go to the
        blocks in ``ids`` (bucket-covering; trailing ids may be 0 = trash
        for all-pad blocks), any other state leaves (stub counters etc.)
        keep the generic row insert."""
        nb = ids.shape[0]
        bs = self.cfg.block_size
        out = dict(state)
        for key in ("k", "v"):
            slab, row = state[key], row_state[key]
            lyr, _, kvh, pos, hd = row.shape          # pos == nb * bs
            blocks = row[:, 0, :, :nb * bs, :].reshape(
                lyr, kvh, nb, bs, hd).transpose(0, 2, 1, 3, 4)
            out[key] = slab.at[:, ids].set(blocks.astype(slab.dtype))
        for path, _ in _leaf_paths(self._row_shapes):
            if path in ("k", "v"):
                continue
            out[path] = jax.lax.dynamic_update_slice_in_dim(
                state[path], row_state[path].astype(state[path].dtype),
                slot, axis=self._axes[path])
        return out

    def prefill_insert(self, row_state, slot: int, length: int,
                       bucket: int) -> None:
        ids = self._blocks[slot]
        # the returned row cache is block-shaped by construction; its own
        # position extent (cache_len, = cover for prefix tails) names the
        # scatter width — shared prefix blocks scatter to the trash block
        # so a block another request references is never written
        nb = row_state["k"].shape[3] // self.cfg.block_size
        m = int(self._shared[slot])
        bucket_ids = np.zeros(nb, np.int32)
        bucket_ids[m:len(ids)] = ids[m:]
        self.data = self._insert(self.data, row_state, jnp.int32(slot),
                                 jnp.asarray(bucket_ids))

    # -- decode / eviction -------------------------------------------------

    def decode_view(self, positions, active):
        """Lazy table growth: map a fresh block the moment a row's write
        position crosses into it, then refresh the device table copy —
        same shape every step, so the jitted decode never retraces. With
        honest reservations (overcommit=1.0) ``take`` always succeeds;
        under over-commit it may raise ``PoolExhausted``, which propagates
        to the scheduler's preempt-and-retry loop — safe because rows
        already grown this call just pass the length check on retry and
        ``take`` raises before touching pool state."""
        for slot in np.flatnonzero(active):
            b_idx = int(positions[slot]) // self.cfg.block_size
            if b_idx >= len(self._blocks[slot]):
                blk = self.pool.take()
                self._blocks[slot].append(blk)
                self._table[slot, b_idx] = blk
        self.data["table"] = jnp.asarray(self._table)
        return self.data

    def evict(self, slot: int) -> None:
        """Drop one reference per mapped block (shared blocks survive for
        their other sharers; blocks reaching refcount 0 return to the free
        list) and cancel the unused tail of the reservation — which only
        ever covered *owned* blocks, so the shared count is excluded."""
        m = int(self._shared[slot])
        owned = len(self._blocks[slot]) - m
        self.pool.free(self._blocks[slot])
        self.pool.cancel(int(self._reserved[slot]) - owned)
        self._blocks[slot] = []
        self._reserved[slot] = 0
        self._shared[slot] = 0
        self._table[slot, :] = 0     # dead-row writes -> trash block
        if self.pool.debug:
            self.pool.check_invariants()

    # -- metrics -----------------------------------------------------------

    def occupancy(self, num_active: int) -> tuple[int, int, int]:
        """live counts *unique* resident blocks: a block shared by five
        requests pins its bytes once — that is the whole point."""
        return (self.pool.live_blocks, self.pool.capacity,
                self.pool.block_bytes)

    def referenced(self, num_active: int) -> int:
        return self.pool.referenced_blocks


_KINDS = {
    "kv": DenseKVState,
    "recurrent": RecurrentState,
    "hybrid": HybridState,
    "cross": CrossAttnState,
}


def make_decode_state(api: ModelApi, cfg, params, mesh=None,
                      counted=None) -> DecodeState:
    """Resolve the family's ``DecodeState`` implementation from its
    registry capability flags. Unknown families fail loudly — there is no
    fixed-batch fallback to hide behind anymore."""
    caps = getattr(api, "caps", None)
    if caps is None or caps.state_kind not in _KINDS:
        kind = None if caps is None else caps.state_kind
        raise ValueError(
            f"unknown serving family {api.cfg.family!r} (state kind "
            f"{kind!r}); known kinds: {sorted(_KINDS)} — declare "
            "ServeCaps in models/registry.py for new families")
    if getattr(cfg, "prefix_cache", False) and not cfg.paged:
        raise ValueError(
            "prefix_cache=True requires paged=True: prefix sharing maps "
            "resident pool blocks into new requests' block tables, which "
            "only exist in paged mode")
    overcommit = getattr(cfg, "overcommit", 1.0)
    if overcommit < 1.0:
        raise ValueError(
            f"overcommit must be >= 1.0, got {overcommit}")
    if overcommit > 1.0 and not cfg.paged:
        raise ValueError(
            "overcommit > 1.0 requires paged=True: only the block pool "
            "can admit past its worst-case reservation and preempt on "
            "exhaustion — dense rows are pinned for a request's lifetime")
    if cfg.paged:
        if not caps.paged:
            raise ValueError(
                f"paged KV serves caps.paged families only; family "
                f"{api.cfg.family!r} ({caps.state_kind}) keeps its own "
                "state layout")
        return PagedKVState(api, cfg, params, mesh=mesh, counted=counted)
    return _KINDS[caps.state_kind](api, cfg, params, mesh=mesh,
                                   counted=counted)
