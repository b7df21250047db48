"""Jit'd public wrapper for flash attention.

``flash_attention(..., impl=...)``:
* ``"pallas"``    — TPU Pallas kernel (kernel.py);
* ``"interpret"`` — same kernel, Pallas interpret mode (CPU validation);
* ``"ref"``       — pure-jnp oracle (ref.py); the dry-run/compile path.

Gradients flow through a recompute-based custom_vjp: the backward pass
re-derives attention from the oracle formulation (flash backward recomputes
p block-wise on TPU anyway; on this CPU container the oracle *is* the
backward). This keeps the Pallas surface forward-only while training end to
end — documented in DESIGN.md §Hardware-adaptation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import flash_attention_fwd, paged_decode_attention_fwd
from .ref import attention_ref, attention_blocked, paged_attention_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_pallas(q, k, v, causal, scale, kv_len, q_offset, interpret):
    return flash_attention_fwd(q, k, v, causal=causal, scale=scale,
                               kv_len=kv_len, q_offset=q_offset,
                               interpret=interpret)


def _flash_fwd_rule(q, k, v, causal, scale, kv_len, q_offset, interpret):
    out = _flash_pallas(q, k, v, causal, scale, kv_len, q_offset, interpret)
    return out, (q, k, v)


def _flash_bwd_rule(causal, scale, kv_len, q_offset, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: attention_ref(
            q_, k_, v_, causal=causal, scale=scale, kv_len=kv_len,
            q_offset=q_offset), q, k, v)
    return vjp(g)


_flash_pallas.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, *, causal: bool = True,
                    scale: float | None = None, kv_len=None,
                    q_offset=0, impl: str = "ref", unroll: bool = False):
    """GQA attention. q: (B, H, Lq, D); k, v: (B, KVH, Lk, D).

    ``impl="ref"`` accepts traced kv_len/q_offset (the decode path);
    the Pallas impls require them static (training/prefill shapes).
    Per-row (B,)-shaped kv_len/q_offset — the continuous-batching decode
    path, Lq == 1 — is the oracle's alone: the Pallas kernel's masking is
    scalar-only, so the Pallas impls refuse it (paged decode has its own
    kernel, ``paged_decode_attention``).
    ``unroll`` unrolls the blocked impl's k-scan (cost-mode compiles).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    per_row = (kv_len is not None and jnp.ndim(kv_len) >= 1) or \
        jnp.ndim(q_offset) >= 1
    if per_row:
        if q.shape[2] != 1:
            raise ValueError(
                "per-row kv_len/q_offset is single-token decode only "
                f"(got Lq={q.shape[2]}); ragged prefill uses scalar "
                "kv_len with per-row logit reads instead")
        if impl in ("pallas", "interpret"):
            raise ValueError(
                f"impl={impl!r} masks with a scalar kv_len only; per-row "
                "decode runs impl='ref' or the paged decode kernel")
        return attention_ref(q, k, v, causal=causal, scale=scale,
                             kv_len=kv_len, q_offset=q_offset)
    if impl == "ref":
        return attention_ref(q, k, v, causal=causal, scale=scale,
                             kv_len=kv_len, q_offset=q_offset)
    if impl == "blocked":
        if q.shape[2] == 1:   # decode: single-row scores are already cheap
            return attention_ref(q, k, v, causal=causal, scale=scale,
                                 kv_len=kv_len, q_offset=q_offset)
        return attention_blocked(q, k, v, causal=causal, scale=scale,
                                 kv_len=kv_len, q_offset=q_offset,
                                 unroll=unroll)
    if impl not in ("pallas", "interpret"):
        raise ValueError(
            f"unknown flash-attention impl {impl!r}; expected "
            "'ref' | 'blocked' | 'interpret' | 'pallas'")
    return _flash_pallas(q, k, v, causal, float(scale), kv_len, q_offset,
                         impl == "interpret")


def paged_decode_attention(q, k_pool, v_pool, block_table, kv_len, *,
                           scale: float | None = None, impl: str = "ref"):
    """Block-sparse decode attention through a paged KV pool.

    q: (B, H, 1, D); k_pool/v_pool: (N, KVH, bs, D);
    block_table: (B, max_blocks) int32; kv_len: (B,) int32 per-row valid
    length (the query sits at ``kv_len - 1``).

    ``impl="ref"``/``"blocked"`` gather through the table and run the
    per-row oracle — bit-equal to the dense decode path by construction.
    ``"interpret"``/``"pallas"`` run the Pallas kernel, which tiles over
    blocks via scalar-prefetched index maps and never materializes the
    gather. Forward-only (decode never differentiates).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if q.shape[2] != 1 or block_table.ndim != 2 or jnp.ndim(kv_len) != 1:
        raise ValueError(
            "paged decode attention is per-row single-token only: "
            f"got Lq={q.shape[2]}, table ndim={block_table.ndim}, "
            f"kv_len ndim={jnp.ndim(kv_len)}")
    if impl in ("pallas", "interpret"):
        return paged_decode_attention_fwd(
            q, k_pool, v_pool, block_table, kv_len, scale=float(scale),
            interpret=impl == "interpret")
    if impl not in ("ref", "blocked"):
        raise ValueError(
            f"unknown paged-attention impl {impl!r}; expected "
            "'ref' | 'blocked' | 'interpret' | 'pallas'")
    return paged_attention_ref(q, k_pool, v_pool, block_table, kv_len,
                               scale=scale)
