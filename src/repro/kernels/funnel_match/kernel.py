"""Funnel stage-automaton — Pallas TPU kernel.

TPU adaptation of the paper's regex-over-strings funnel UDF (§5.3),
decomposed as: (a) an embarrassingly-parallel gather turning each symbol
into a per-stage *match bitmask* (left to XLA — it fuses with upstream
ops), and (b) the inherently sequential automaton advance over positions —
this kernel.

The kernel holds an (L, block_s) tile of bitmasks in VMEM — positions on
sublanes, sessions on lanes — and advances the per-session stage vector
``k`` with a fori_loop: ``k += (bits[t] >> k) & 1`` — one vectorized
variable-shift per position, zero HBM traffic beyond the single tile read.
Position ``t`` is a dynamic row read of the ref; the TPU lowering has no
dynamic slice along the lane axis, so sessions, not positions, sit there.
Grid is 1-D over session blocks; sessions are independent so blocks
parallelize freely.

VMEM: block_s=256, L=2048 -> 2MB int32 tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def _funnel_kernel(bits_ref, out_ref, *, seq_len: int):
    def body(t, k):
        return k + ((bits_ref[pl.ds(t, 1), :] >> k) & 1)   # (1, block_s)

    out_ref[...] = jax.lax.fori_loop(
        0, seq_len, body, jnp.zeros(out_ref.shape, jnp.int32))


def deepest_stage_pallas(match_bits, *, block_s: int = 256,
                         interpret: bool = False):
    """(S, L) int32 bitmasks -> (S,) deepest stage reached."""
    if block_s % LANES:
        raise ValueError(f"block_s={block_s} must be a multiple of {LANES}")
    s, l = match_bits.shape
    sp = max(s + (-s) % block_s, block_s)
    bits_t = jnp.pad(match_bits, ((0, sp - s), (0, 0))).T   # (L, Sp)

    out = pl.pallas_call(
        functools.partial(_funnel_kernel, seq_len=l),
        grid=(sp // block_s,),
        in_specs=[pl.BlockSpec((l, block_s), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, block_s), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, sp), jnp.int32),
        interpret=interpret,
    )(bits_t)
    return out[0, :s]
