"""Blocked alphabet histogram — Pallas TPU kernel.

The daily dictionary/count job (§4.2) reduced to hardware terms: scatter-add
histograms are hostile to the VPU (serialized RMW), so the TPU-native
formulation is compare-and-reduce — for an alphabet tile A and a symbol tile
S, counts[a] += sum_s (S == a), an (|S| x |A|) broadcast compare reduced
over symbols. All tiles live in VMEM; the alphabet axis is the innermost
sequential grid dim so each symbol tile is read once per alphabet tile.

Grid = (alphabet/block_a, N/block_n); out tile (block_a,) accumulates across
the sequential n axis. Both 1-D tiles are 1024 wide: XLA lays a 1-D int32
array out in tiles of 1024, and a Pallas block of another width gets a
layout the TPU compiler refuses to bridge. The alphabet and the symbol
stream are padded up to whole tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE = 1024  # XLA's tile of a 1-D int32 array on TPU


def _hist_kernel(sym_ref, out_ref, *, block_a: int):
    ia = pl.program_id(0)
    in_ = pl.program_id(1)

    @pl.when(in_ == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    sym = sym_ref[...]                                   # (block_n,) int32
    base = ia * block_a
    # (block_n, block_a) compare; invalid positions were pre-mapped to -1.
    a = base + jax.lax.broadcasted_iota(jnp.int32, (sym.shape[0], block_a), 1)
    eq = (sym[:, None] == a).astype(jnp.int32)
    out_ref[...] += jnp.sum(eq, axis=0)


def histogram_pallas(symbols_flat, *, alphabet_size: int,
                     interpret: bool = False):
    """symbols_flat: (N,) int32 with invalid positions = -1."""
    n = symbols_flat.shape[0]
    pad_n = (-n) % TILE
    if pad_n or n == 0:
        symbols_flat = jnp.pad(symbols_flat, (0, pad_n or TILE),
                               constant_values=-1)
    a_total = alphabet_size + (-alphabet_size) % TILE

    out = pl.pallas_call(
        functools.partial(_hist_kernel, block_a=TILE),
        grid=(a_total // TILE, symbols_flat.shape[0] // TILE),
        in_specs=[pl.BlockSpec((TILE,), lambda ia, in_: (in_,))],
        out_specs=pl.BlockSpec((TILE,), lambda ia, in_: (ia,)),
        out_shape=jax.ShapeDtypeStruct((a_total,), jnp.int32),
        interpret=interpret,
    )(symbols_flat)
    return out[:alphabet_size]
